import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# NOTE: no xla_force_host_platform_device_count here — smoke tests and
# benches must see 1 device. Only launch/dryrun.py sets placeholder devices.

import pytest


@pytest.fixture(scope="session")
def mesh11():
    """Trivial (1,1) mesh with production axis names for smoke tests."""
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))
