"""The profiler's trace, reduced to device operations on the host's clock.

``start`` turns the JAX profiler on (Python tracing off: it would slow the
host it measures) and writes a marker span at a known ``time.monotonic``
instant; ``read`` finds the marker in the host plane and puts every
device operation of the ``XLA Ops`` lines on the same monotonic clock as
the planner's event tape and the load generator.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import time
from typing import Dict, List, Optional, Tuple

MARKER = "perfbench_clock_marker"
_SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")


@dataclasses.dataclass(slots=True)
class Op:
    start: float                    # monotonic seconds
    end: float
    name: str                       # HLO instruction name, e.g. %fusion.12
    text: str                       # the whole HLO line, kept for kernels
    device: int


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Op]
    devices: int


def start(log_dir: str) -> int:
    """Start tracing into ``log_dir``; returns the marker's monotonic ns."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    mono = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(MARKER):
        pass
    return mono


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def xplane_path(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read(path: str, marker_mono_ns: int, kernel: str = "sgs_decode"
         ) -> DeviceTrace:
    """Device operations of an ``.xplane.pb`` file (or of its bytes), on
    the monotonic clock whose reading at the marker was ``marker_mono_ns``.
    Only operations whose name holds ``kernel`` keep their HLO text."""
    from jax.profiler import ProfileData
    if isinstance(path, bytes):
        pd = ProfileData.from_serialized_xspace(path)
    else:
        pd = ProfileData.from_file(path)
    marker_ns: Optional[float] = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:") and marker_ns is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER:
                        marker_ns = ev.start_ns
                        break
    if marker_ns is None:
        raise ValueError("the trace holds no clock marker")
    shift = marker_mono_ns - marker_ns
    ops: List[Op] = []
    names: Dict[str, Tuple[str, str]] = {}
    for d, plane in enumerate(devices):
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                full = ev.name
                if full not in names:
                    short = full.split(" = ", 1)[0]
                    names[full] = (short, full if kernel in short else "")
                name, text = names[full]
                ops.append(Op((ev.start_ns + shift) / 1e9,
                              (ev.start_ns + ev.duration_ns + shift) / 1e9,
                              name, text, d))
    ops.sort(key=lambda o: o.start)
    return DeviceTrace(ops, len(devices))


def shapes(text: str) -> List[Tuple[int, ...]]:
    """Every array shape in an HLO line, in order: results first, then the
    operands."""
    return [tuple(int(x) for x in m.group(1).split(",") if x)
            for m in _SHAPE.finditer(text)]
