"""The trace reduction on a window recorded on a TPU v5e.

``data/paper_m5-poisson`` holds one traced window of that cell as the chip
recorded it (`perfbench/record_trace.py`): the profiler's device operations
that overlap the window and the clock marker (the rest of the trace cut
away; nothing the reduction reads), the planner's event tape, and the
result line the chip printed. Reducing it here must give that result's
per-layer numbers and device busy time.
"""
import gzip
import json
import os

import pytest

from harness import layers, manifest, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = sorted(os.listdir(DATA)) if os.path.isdir(DATA) else []


def _window(path):
    with open(os.path.join(path, "window.json")) as f:
        meta = json.load(f)
    with gzip.open(os.path.join(path, "window.xplane.pb.gz")) as f:
        dt = trace.read(f.read(), meta["marker_mono_ns"])
    with open(os.path.join(path, "result.json")) as f:
        result = json.load(f)
    w = layers.Window(meta["t0"], meta["t1"], meta["events"],
                      meta["max_batch"], meta["shared"], meta["grid"],
                      manifest.peaks(result["device"]["kind"]), dt)
    return w, result


@pytest.mark.parametrize("cell", CELLS)
def test_reduction_reproduces_the_chip_result(cell):
    w, result = _window(os.path.join(DATA, cell))
    assert result["correct"]
    assert w.busy_s() == pytest.approx(result["device"]["busy_s"], rel=1e-9)
    assert result["metrics"]
    for name, m in result["metrics"].items():
        got = manifest.metric_reader(name).read(w)
        assert got == pytest.approx(m["value"], rel=1e-9), name


@pytest.mark.parametrize("cell", CELLS)
def test_kernel_calls_are_the_solves_sweeps(cell):
    w, _ = _window(os.path.join(DATA, cell))
    solves = w.solves()
    assert solves
    for s in solves:
        ops = w.kernel_ops(s.start, s.end)
        # one decode per SA sweep plus the initial energies
        assert len(ops) == 601
        chains, padded, M = layers.call_shape(ops[0])
        assert (chains, padded, M) == (256 * s.bucket, 8, 4)
        assert all(o.end <= s.end and o.start >= s.start for o in ops)


@pytest.mark.parametrize("cell", CELLS)
def test_breakdown_names_what_the_host_did_in_each_gap(cell):
    from harness import bench
    w, result = _window(os.path.join(DATA, cell))
    b = bench._breakdown(w)
    assert [n for n, _ in b["device_ops"]] == \
        [n for n, _ in result["breakdown"]["device_ops"]]
    assert 0 < len(b["idle_gaps"]) <= 10
    kinds = ("inside a solve", "queued or in set-up", "no request in flight")
    assert all(any(k in n for k in kinds) for n, _ in b["idle_gaps"])
    # the window opens with requests submitted and waiting for their solve
    assert b["idle_gaps"][0][0].startswith("host: requests queued")
