"""Per-layer readers on synthetic windows: the arithmetic of each metric,
independent of a recorded trace."""
import pytest

from harness import layers, manifest
from harness.trace import DeviceTrace, Op

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _kernel(start, end, chains_shape, J_pad, M):
    dims = ",".join(str(d) for d in chains_shape)
    text = (f"%sgs_decode.3 = (f32[{dims},1,{J_pad}]{{3,2,1,0}}, "
            f"f32[{dims},1,{J_pad}], f32[{dims},1,{J_pad}]) custom-call("
            f"f32[{dims},1,{J_pad}] %a, f32[{dims},{M},{J_pad}] %b)")
    return Op(start, end, "%sgs_decode.3", text, 0)


def _window(ops, events, shared=False):
    return layers.Window(10.0, 20.0, events, 8, shared, 256, PEAKS,
                         DeviceTrace(ops, 1))


def _solve(end, seconds, n, bucket, jmax, ids):
    return [{"type": "cache_hit", "ts": end, "data": {
                "bucket": bucket, "jmax": jmax, "omax": 6, "trace_ids": ids}},
            {"type": "plan_solved", "ts": end, "data": {
                "kind": "plan", "n": n, "bucket": bucket, "seconds": seconds,
                "trace_ids": ids}}]


def read(name, w):
    return manifest.metric_reader(name).read(w)


def test_front_wait_batch_fill_and_host_time():
    ev = ([{"type": "submit", "ts": 11.0, "trace_id": "a"},
           {"type": "submit", "ts": 11.5, "trace_id": "b"},
           {"type": "submit", "ts": 12.0, "trace_id": "c"},
           {"type": "flush", "ts": 12.1, "data": {"n": 2}},
           {"type": "flush", "ts": 15.1, "data": {"n": 1}}]
          + _solve(14.0, 2.0, 2, 2, 7, ["a", "b"])
          + _solve(17.0, 1.0, 1, 1, 7, ["c"]))
    # device busy 12.5-13.5 in the first solve, 16.2-16.8 in the second
    ops = [_kernel(12.5, 13.5, (2, 256), 8, 4), _kernel(16.2, 16.8, (256,),
                                                        8, 4)]
    w = _window(ops, ev)
    assert read("front_wait_s.poisson", w) == pytest.approx(1.0)  # 1, .5, 4
    assert read("batch_fill.poisson", w) == pytest.approx(1.5 / 8)
    assert read("host_s_per_plan.poisson", w) == pytest.approx(
        ((2.0 - 1.0) + (1.0 - 0.6)) / 3)
    assert read("sgs_decode_ms_per_plan.backlog", w) == pytest.approx(
        1e3 * 1.6 / 3)
    assert read("device_idle.backlog", w) == pytest.approx(
        100 * (1 - 1.6 / 10))


def test_roofline_counts_real_slots_not_padding():
    ev = _solve(14.0, 3.0, 8, 8, 7, ["x"])
    op = _kernel(12.0, 12.001, (8, 256), 8, 4)
    w = _window([op], ev)
    ops, nbytes = layers.decode_work(8 * 256, 7, 4, 256)
    want = max(ops / PEAKS["flops_per_s"], nbytes / PEAKS["hbm_bytes_per_s"])
    assert read("sgs_decode_roofline.backlog", w) == pytest.approx(
        100 * want / 0.001)
    assert nbytes / PEAKS["hbm_bytes_per_s"] > ops / PEAKS["flops_per_s"]


def test_roofline_of_a_joint_decode_counts_bucket_times_widest():
    ev = _solve(14.0, 3.0, 8, 8, 14, ["x"])
    op = _kernel(12.0, 12.002, (256,), 112, 2)
    w = _window([op], ev, shared=True)
    t, bound = layers.least_time(*layers.decode_work(256, 112, 2, 256), PEAKS)
    assert read("sgs_decode_roofline.backlog", w) == pytest.approx(
        100 * t / 0.002)
    assert layers.call_shape(op) == (256, 112, 2)


def test_readers_return_nothing_without_their_source():
    w = layers.Window(0.0, 1.0, [], 8, False, 256, PEAKS, None)
    for name in ("host_s_per_plan.backlog", "sgs_decode_ms_per_plan.backlog",
                 "sgs_decode_roofline.backlog", "device_idle.backlog",
                 "front_wait_s.poisson", "batch_fill.poisson"):
        assert read(name, w) is None
    empty = _window([], [])
    assert read("sgs_decode_roofline.backlog", empty) is None
    assert read("sgs_decode_ms_per_plan.backlog", empty) is None


def test_solves_outside_the_window_are_left_out():
    ev = _solve(10.5, 1.0, 1, 1, 7, ["early"]) + _solve(
        20.5, 1.0, 1, 1, 7, ["late"]) + _solve(15.0, 1.0, 1, 1, 7, ["in"])
    w = _window([], ev)
    assert [s.end for s in w.solves()] == [15.0]
