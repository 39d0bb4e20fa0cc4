"""Per-layer readers of the planner's host spans (``span`` events) and the
flush timer's lateness, on synthetic windows and on one window of
``alibaba-backlog`` recorded on a TPU v5e.

``spans_data/alibaba-backlog`` holds that window as
``perfbench/record_trace.py`` kept it (the profiler's device operations
that overlap the window and the clock marker, the rest cut away; the
event tape; the result line the chip printed). It lives apart from
``data/``, whose tests assume the paper cluster's decode shape.
"""
import gzip
import json
import os

import pytest

from harness import layers, manifest, trace
from harness.trace import DeviceTrace, Op

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SPANS_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "spans_data")
NEW = ("prepare_ms_per_plan", "pack_ms_per_plan", "recheck_ms_per_plan",
       "codec_ms_per_plan", "flush_late_ms", "idle_in_flight")


def read(name, w):
    return manifest.metric_reader(name).read(w)


def _span(name, end, secs, ids):
    return {"type": "span", "ts": end,
            "data": {"name": name, "seconds": secs, "trace_ids": ids}}


def _batch(end, seconds, ids, prep, pack, device, recheck, select=None):
    """One served batch: its plan_solved event and the solve spans that
    tile it, the prepare span right before it."""
    start = end - seconds
    ev = [{"type": "plan_solved", "ts": end, "data": {
        "kind": "plan", "n": len(ids), "bucket": len(ids),
        "seconds": seconds, "trace_ids": ids}},
          _span("solve.prepare", start, prep, ids)]
    t = start
    for name, secs in (("solve.pack", pack), ("solve.device", device),
                       ("solve.select", select), ("solve.recheck", recheck)):
        if secs is not None:
            t += secs
            ev.append(_span(name, t, secs, ids))
    return ev


def _request(tid, submit, decode_end, decode, encode_end, encode):
    return [{"type": "submit", "ts": submit, "trace_id": tid},
            _span("http.decode", decode_end, decode, [tid]),
            _span("http.encode", encode_end, encode, [tid])]


def _window(events, ops=()):
    return layers.Window(10.0, 20.0, events, 8, False, 256, PEAKS,
                         DeviceTrace(list(ops), 1))


def _op(start, end):
    return Op(start, end, "%fusion.1", "", 0)


def test_solve_span_readers_sum_over_the_windows_solves_per_plan():
    ev = (_batch(14.0, 2.0, ["a", "b"], 0.002, 0.010, 1.980, 0.010)
          + _batch(17.0, 1.0, ["c"], 0.001, 0.004, 0.990, 0.006)
          # began before the window opened: left out
          + _batch(10.5, 1.0, ["z"], 0.5, 0.5, 0.0, 0.5))
    w = _window(ev)
    for suffix in ("poisson", "backlog"):
        assert read(f"prepare_ms_per_plan.{suffix}", w) == pytest.approx(
            1e3 * 0.003 / 3)
        assert read(f"pack_ms_per_plan.{suffix}", w) == pytest.approx(
            1e3 * 0.014 / 3)
        assert read(f"recheck_ms_per_plan.{suffix}", w) == pytest.approx(
            1e3 * 0.016 / 3)


def test_shared_batches_keep_select_out_of_pack_and_recheck():
    ev = _batch(15.0, 3.0, ["a"], 0.001, 0.02, 2.9, 0.05, select=0.03)
    w = _window(ev)
    assert read("pack_ms_per_plan.backlog", w) == pytest.approx(20.0)
    assert read("recheck_ms_per_plan.backlog", w) == pytest.approx(50.0)


def test_codec_reads_requests_whose_encode_ended_in_the_window():
    ev = (_request("a", 11.0, 11.0, 0.001, 14.002, 0.002)
          + _request("b", 11.1, 11.1, 0.003, 14.004, 0.004)
          + _request("late", 19.0, 19.0, 0.1, 21.0, 0.1))
    w = _window(ev)
    assert read("codec_ms_per_plan.poisson", w) == pytest.approx(
        (3.0 + 7.0) / 2)
    assert read("codec_ms_per_plan.backlog", w) == pytest.approx(5.0)


def test_flush_lateness_is_the_p90_of_timer_flushes():
    ev = [{"type": "flush", "ts": 11.0 + i, "data": {
        "cause": "wait", "n": 1, "late_s": 0.001 * (i + 1)}}
        for i in range(5)]
    ev += [{"type": "flush", "ts": 12.5, "data": {
        "cause": "fill", "n": 8, "late_s": 0.0}},
           {"type": "flush", "ts": 9.0, "data": {
               "cause": "wait", "n": 1, "late_s": 9.0}}]
    w = _window(ev)
    # 1..5 ms; the fill flush and the flush before the window left out
    assert read("flush_late_ms.poisson", w) == pytest.approx(4.6)


def test_idle_in_flight_counts_idle_only_while_a_request_waits():
    # in flight 11-14 (a) and 13-16 (b): 11-16; busy 12-15 inside it
    ev = (_request("a", 11.0, 11.0, 0.0, 14.0, 0.0)
          + _request("b", 13.0, 13.0, 0.0, 16.0, 0.0))
    w = _window(ev, [_op(12.0, 15.0), _op(17.0, 18.0)])
    assert read("idle_in_flight.backlog", w) == pytest.approx(
        100 * (5.0 - 3.0) / 10)
    assert read("device_idle.backlog", w) == pytest.approx(
        100 * (1 - 4.0 / 10))


def test_new_readers_return_nothing_on_an_older_tape():
    """A program without spans (the parent of this change) leaves every
    new reader with nothing to read: ``None``, never an error."""
    old = ([{"type": "submit", "ts": 11.0, "trace_id": "a"},
            {"type": "flush", "ts": 11.5, "data": {"cause": "wait", "n": 1}},
            {"type": "cache_hit", "ts": 14.0, "data": {
                "bucket": 1, "jmax": 7, "trace_ids": ["a"]}},
            {"type": "plan_solved", "ts": 14.0, "data": {
                "kind": "plan", "n": 1, "bucket": 1, "seconds": 2.0,
                "trace_ids": ["a"]}}])
    for w in (_window(old, [_op(12.5, 13.5)]),
              layers.Window(0.0, 1.0, [], 8, False, 256, PEAKS, None)):
        for fam in NEW:
            for suffix in ("poisson", "backlog"):
                assert read(f"{fam}.{suffix}", w) is None, fam


def _recorded(cell):
    path = os.path.join(SPANS_DATA, cell)
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


def test_recorded_alibaba_window_reads_every_new_metric():
    w, result = _recorded("alibaba-backlog")
    assert result["correct"]
    cell = manifest.load_cell("alibaba-backlog")
    new = [m["name"] for m in cell.per_layer
           if m["name"].split(".")[0] in NEW]
    assert len(new) == 5
    for name in new:
        got = read(name, w)
        assert got is not None and got >= 0, name
        assert got == pytest.approx(result["metrics"][name]["value"],
                                    rel=1e-9), name
    # the shared pool's solve spans, select included, tile each solve
    names = {e["data"]["name"] for e in w.of_type("span")}
    assert {"solve.pack", "solve.device", "solve.select",
            "solve.recheck", "http.decode", "http.encode"} <= names
    for e in w.of_type("plan_solved"):
        ids = e["data"]["trace_ids"]
        parts = [s["data"]["seconds"] for s in w.of_type("span")
                 if s["data"]["trace_ids"] == ids
                 and s["data"]["name"] != "solve.prepare"]
        assert sum(parts) == pytest.approx(e["data"]["seconds"], abs=1e-3)
    # the kernel shows under its one stable name
    ops = [o for s in w.solves() for o in w.kernel_ops(s.start, s.end)]
    assert ops and all(o.name.startswith("%sgs_decode.") for o in ops)
