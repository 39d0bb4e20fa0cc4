"""Host spans (``repro.obs.spans``, schema v3).

* the disabled recorder is falsy, reads no clock and builds no event;
  recorders of the executor thread and of the event loop never mix;
* every live batch's ``solve.*`` spans tile the engine call that
  ``plan_solved.seconds`` times, carry the batch's trace ids, and
  ``solve.select`` appears only in shared pools;
* every answered ``POST /v1/plan`` gets one ``http.decode`` and one
  ``http.encode`` span with its trace id, and ``flush`` events carry the
  timer's lateness (0 on fill flushes);
* the aggregator folds spans per (pool, span) into ``/v1/stats``,
  ``/v1/metrics`` and ``obs_report``.
"""
import asyncio
import json
import threading

import pytest

from repro.cluster.catalog import Cluster, InstanceType
from repro.core.agora import Agora
from repro.core.dag import DAG, Task, TaskOption
from repro.core.objectives import Goal
from repro.core.session import PlanRequest
from repro.core.vectorized import VecConfig
from repro.flow.daemon import (DaemonConfig, PlannerHTTPServer,
                               PlannerService, PoolSpec, dag_to_json,
                               metrics_text)
from repro.launch import obs_report
from repro.obs import events as ev
from repro.obs import spans as sp
from repro.obs.aggregate import EventAggregator
from repro.obs.events import Event
from repro.obs.sink import JsonlSink, NullSink, RingSink, replay

CFG = VecConfig(chains=8, iters=40, grid=64, seed=0)
SOLVE_SPANS = {sp.SOLVE_PREPARE, sp.SOLVE_REFPOINT, sp.SOLVE_PACK,
               sp.SOLVE_DEVICE, sp.SOLVE_SELECT, sp.SOLVE_RECHECK}


class _NoClock:
    """Stands in for the ``time`` module: any clock read fails."""

    @staticmethod
    def monotonic():
        raise AssertionError("a disabled span read the clock")


def _cluster(caps=(4.0,)):
    return Cluster(tuple(InstanceType(f"r{m}", 1, 1, 3.6)
                         for m in range(len(caps))), tuple(caps))


def _agora(cluster):
    return Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                 vec_cfg=CFG)


def _chain_dag(name, n=3, dur=20.0, dem=1.0, price=1e-3):
    tasks = [Task(f"t{i}", [TaskOption("o", dur, (dem,), dur * dem * price),
                            TaskOption("p", dur / 2, (2 * dem,),
                                       dur * dem * price)])
             for i in range(n)]
    return DAG(name, tasks, [(i, i + 1) for i in range(n - 1)])


# ---------------------------------------------------------------------------
# the primitive


def test_recorder_records_contiguous_spans_as_span_events():
    rec = sp.recorder(True)
    assert rec and rec is not sp.NULL_SPANS
    rec.mark()
    rec.lap(sp.SOLVE_PACK)
    rec.lap(sp.SOLVE_DEVICE)
    (n1, a1, b1), (n2, a2, b2) = rec.spans
    assert (n1, n2) == (sp.SOLVE_PACK, sp.SOLVE_DEVICE)
    assert a1 <= b1 == a2 <= b2          # one clock read per boundary
    out = rec.events(trace_ids=["t-0", None, "t-1"], pool="p")
    assert [e.type for e in out] == [ev.SPAN, ev.SPAN]
    assert out[1].ts == b2 and out[1].pool == "p"
    assert out[1].data == {"name": sp.SOLVE_DEVICE, "seconds": b2 - a2,
                           "trace_ids": ["t-0", "t-1"]}
    back = ev.event_from_json(json.loads(json.dumps(out[0].to_json())))
    assert back.schema == 3 and dict(back.data) == dict(out[0].data)


def test_disabled_recorder_reads_no_clock_and_builds_no_event(monkeypatch):
    monkeypatch.setattr(sp, "time", _NoClock)
    for off in (None, False, NullSink()):
        assert sp.recorder(off) is sp.NULL_SPANS
    assert not sp.NULL_SPANS
    sp.NULL_SPANS.mark()                 # unguarded calls are inert too
    sp.NULL_SPANS.lap(sp.SOLVE_PACK)
    assert sp.NULL_SPANS.spans == [] and sp.NULL_SPANS.events() == []
    with pytest.raises(AssertionError):
        sp.recorder(True).mark()
    # a session with no sink solves without a single span clock read
    cluster = _cluster()
    res = _agora(cluster).session(shared_capacity=True, bucket_p=4).plan(
        [PlanRequest(dag=_chain_dag("a"))])
    assert res[0].validate() == []


def test_recorders_of_two_threads_do_not_mix():
    """One recorder per unit of work: an executor thread's solve spans and
    the loop thread's codec spans, recorded at the same time, stay apart."""
    loop_rec, exec_rec = sp.recorder(True), sp.recorder(True)
    go = threading.Barrier(2)

    def work(rec, names):
        go.wait()
        rec.mark()
        for _ in range(200):
            for n in names:
                rec.lap(n)

    solve = (sp.SOLVE_PACK, sp.SOLVE_DEVICE, sp.SOLVE_RECHECK)
    codec = (sp.HTTP_DECODE, sp.HTTP_ENCODE)
    t = threading.Thread(target=work, args=(exec_rec, solve))
    t.start()
    work(loop_rec, codec)
    t.join()
    assert {n for n, _, _ in exec_rec.spans} == set(solve)
    assert {n for n, _, _ in loop_rec.spans} == set(codec)
    for rec in (loop_rec, exec_rec):
        ends = [b for _, _, b in rec.spans]
        starts = [a for _, a, _ in rec.spans]
        assert starts[1:] == ends[:-1]   # contiguous within one thread


# ---------------------------------------------------------------------------
# the served solve


@pytest.mark.parametrize("shared", [False, True], ids=["isolated", "shared"])
def test_solve_spans_tile_plan_solved(shared):
    cluster = _cluster()
    ring = RingSink()
    sess = _agora(cluster).session(shared_capacity=shared, bucket_p=4,
                                   sink=ring)
    sess.warmup(_chain_dag("tmpl"), buckets=[4])
    assert not [e for e in ring if e.type == ev.SPAN]   # warm-up: none
    for k in range(2):
        ids = [f"b{k}-{i}" for i in range(3)]
        sess.plan([PlanRequest(dag=_chain_dag(f"d{k}{i}"), trace=t)
                   for i, t in enumerate(ids)])
    solved = [e for e in ring if e.type == ev.PLAN_SOLVED]
    spans = [e for e in ring if e.type == ev.SPAN]
    assert len(solved) == 2
    for ps in solved:
        ids = ps.data["trace_ids"]
        mine = [e for e in spans if e.data["trace_ids"] == ids]
        names = [e.data["name"] for e in mine]
        # shared pools lap the joint reference point ahead of the pack;
        # isolated pools lap as they did
        want = [sp.SOLVE_PREPARE] + ([sp.SOLVE_REFPOINT] if shared else [])
        want += [sp.SOLVE_PACK, sp.SOLVE_DEVICE]
        want += [sp.SOLVE_SELECT] if shared else []
        assert names == want + [sp.SOLVE_RECHECK]
        # each span emitted once, after its plan_solved, on the pool clock
        assert all(e.ts <= ps.ts and e.data["seconds"] >= 0 for e in mine)
        inner = mine[1:]
        start = inner[0].ts - inner[0].data["seconds"]
        for a, b in zip(inner, inner[1:]):   # contiguous
            assert b.ts - b.data["seconds"] == pytest.approx(a.ts, abs=1e-9)
        total = sum(e.data["seconds"] for e in inner)
        # the engine call plan_solved times, to within 1 ms at both ends
        assert total == pytest.approx(ps.data["seconds"], abs=1e-3)
        assert start == pytest.approx(ps.ts - ps.data["seconds"], abs=1e-3)
        prep = mine[0]
        assert prep.ts <= start + 1e-3


# ---------------------------------------------------------------------------
# the daemon: HTTP codec spans and timer lateness


async def _post(host, port, body):
    reader, writer = await asyncio.open_connection(host, port)
    payload = json.dumps(body).encode()
    writer.write(f"POST /v1/plan HTTP/1.1\r\nHost: {host}\r\n"
                 f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, data = raw.partition(b"\r\n\r\n")
    return int(head.split(b" ", 2)[1]), json.loads(data)


def test_http_codec_spans_and_flush_lateness():
    ring = RingSink()
    svc = PlannerService(_agora(_cluster()), DaemonConfig(
        pools=(PoolSpec("shared", shared_capacity=True, bucket_p=True),),
        max_batch=2, max_wait_s=0.05, sink=ring))
    svc.warmup(_chain_dag("tmpl"), max_p=2)

    async def drive():
        http = PlannerHTTPServer(svc)
        async with svc:
            host, port = await http.start()
            # two at once fill the bucket; one alone waits for the timer
            pair = await asyncio.gather(
                _post(host, port, {"dag": dag_to_json(_chain_dag("a"))}),
                _post(host, port, {"dag": dag_to_json(_chain_dag("b"))}))
            lone = await _post(host, port,
                               {"dag": dag_to_json(_chain_dag("c"))})
            bad = await _post(host, port, {"dag": {"oops": True}})
            await http.stop()
            return [*pair, lone], bad

    answered, bad = asyncio.run(drive())
    assert [s for s, _ in answered] == [200, 200, 200] and bad[0] == 400
    submits = [e for e in ring if e.type == ev.SUBMIT]
    assert len(submits) == 3
    codec = [e for e in ring if e.type == ev.SPAN
             and e.data["name"].startswith("http.")]
    for sub in submits:
        mine = [e for e in codec if e.data["trace_ids"] == [sub.trace_id]]
        assert sorted(e.data["name"] for e in mine) == [sp.HTTP_DECODE,
                                                        sp.HTTP_ENCODE]
        assert all(e.pool == "shared" and e.data["seconds"] >= 0
                   for e in mine)
    assert len(codec) == 6               # the 400 is not an answered plan
    flushes = [e for e in ring if e.type == ev.FLUSH]
    assert {e.data["cause"] for e in flushes} == {"fill", "wait"}
    assert all(e.data["late_s"] >= 0 for e in flushes)
    assert all(e.data["late_s"] == 0 for e in flushes
               if e.data["cause"] == "fill")
    # the operator's view of the same spans
    snap = svc.stats()["events"]["spans"]["shared"]
    assert snap[sp.HTTP_ENCODE]["count"] == 3
    assert snap[sp.SOLVE_DEVICE]["count"] == len(
        [e for e in ring if e.type == ev.PLAN_SOLVED])
    text = metrics_text(svc.stats())
    assert 'planner_spans_total{pool="shared",span="http.decode"} 3' in text
    assert 'planner_span_seconds_total{pool="shared",span="solve.pack"}' \
        in text


# ---------------------------------------------------------------------------
# the operator's fold


def _span(pool, name, secs):
    return Event(type=ev.SPAN, ts=1.0, pool=pool,
                 data={"name": name, "seconds": secs, "trace_ids": []})


def test_aggregator_folds_spans_per_pool_and_obs_report_prints_them(
        tmp_path, capsys):
    stream = [_span("a", sp.SOLVE_PACK, 0.25), _span("a", sp.SOLVE_PACK, 0.5),
              _span("b", sp.HTTP_ENCODE, 0.125)]
    agg = EventAggregator.fold(stream)
    assert agg.span_totals() == {
        "a": {sp.SOLVE_PACK: {"seconds": 0.75, "count": 2}},
        "b": {sp.HTTP_ENCODE: {"seconds": 0.125, "count": 1}}}
    assert EventAggregator().snapshot()["spans"] == {}
    path = tmp_path / "events.jsonl"
    with JsonlSink(str(path)) as sink:
        replay(stream, sink)
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "host spans" in out
    assert "solve.pack" in out and "375.000" in out   # mean ms of pool a
    assert "decode slots" in out


def test_dispatch_events_carry_decode_slots_and_obs_report_prints_them(
        tmp_path, capsys):
    """``decode_slots`` is the SA scan's serial placements per chain:
    bucket x Jmax in a joint decode, Jmax in an isolated one; warm-up
    dispatches carry it but do not count in the operator's mean."""
    for shared, want in ((False, 3), (True, 4 * 3)):
        ring = RingSink()
        sess = _agora(_cluster()).session(shared_capacity=shared,
                                          bucket_p=4, sink=ring)
        sess.warmup(_chain_dag("tmpl"), buckets=[4])
        sess.plan([PlanRequest(dag=_chain_dag(f"d{i}"), trace=f"t{i}")
                   for i in range(2)])
        disp = [e for e in ring if e.type in (ev.BUCKET_TRACED,
                                             ev.CACHE_HIT)]
        assert [e.data["decode_slots"] for e in disp] == [want, want]
        agg = EventAggregator.fold(ring)
        assert agg.snapshot()["decode_slots"] == {"": float(want)}
    path = tmp_path / "events.jsonl"
    with JsonlSink(str(path)) as sink:
        replay(list(ring) + [_span(None, sp.SOLVE_PACK, 0.5)], sink)
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    row = [ln for ln in out.splitlines() if "solve.pack" in ln]
    assert row and row[0].split()[-1] == "12.0"
