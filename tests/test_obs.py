"""Observability-plane invariants (``repro.obs``).

The contracts the docs promise (docs/events.md):

* wire schema v3 round-trips through JSON / JSON-lines bit-for-bit,
  committed v1 and v2 golden tapes still fold identically, and the reader
  refuses streams from a foreign schema version;
* causal traces reconstruct per-request span chains across both event
  granularities, and ``chain_complete`` gates on submit-root + terminal;
* ``VecConfig.telemetry`` off is bit-identical to on (pure extra
  outputs), on attaches ``ConvergenceTrace``s and emits ``solve_profile``
  exactly once per solve with zero warm-bucket retraces;
* the disabled sink is FALSY and free — plans served with no sink are
  bit-for-bit identical to plans served with a recording sink, which
  also records host spans (``span`` events, schema v3);
* terminal ``deadline_hit`` / ``deadline_miss`` events are exactly-once
  per tenant across every streaming exit path (rejected at admission,
  dropped after plan retries, served);
* the ``EventAggregator`` fold of a recorded stream equals the live fold,
  and its event-derived accounting reproduces the post-hoc benchmark
  numbers (hit rates, retrace counts) on the same run;
* the daemon's ``/v1/stats`` ``events`` block is that same aggregator.
"""
import asyncio
import dataclasses
import json
import math
import os

import numpy as np
import pytest

from repro.cluster.catalog import Cluster, InstanceType
from repro.core.agora import Agora
from repro.core.dag import DAG, Task, TaskOption
from repro.core.objectives import Goal
from repro.core.session import SLA_GUARANTEED, PlanRequest
from repro.core.vectorized import VecConfig
from repro.flow.daemon import (DaemonConfig, PlannerService, PoolSpec,
                               metrics_text)
from repro.flow.executor import FlowConfig
from repro.flow.streaming import (SLA_BEST_EFFORT, StreamConfig,
                                  StreamingRunner, TenantRequest,
                                  deadline_hit_rate)
from repro.obs import events as ev
from repro.obs.aggregate import (EventAggregator, finite_or_none,
                                 percentile)
from repro.obs.events import Event, event_from_json, read_jsonl
from repro.obs.sink import (NULL, JsonlSink, NullSink, RingSink, TagSink,
                            TeeSink, replay)
from repro.obs.trace import (TraceIds, chain_complete, render_trace, spans,
                             trace_ids)

CFG = VecConfig(chains=8, iters=40, grid=64, seed=0)


def _cluster(caps=(4.0,)):
    return Cluster(tuple(InstanceType(f"r{m}", 1, 1, 3.6)
                         for m in range(len(caps))), tuple(caps))


def _agora(cluster):
    return Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                 vec_cfg=CFG)


def _chain_dag(name, n, dur, dem, t0, price):
    tasks = [Task(f"t{i}", [TaskOption("o", dur, (dem,), dur * dem * price)])
             for i in range(n)]
    return DAG(name, tasks, [(i, i + 1) for i in range(n - 1)],
               release_time=t0)


# ---------------------------------------------------------------------------
# wire schema


def test_event_wire_roundtrip_every_type():
    """Schema golden test: every declared event type survives
    ``to_json`` -> ``event_from_json`` with every envelope field intact."""
    for i, etype in enumerate(ev.EVENT_TYPES):
        e = Event(type=etype, ts=1.5 + i, tenant=f"t{i}", pool="shared",
                  sla="guaranteed", data={"k": i, "deadline": None})
        obj = e.to_json()
        assert obj["schema"] == ev.SCHEMA_VERSION
        back = event_from_json(obj)
        assert (back.type, back.ts, back.tenant, back.pool, back.sla) == \
            (e.type, e.ts, e.tenant, e.pool, e.sla)
        assert dict(back.data) == dict(e.data)


def test_unknown_type_and_foreign_schema_are_refused():
    with pytest.raises(ValueError):
        Event(type="made_up_event", ts=0.0)
    good = Event(type=ev.PLAN_SOLVED, ts=0.0).to_json()
    good["schema"] = ev.SCHEMA_VERSION + 1
    with pytest.raises(ValueError):
        event_from_json(good)


def test_finite_or_none_encodes_inf_nan_as_null():
    assert finite_or_none(None) is None
    assert finite_or_none(math.inf) is None
    assert finite_or_none(math.nan) is None
    assert finite_or_none(2.5) == 2.5


# ---------------------------------------------------------------------------
# sinks


def test_null_sink_is_falsy_and_real_sinks_are_truthy():
    """The emission-site guard ``if self.sink:`` must cost one truthiness
    check on the disabled path — NULL and an empty tee are falsy."""
    assert not NULL and not NullSink()
    assert not TeeSink() and not TeeSink(NULL, None)
    ring = RingSink()
    assert ring and TeeSink(ring) and TeeSink(NULL, ring)


def test_ring_sink_keeps_the_last_capacity_events():
    ring = RingSink(capacity=3)
    for i in range(5):
        ring.emit(Event(type=ev.CACHE_HIT, ts=float(i)))
    assert len(ring) == 3
    assert [e.ts for e in ring] == [2.0, 3.0, 4.0]


def test_tag_sink_stamps_pool_only_when_absent():
    ring = RingSink()
    tagged = TagSink(ring, pool="shared")
    tagged.emit(Event(type=ev.CACHE_HIT, ts=0.0))
    tagged.emit(Event(type=ev.CACHE_HIT, ts=1.0, pool="other"))
    assert [e.pool for e in ring] == ["shared", "other"]


def test_jsonl_roundtrip_and_fold_matches_live(tmp_path):
    """A recorded stream folds to the SAME snapshot as the live fold —
    the obs_report CLI and /v1/stats cannot disagree about one stream."""
    events = [
        Event(type=ev.BUCKET_TRACED, ts=0.0, pool="shared",
              data={"bucket": 8, "warming": True}),
        Event(type=ev.BUCKET_TRACED, ts=1.0, pool="shared",
              data={"bucket": 8, "warming": False}),
        Event(type=ev.CACHE_HIT, ts=2.0, pool="shared", data={"bucket": 8}),
        Event(type=ev.DISPATCH, ts=3.0, pool="shared",
              data={"mode": "daemon", "latency_s": [0.1, 0.3]}),
        Event(type=ev.DEADLINE_HIT, ts=4.0, tenant="a", sla="guaranteed",
              data={"deadline": 10.0, "completion": 4.0}),
        Event(type=ev.DEADLINE_MISS, ts=5.0, tenant="b", sla="guaranteed",
              data={"deadline": 4.0, "completion": 5.0}),
        Event(type=ev.DEADLINE_HIT, ts=6.0, tenant="c", sla="best_effort",
              data={"deadline": None, "completion": 6.0}),
        Event(type=ev.CAPACITY_AUDIT, ts=7.0, data={"headroom": [2.0, 1.0]}),
        Event(type=ev.CAPACITY_AUDIT, ts=8.0, data={"headroom": [0.5, 3.0]}),
    ]
    path = tmp_path / "events.jsonl"
    with JsonlSink(str(path)) as sink:
        assert replay(events, sink) == len(events)
    live = EventAggregator.fold(events)
    replayed = EventAggregator.fold(read_jsonl(str(path)))
    assert replayed.snapshot() == live.snapshot()
    # the fold itself: declared-class accounting, min-headroom, retraces
    assert live.hit_counts("guaranteed") == (1, 1)
    assert live.hit_rate("guaranteed") == 0.5
    assert live.hit_rate("standard") == 1.0       # no samples -> 1.0
    assert live.hit_counts("best_effort") == (0, 0)   # no finite deadline
    assert live.tenants["c"]["hit"] is True           # ...but a verdict
    assert (live.retraces, live.warmup_traces, live.cache_hits) == (1, 1, 1)
    assert live.headroom == [0.5, 1.0]
    lat = live.latency_percentiles()
    assert lat["p50"] == pytest.approx(0.2)
    # an empty stream has NO latency distribution: explicit None, not NaN
    empty = EventAggregator().latency_percentiles()
    assert empty == {"p50": None, "p99": None}


def test_closed_jsonl_sink_drops_late_events_but_counts_them(tmp_path):
    """Close races late emissions in a draining daemon — a closed file
    sink drops instead of crashing the serving thread, but COUNTS every
    dropped event so the operator learns the tape is incomplete."""
    path = tmp_path / "e.jsonl"
    sink = JsonlSink(str(path))
    sink.emit(Event(type=ev.CACHE_HIT, ts=0.0))
    assert sink.dropped == 0
    sink.close()
    sink.emit(Event(type=ev.CACHE_HIT, ts=1.0))
    sink.emit(Event(type=ev.CACHE_HIT, ts=2.0))
    assert len(list(read_jsonl(str(path)))) == 1
    assert sink.dropped == 2


# ---------------------------------------------------------------------------
# disabled sink == free: bit-for-bit identical plans


def test_no_sink_plans_are_bit_identical_to_recorded_plans():
    """Both engines: a recording sink (which also records the host spans
    of every solve) and ``NullSink`` serve the same plans, bit for bit."""
    cluster = _cluster((4.0,))
    price = float(cluster.prices_per_sec[0])
    dags = [_chain_dag(f"d{i}", 3, 20.0, 1.0, 0.0, price) for i in range(3)]
    for shared in (True, False):
        ring = RingSink()
        plain = _agora(cluster).session(shared_capacity=shared, bucket_p=4,
                                        sink=NullSink())
        taped = _agora(cluster).session(shared_capacity=shared, bucket_p=4,
                                        sink=ring)
        assert not plain.sink
        a = plain.plan([PlanRequest(dag=d) for d in dags])
        b = taped.plan([PlanRequest(dag=d) for d in dags])
        assert len(ring) > 0
        names = [e.data["name"] for e in ring if e.type == ev.SPAN]
        assert "solve.device" in names and "solve.recheck" in names
        assert ("solve.select" in names) == shared
        for ra, rb in zip(a, b):
            assert np.array_equal(ra.solution.option_idx,
                                  rb.solution.option_idx)
            assert np.array_equal(ra.solution.start, rb.solution.start)
            assert np.array_equal(ra.solution.finish, rb.solution.finish)
            assert ra.solution.cost == rb.solution.cost


class _BoobyTrappedSink(NullSink):
    """Falsy like NullSink, but ``emit`` raises: proves the disabled
    plane never constructs or forwards an event at all (the falsy-sink
    single-truthiness-check contract that `agoralint sink-discipline`
    enforces lexically — including helper paths like
    ``PlannerSession._emit_dispatch``)."""

    def emit(self, event):
        raise AssertionError(f"emit reached a disabled sink: {event}")


def test_disabled_sink_is_never_called_and_plans_match():
    cluster = _cluster((4.0,))
    price = float(cluster.prices_per_sec[0])
    dags = [_chain_dag(f"d{i}", 3, 20.0, 1.0, 0.0, price) for i in range(2)]
    trap = _BoobyTrappedSink()
    assert not trap                      # still falsy, like NullSink
    trapped = _agora(cluster).session(shared_capacity=True, bucket_p=4,
                                      sink=trap)
    plain = _agora(cluster).session(shared_capacity=True, bucket_p=4)
    reqs = [PlanRequest(dag=d) for d in dags]
    a = trapped.plan(reqs)               # any emission would raise here
    b = plain.plan(reqs)
    for ra, rb in zip(a, b):
        assert np.array_equal(ra.solution.option_idx, rb.solution.option_idx)
        assert np.array_equal(ra.solution.start, rb.solution.start)
        assert np.array_equal(ra.solution.finish, rb.solution.finish)
        assert ra.solution.cost == rb.solution.cost


# ---------------------------------------------------------------------------
# streaming: exactly-once terminal events, event-derived == post-hoc


def test_streaming_terminal_events_exactly_once_across_exit_paths():
    """The reject/drop/served triple of test_streaming: every tenant gets
    EXACTLY one terminal deadline verdict event, the event-derived hit
    rate equals ``deadline_hit_rate`` over the returned records, and the
    two non-served exits also emit their ``drop`` events."""
    cluster = _cluster((4.0,))
    price = float(cluster.prices_per_sec[0])
    reqs = [
        # provably infeasible guaranteed: rejected at admission
        TenantRequest(_chain_dag("doomed", 2, 50.0, 3.0, 0.0, price),
                      sla=SLA_GUARANTEED, deadline=60.0),
        # structurally oversized standard: dropped after max_retries
        TenantRequest(_chain_dag("big", 2, 30.0, 5.0, 0.0, price)),
        # a normal tenant: served
        TenantRequest(_chain_dag("ok", 2, 30.0, 1.0, 0.0, price)),
    ]
    cfg = FlowConfig(mode="sim", enforce_capacity=True, speculation=False)
    ring = RingSink()
    agg = EventAggregator()
    runner = StreamingRunner(_agora(cluster), reqs, cfg, StreamConfig(),
                             sink=TeeSink(ring, agg))
    records = runner.run()
    assert sorted(r.name for r in records) == ["big", "doomed", "ok"]

    terminal = [e for e in ring
                if e.type in (ev.DEADLINE_HIT, ev.DEADLINE_MISS)]
    assert len(terminal) == len(records)                  # exactly once
    assert sorted(e.tenant for e in terminal) == ["big", "doomed", "ok"]
    by = {e.tenant: e for e in terminal}
    assert by["doomed"].type == ev.DEADLINE_MISS
    assert by["doomed"].data["admission"] == "rejected"
    assert by["big"].data["failed"] is True
    assert by["ok"].type == ev.DEADLINE_HIT
    drops = {e.tenant: e.data["reason"] for e in ring if e.type == ev.DROP}
    assert drops == {"doomed": "admission_rejected", "big": "invalid_plan"}

    # event-derived accounting == post-hoc accounting, same run
    h, m = agg.hit_counts(SLA_GUARANTEED)
    assert (h, m) == (0, 1)
    assert agg.hit_rate(SLA_GUARANTEED) == deadline_hit_rate(
        records, sla=SLA_GUARANTEED)
    # only the guaranteed arrival is admission-checked
    assert agg.counts[ev.ADMISSION_DECISION] == 1
    assert agg.violations == 0 and agg.headroom is not None


def test_streaming_preempt_and_defer_events_are_emitted():
    """The contended scenario (best-effort hog + mid-flight guaranteed
    arrival) must narrate its control actions: a preemption event for the
    victim, carrying who was at risk."""
    cluster = _cluster((4.0,))
    price = float(cluster.prices_per_sec[0])
    be = TenantRequest(_chain_dag("be", 6, 50.0, 2.0, 0.0, price),
                       sla=SLA_BEST_EFFORT)
    g = TenantRequest(_chain_dag("g", 2, 50.0, 3.0, 40.0, price),
                      sla=SLA_GUARANTEED, deadline=40.0 + 130.0)
    cfg = FlowConfig(mode="sim", enforce_capacity=True, speculation=False)
    ring = RingSink()
    runner = StreamingRunner(_agora(cluster), [be, g], cfg, StreamConfig(),
                             sink=ring)
    runner.run()
    if runner.preempt_events:      # same condition the PR 3 test asserts
        pre = [e for e in ring if e.type == ev.PREEMPT]
        assert len(pre) == runner.preempt_events
        assert pre[0].tenant == "be" and "g" in pre[0].data["at_risk"]


# ---------------------------------------------------------------------------
# daemon: /v1/stats events block rides the same aggregator


def test_daemon_stats_events_block_is_the_aggregator():
    cluster = _cluster((4.0,))
    price = float(cluster.prices_per_sec[0])
    agora = _agora(cluster)
    ring = RingSink()
    svc = PlannerService(agora, DaemonConfig(
        pools=(PoolSpec("shared", shared_capacity=True, bucket_p=True),),
        max_batch=2, max_wait_s=0.05, sink=ring))
    svc.warmup(_chain_dag("t", 2, 2.0, 1.0, 0.0, price), max_p=2)

    async def drive():
        async with svc:
            await svc.submit(PlanRequest(
                dag=_chain_dag("a", 2, 2.0, 1.0, 0.0, price),
                sla=SLA_GUARANTEED, deadline=1e9))
            await svc.submit(_chain_dag("b", 2, 2.0, 1.0, 0.0, price))

    asyncio.run(drive())
    st = svc.stats()
    snap = st["events"]
    # the operator sink saw exactly what the internal aggregator folded
    assert len(ring) == snap["events"]
    assert all(e.pool == "shared" for e in ring)
    assert svc.aggregator.hit_counts(SLA_GUARANTEED) == (1, 0)
    # zero-retrace after warmup; warmup itself rides either a fresh trace
    # or the process-global JIT cache (earlier tests may have compiled the
    # same signature), so gate on total warm-path activity
    assert snap["retraces"] == 0
    assert snap["warmup_traces"] + snap["cache_hits"] > 0
    # /v1/stats latency percentiles ARE the aggregator's
    assert st["latency"]["p50"] == svc.aggregator.latency_percentiles()["p50"]
    assert st["latency"]["p50"] is not None


# ---------------------------------------------------------------------------
# schema versioning: a committed v1 tape must keep folding under v2

GOLDEN_V1 = os.path.join(os.path.dirname(__file__), "golden",
                         "events_v1.jsonl")
GOLDEN_V2 = os.path.join(os.path.dirname(__file__), "golden",
                         "events_v2.jsonl")


def test_v1_golden_tape_folds_identically_under_v2_reader():
    """The versioning policy, applied: v1 events are a strict subset of
    v2, so the committed v1 tape reads back with ``None`` causal fields
    and folds to the SAME snapshot as the equivalent v2 events."""
    tape = list(read_jsonl(GOLDEN_V1))
    assert tape and all(e.schema == 1 for e in tape)
    assert all(e.trace_id is None and e.parent is None for e in tape)
    v2 = [Event(type=e.type, ts=e.ts, tenant=e.tenant, pool=e.pool,
                sla=e.sla, data=e.data) for e in tape]
    old, new = EventAggregator.fold(tape), EventAggregator.fold(v2)
    # snapshots differ ONLY in the schema stamp (both report v2's fold)
    assert old.snapshot() == new.snapshot()
    assert (old.retraces, old.warmup_traces, old.cache_hits) == (1, 1, 1)
    assert old.hit_counts("guaranteed") == (1, 1)
    assert old.latency_percentiles()["p50"] == pytest.approx(0.2)
    assert old.headroom == [0.5, 1.0]


def test_v2_golden_tape_folds_identically_under_v3_reader():
    """v2 tapes carry fields v3 dropped as copies of ``plan_solved``
    (``seconds`` on ``cache_hit`` / ``bucket_traced``, ``n`` / ``bucket``
    / ``seconds`` on ``solve_profile``); no fold reads them, so the
    committed v2 tape folds to the snapshot of its v3 equivalent."""
    tape = list(read_jsonl(GOLDEN_V2))
    assert tape and all(e.schema == 2 for e in tape)
    dropped = {ev.CACHE_HIT: ("seconds",), ev.BUCKET_TRACED: ("seconds",),
               ev.SOLVE_PROFILE: ("n", "bucket", "seconds")}
    v3 = [Event(type=e.type, ts=e.ts, tenant=e.tenant, pool=e.pool,
                sla=e.sla, trace_id=e.trace_id, parent=e.parent,
                data={k: v for k, v in e.data.items()
                      if k not in dropped.get(e.type, ())})
          for e in tape]
    assert all(e.schema == 3 for e in v3)
    old, new = EventAggregator.fold(tape), EventAggregator.fold(v3)
    assert old.snapshot() == new.snapshot()
    assert (old.retraces, old.warmup_traces, old.cache_hits) == (1, 1, 1)
    assert old.hit_counts("guaranteed") == (1, 1)
    assert old.convergence_stats()["profiles"] == 1
    assert old.snapshot()["spans"] == {}
    assert chain_complete(spans(tape, "cafe0123-0000"))


def test_foreign_schema_line_in_a_tape_is_refused_loudly(tmp_path):
    path = tmp_path / "future.jsonl"
    line = Event(type=ev.CACHE_HIT, ts=0.0).to_json()
    line["schema"] = 99
    path.write_text(json.dumps(line) + "\n")
    with pytest.raises(ValueError, match="schema 99"):
        list(read_jsonl(str(path)))


# ---------------------------------------------------------------------------
# causal traces (schema v2): ids, span merge, completeness gate


def test_trace_ids_are_unique_monotonic_and_prefixed():
    ids = TraceIds(prefix="cafe0123")
    assert ids.next() == "cafe0123-0000"
    assert ids.next() == "cafe0123-0001"
    other = TraceIds()
    assert other.next() != "cafe0123-0000"   # fresh lifetime, fresh prefix


def _trace_stream(t):
    """One request's life plus an unrelated event, deliberately shuffled
    across both granularities (per-request stamps + batch membership)."""
    return [
        Event(type=ev.SUBMIT, ts=0.0, tenant="a", trace_id=t,
              data={"deadline": 9.0}),
        Event(type=ev.ADMISSION_DECISION, ts=1.0, tenant="a", trace_id=t,
              parent=ev.SUBMIT, data={"admitted": True}),
        Event(type=ev.CACHE_HIT, ts=1.5, pool="shared"),   # not ours
        Event(type=ev.FLUSH, ts=2.0, pool="shared",
              data={"cause": "fill", "n": 1, "trace_ids": [t]}),
        Event(type=ev.DISPATCH, ts=3.0, pool="shared",
              data={"latency_s": [0.5], "trace_ids": [t]}),
        Event(type=ev.DEADLINE_HIT, ts=4.0, tenant="a", trace_id=t,
              parent=ev.DISPATCH, data={"deadline": 9.0, "completion": 4.0}),
    ]


def test_trace_spans_merge_both_granularities_in_order():
    t = "cafe0123-0000"
    stream = _trace_stream(t)
    assert trace_ids(stream) == [t]
    chain = spans(stream, t)
    assert [e.type for e in chain] == [
        ev.SUBMIT, ev.ADMISSION_DECISION, ev.FLUSH, ev.DISPATCH,
        ev.DEADLINE_HIT]
    assert chain_complete(chain)
    # no submit root, or no terminal span yet -> incomplete
    assert not chain_complete(chain[1:])
    assert not chain_complete(chain[:3])
    out = render_trace(stream, t)
    assert out.startswith(f"trace {t} (complete, 5 spans)")
    assert ev.DEADLINE_HIT in out and "cause=fill" in out


def test_trace_roundtrips_the_jsonl_wire(tmp_path):
    t = "cafe0123-0007"
    path = tmp_path / "t.jsonl"
    with JsonlSink(str(path)) as sink:
        replay(_trace_stream(t), sink)
    back = list(read_jsonl(str(path)))
    assert back[0].trace_id == t and back[0].parent is None
    assert back[1].parent == ev.SUBMIT
    assert chain_complete(spans(back, t))


def test_shed_request_chain_is_complete():
    """A request shed at the front door still gets a complete chain:
    submit -> drop -> deadline_miss (the daemon stamps the trace BEFORE
    the queue-full check)."""
    t = "cafe0123-0002"
    chain = [
        Event(type=ev.SUBMIT, ts=0.0, tenant="a", trace_id=t),
        Event(type=ev.DROP, ts=0.0, tenant="a", trace_id=t,
              parent=ev.SUBMIT, data={"reason": "queue_full"}),
        Event(type=ev.DEADLINE_MISS, ts=0.0, tenant="a", trace_id=t,
              parent=ev.DROP, data={"deadline": 5.0}),
    ]
    assert chain_complete(spans(chain, t))


# ---------------------------------------------------------------------------
# in-solve convergence telemetry: off is bit-identical, on is narrated


def test_telemetry_off_vs_on_differential():
    """``VecConfig.telemetry`` is pure extra outputs: plans bit-for-bit
    identical either way; off attaches NO trace and emits NO
    ``solve_profile``; on attaches a ``ConvergenceTrace`` per result and
    emits ``solve_profile`` exactly once per live solve — with zero
    retraces on the warmed bucket."""
    cluster = _cluster((4.0,))
    price = float(cluster.prices_per_sec[0])
    dags = [_chain_dag(f"d{i}", 3, 20.0, 1.0, 0.0, price) for i in range(3)]
    reqs = [PlanRequest(dag=d) for d in dags]

    ring = RingSink()
    off_sess = _agora(cluster).session(shared_capacity=True, bucket_p=4)
    on_agora = Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                     vec_cfg=dataclasses.replace(CFG, telemetry=True))
    on_sess = on_agora.session(shared_capacity=True, bucket_p=4, sink=ring)

    a = off_sess.plan(reqs)
    b = on_sess.plan(reqs)
    for ra, rb in zip(a, b):
        assert np.array_equal(ra.solution.option_idx,
                              rb.solution.option_idx)
        assert np.array_equal(ra.solution.start, rb.solution.start)
        assert ra.solution.cost == rb.solution.cost
        assert ra.convergence is None          # off: nothing attached
        tr = rb.convergence
        assert tr is not None and tr.iters > 0 and tr.chains > 0
        assert len(tr.steps) == len(tr.best_e) == len(tr.accept)
        # the incumbent energy is monotone non-increasing by construction
        assert np.all(np.diff(np.asarray(tr.best_e)) <= 1e-9)
        assert np.all((np.asarray(tr.accept) >= 0.0)
                      & (np.asarray(tr.accept) <= 1.0))
        assert 0 <= tr.steps_to_best <= tr.iters
        assert 0.0 <= tr.plateau_fraction <= 1.0

    profiles = [e for e in ring if e.type == ev.SOLVE_PROFILE]
    assert len(profiles) == 1                  # exactly once per solve
    assert len(profiles[0].data["profiles"]) == len(dags)
    assert {p["tenant"] for p in profiles[0].data["profiles"]} == \
        {d.name for d in dags}

    # warm re-solve: telemetry-on signature is warmed too — zero retraces
    t0 = on_sess.stats.trace_count
    b2 = on_sess.plan(reqs)
    assert on_sess.stats.trace_count == t0
    assert all(r.convergence is not None for r in b2)
    assert len([e for e in ring if e.type == ev.SOLVE_PROFILE]) == 2


# ---------------------------------------------------------------------------
# aggregator roll-ups + Prometheus exposition


def test_percentile_helper_matches_numpy_linear_interpolation():
    vals = sorted([3.0, 1.0, 4.0, 1.5, 9.0])
    for q in (0.0, 50.0, 90.0, 99.0, 100.0):
        assert percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert percentile([5.0], 99.0) == 5.0


def test_convergence_stats_empty_is_explicit_nones_and_fold_rolls_up():
    assert EventAggregator().convergence_stats() == {
        "profiles": 0,
        "steps_to_best": {"p50": None, "p99": None},
        "plateau_fraction": None,
        "accept_decay": None,
    }
    agg = EventAggregator.fold([Event(
        type=ev.SOLVE_PROFILE, ts=0.0, pool="shared",
        data={"n": 2, "profiles": [
            {"tenant": "a", "steps_to_best": 10, "plateau_fraction": 0.5,
             "accept_decay": 0.3},
            {"tenant": "b", "steps_to_best": 30, "plateau_fraction": 0.1,
             "accept_decay": 0.1},
        ]})])
    conv = agg.convergence_stats()
    assert conv["profiles"] == 2
    assert conv["steps_to_best"]["p50"] == pytest.approx(20.0)
    assert conv["plateau_fraction"] == pytest.approx(0.3)
    assert conv["accept_decay"] == pytest.approx(0.2)
    assert agg.pools["shared"]["solve_profiles"] == 1


def test_metrics_text_omits_missing_quantiles_never_fakes_zeros():
    """Before any traffic the aggregator's quantiles are ``None`` — the
    exposition must OMIT those samples (Prometheus has no null), while
    plain counters still render as zeros."""
    cluster = _cluster((4.0,))
    svc = PlannerService(_agora(cluster), DaemonConfig(
        pools=(PoolSpec("shared", shared_capacity=True, bucket_p=True),)))
    text = metrics_text(svc.stats())
    assert text.endswith("\n")
    assert "# TYPE planner_up gauge\nplanner_up 0" in text
    assert "planner_submitted_total 0" in text
    assert "planner_latency_seconds{" not in text          # None -> absent
    assert "planner_convergence_steps_to_best{" not in text
    assert "planner_convergence_plateau_fraction" not in text
    assert 'planner_pool_pending{pool="shared"} 0' in text
