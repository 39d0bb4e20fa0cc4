"""The benchmark's percentile, rate and interval arithmetic."""
import json
import math
import statistics

import numpy as np
import pytest

import spreads
from harness import manifest, stats, traffic


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_matches_numpy_linear(q):
    xs = list(np.random.default_rng(3).exponential(2.0, size=67))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_missing_requests_sit_above_every_served_one():
    xs = [1.0] * 9 + [math.inf]
    assert stats.percentile(xs, 50) == 1.0
    assert stats.percentile(xs, 100) == math.inf


def test_completion_rate_counts_whole_bursts_after_the_first():
    # batches of 8 plans every 4 s, each read back over a few milliseconds
    times = [4.0 * b + 0.001 * i for b in range(1, 6) for i in range(8)]
    rate = stats.completion_rate(times)
    assert rate == pytest.approx(32 / (times[-1] - times[0]))
    assert rate == pytest.approx(2.0, rel=0.01)


def test_completion_rate_of_a_steady_stream_is_its_rate():
    times = [0.1 * i for i in range(201)]
    assert stats.completion_rate(times) == pytest.approx(10.0, rel=0.02)


def test_completion_rate_needs_two_bursts():
    assert stats.completion_rate([1.0, 1.001, 1.002]) is None
    assert stats.completion_rate([]) is None


def test_merge_covered_and_gaps():
    iv = stats.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)])
    assert iv == [(0, 2.5), (3, 4)]
    assert stats.covered(iv, 0.0, 5.0) == pytest.approx(3.5)
    assert stats.covered(iv, 1.0, 3.5) == pytest.approx(2.0)
    assert stats.gaps(iv, 0.0, 5.0) == [(2.5, 3), (4, 5.0)]


def test_spread_is_iqr_over_median_by_statistics_quantiles():
    xs = [1.0, 1.1, 0.9, 1.05, 0.95, 1.2]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


def test_open_loop_schedule_belongs_to_the_traffic_file():
    t = manifest.load_cell("paper_m5-poisson").traffic
    due = traffic.due_offsets(t, 51.0)
    assert due == traffic.due_offsets(dict(t), 51.0)
    assert due != traffic.due_offsets(dict(t, arrival_seed=t["arrival_seed"]
                                           + 1), 51.0)
    # the same quantile gaps whatever their order: the rate is the file's
    gaps = sorted(b - a for a, b in zip(due, due[1:]))
    other = traffic.due_offsets(dict(t, arrival_seed=7), 51.0)
    assert len(due) == int(t["rate_per_s"] * 51.0) == len(other)
    assert due[0] == 0.0 and due[-1] < 51.0
    assert len(gaps) == len(due) - 1


def test_spreads_reports_each_sets_spread_and_the_bound(tmp_path):
    runs = {"s1": [1.00, 1.02, 0.98, 1.01, 0.99, 1.30],
            "s2": [1.00, 1.01, 0.99, 1.00, 1.02, 0.98]}
    sets = []
    for name, vals in runs.items():
        paths = []
        for i, v in enumerate(vals):
            p = tmp_path / f"{name}_{i}.out"
            p.write_text("warmup line\n" + json.dumps(
                {"correct": True, "metrics": {"plans_per_s": {
                    "value": v, "unit": "plans/s"}}}) + "\n")
            paths.append(str(p))
        sets.append(spreads.read_set(paths))
    r = spreads.report(sets)["plans_per_s"]
    assert r["spreads"] == [stats.spread(v) for v in runs.values()]
    assert r["widest"] == max(r["spreads"])
    assert r["suggested_bound"] == max(0.01, 5 * r["widest"])
    # the far-off 1.30 leaves the first set's tightness reading
    assert r["spreads_without_farthest"][0] == \
        stats.spread(runs["s1"][:5])
    assert r["median_change"] == [pytest.approx(
        statistics.median(runs["s2"]) / statistics.median(runs["s1"]) - 1)]
