import random

import pytest

from harness import estimators as est


def _rounds(period=0.147, per_round=15, chunk_every=4, chunk=0.194, until=80.0, jitter=0.0005):
    """Token event times of a decode loop: a burst of tokens per round, every
    few rounds a prefill chunk that emits none."""
    rng, t, out, n = random.Random(1), 0.0, [], 0
    while t < until:
        t += period + (chunk if n % chunk_every == 0 else 0.0)
        out += [t + rng.uniform(0, jitter) for _ in range(per_round)]
        n += 1
    return sorted(out)


def test_aligned_rate_does_not_depend_on_where_the_edges_fall():
    times = _rounds()
    true_rate = 15 / (0.147 + 0.194 / 4)
    aligned, naive = [], []
    for k in range(40):  # slide the window's edges through one round and more
        t0 = 10.0 + 0.0093 * k
        r = est.aligned_rate(times, t0, t0 + 30.0)
        aligned.append(r["value"])
        naive.append(r["naive"])
    # the aligned estimate moves only with which chunk rounds the span holds
    assert max(aligned) - min(aligned) < 0.012 * true_rate
    assert all(abs(a - true_rate) < 0.012 * true_rate for a in aligned)
    # with no chunks at all it is exact, where the naive count quantises by a round
    flat = _rounds(chunk_every=10**9)
    a = [est.aligned_rate(flat, 10.0 + 0.0093 * k, 40.0 + 0.0093 * k) for k in range(40)]
    assert max(x["value"] for x in a) - min(x["value"] for x in a) < 1e-3 * 15 / 0.147
    assert max(x["naive"] for x in a) - min(x["naive"] for x in a) >= 15 / 30.0 - 1e-9


def test_first_burst_only_anchors_the_clock():
    times = [1.0, 1.001, 2.0, 2.001, 2.002, 3.0]
    r = est.aligned_rate(times, 0.5, 3.5)
    assert r["counted"] == 4 and r["bursts"] == 3
    assert r["value"] == pytest.approx(4 / 2.0)
    # an edge that cuts a burst: the part inside still only anchors
    r = est.aligned_rate(times, 1.0005, 3.5)
    assert r["counted"] == 4 and r["value"] == pytest.approx(4 / (3.0 - 1.001))
    assert est.aligned_rate([1.0, 1.001], 0.0, 2.0) is None


def test_quantile_reports_its_sample_counts():
    q = est.quantile(range(1, 101), 0.95)
    assert (q["value"], q["n"], q["beyond"]) == (95, 100, 5)
    q = est.quantile([3.0, 1.0, 2.0], 0.5)
    assert (q["value"], q["n"], q["beyond"]) == (2.0, 3, 1)
    assert est.quantile([], 0.5) is None
    # 45 requests: a 95th percentile has two samples beyond it, a 90th four
    assert est.quantile(range(45), 0.95)["beyond"] == 2
    assert est.quantile(range(45), 0.90)["beyond"] == 4


def test_gaps_exclude_first_tokens_and_follow_the_later_event():
    streams = [[0.9, 1.1, 1.3], [1.95, 2.05]]
    assert est.gaps_in_window(streams, 1.0, 2.0) == pytest.approx([0.2, 0.2])
    assert est.gaps_in_window(streams, 2.0, 3.0) == pytest.approx([0.1])


def test_upper_plateau_share():
    gaps = [0.147] * 60 + [0.341] * 40
    assert est.upper_plateau_share(gaps) == pytest.approx(0.4)
    # three plateaus (step; step + small chunk; step + large chunk), most gaps with a chunk
    assert est.upper_plateau_share([0.134] * 40 + [0.284] * 20 + [0.383] * 40) == pytest.approx(0.6)
    assert est.quantile(gaps, 0.95)["value"] == 0.341


def test_spread_is_the_drivers_rule():
    import statistics

    vals = [59.1, 59.6, 59.4, 60.2, 59.9, 59.5]
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert est.spread(vals) == pytest.approx((q3 - q1) / med)
