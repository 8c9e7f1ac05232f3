"""Tail arithmetic over raw samples, and the spread of runs."""
import chipbench_testkit  # noqa: F401
import statistics

import numpy as np
import pytest

from chipbench.tails import percentile, spread


@pytest.mark.parametrize("n", [1, 2, 7, 20, 201])
@pytest.mark.parametrize("q", [0, 50, 95, 99, 100])
def test_percentile_interpolates_order_statistics(n, q):
    xs = np.random.default_rng(n).lognormal(0, 1, n)
    assert percentile(list(xs), q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_none_and_q_is_checked():
    assert percentile([], 95) is None
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_p95_is_a_raw_tail_not_a_bucket_edge():
    # 100 samples: 95 at 1 ms, 5 at 30 ms -> the 95th percentile lies
    # between them, not at a histogram bucket's upper edge
    xs = [0.001] * 95 + [0.030] * 5
    assert percentile(xs, 95) == pytest.approx(0.001 + 0.05 * 0.029)


def test_spread_uses_statistics_quartiles():
    xs = [10.0, 10.2, 9.9, 10.4, 10.1, 9.7]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert spread(xs) == pytest.approx((q3 - q1) / med)


def test_first_tokens_pair_in_submit_order():
    from chipbench.harness import first_tokens
    from chipbench.traffic import Request
    reqs = [Request(due=0.0, model=0, tokens=np.zeros(1, np.int32),
                    steps=1) for _ in range(4)]
    for r, s in zip(reqs, (1.000, 1.010, 1.020, 1.500)):
        r.submitted = s
    # program stamps lag the benchmark's by up to 8 ms (a GIL switch),
    # each before the next submit; request 2 never got a first token
    stamps = {0: 1.008, 1: 1.0185, 3: 1.5001}
    firsts = {0: 3.0, 1: 2.0, 3: 4.0}
    observed = [(firsts[i], firsts[i] - stamps[i]) for i in (3, 1, 0)]
    first_tokens(reqs, observed)
    assert [r.first for r in reqs[:2]] == [3.0, 2.0]
    assert np.isnan(reqs[2].first) and reqs[3].first == 4.0


def test_knee_is_the_last_rate_without_a_growing_backlog():
    from chipbench.sweep import knee, sustained

    def row(rate, first, last, drain, answered=10):
        return {"rate": rate, "requests": 10, "answered": answered,
                "latency_first_third_s": first, "latency_last_third_s": last,
                "latency_p50_s": first, "latency_p95_s": 2 * first,
                "drain_s": drain}
    rows = [row(1, 2.0, 2.1, 3.0), row(2, 2.0, 3.4, 5.0),
            row(3, 2.0, 6.0, 9.0), row(4, 2.0, 2.0, 2.0)]
    assert [sustained(r) for r in rows] == [True, True, False, True]
    assert knee(rows) == 2
    assert knee([row(1, 2.0, 2.0, 1.0, answered=9)]) is None


def test_a_burst_mix_is_judged_by_its_drain_alone():
    from chipbench.sweep import knee, sustained
    # rows as the switching mix's sweeps read them on the chip: the last
    # third slower than the first where a load falls late, yet every
    # answer in before the close; at 0.16 a backlog left 7.8 s of work
    rows = [{"rate": r, "kind": "bursts", "requests": n, "answered": n,
             "latency_first_third_s": f, "latency_last_third_s": l,
             "latency_p50_s": p50, "latency_p95_s": 2 * p50, "drain_s": d}
            for r, n, f, l, p50, d in [(0.06, 15, 2.8, 6.5, 5.57, -6.12),
                                       (0.08, 20, 3.0, 6.4, 4.07, -1.14),
                                       (0.12, 30, 3.1, 3.3, 3.36, -2.04),
                                       (0.16, 40, 3.4, 10.1, 7.86, 7.80)]]
    assert [sustained(r) for r in rows] == [True, True, True, False]
    assert knee(rows) == 0.12
