"""The traffic generator: same seed, same schedule; every seed the same
multiset of sizes and arrivals; lengths clipped and distributed as the
mix declares."""
import chipbench_testkit  # noqa: F401
import numpy as np
import pytest

from chipbench import traffic

MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 5.0},
       "models": [1.0],
       "prompt": {"median": 1020, "sigma": 0.6, "min": 64, "max": 1792},
       "output": {"median": 129, "sigma": 0.7, "min": 8, "max": 256}}
BURSTS = dict(MIX, arrivals={"kind": "bursts", "bursts_per_s": 0.5,
                             "size": [4, 12], "span_s": 1.0},
              models=[0.6, 0.3, 0.1])
SEED = 2 ** 31 + 12345          # seeds go past 32 signed bits


def _key(reqs):
    return [(r.due, r.model, r.steps, r.tokens.tobytes()) for r in reqs]


@pytest.mark.parametrize("mix", [MIX, BURSTS], ids=["poisson", "bursts"])
def test_same_seed_same_schedule(mix):
    a = traffic.schedule(mix, SEED, 40, 102400, len(mix["models"]))
    b = traffic.schedule(mix, SEED, 40, 102400, len(mix["models"]))
    assert _key(a) == _key(b)
    c = traffic.schedule(mix, SEED + 1, 40, 102400, len(mix["models"]))
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", [MIX, BURSTS], ids=["poisson", "bursts"])
def test_every_seed_offers_the_same_work(mix):
    n = len(mix["models"])
    runs = [traffic.schedule(mix, s, 40, 1000, n) for s in (1, 2, SEED)]
    for f in (lambda r: len(r.tokens), lambda r: r.steps,
              lambda r: r.model):
        assert len({tuple(sorted(map(f, reqs))) for reqs in runs}) == 1
    gaps = [np.sort(np.diff([0.0] + [r.due for r in reqs])) for reqs in runs]
    if mix["arrivals"]["kind"] == "poisson":
        np.testing.assert_allclose(gaps[0], gaps[1], atol=1e-9)


def test_lengths_clipped_and_distributed_as_declared():
    reqs = traffic.schedule(MIX, 3, 400, 1000)
    p = np.array([len(r.tokens) for r in reqs])
    o = np.array([r.steps for r in reqs])
    assert len(reqs) == 2000
    assert p.min() >= 64 and p.max() <= 1792
    assert o.min() >= 8 and o.max() <= 256
    assert abs(np.median(p) - 1020) <= 2 and abs(np.median(o) - 129) <= 1
    # lognormal shape: the quartiles sit at median * exp(+-0.674 sigma)
    q1, q3 = np.percentile(p, [25, 75])
    assert q1 == pytest.approx(1020 * np.exp(-0.6745 * 0.6), rel=0.01)
    assert q3 == pytest.approx(1020 * np.exp(0.6745 * 0.6), rel=0.01)
    due = np.array([r.due for r in reqs])
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 400


def test_bursts_sizes_span_and_popularity():
    reqs = traffic.schedule(BURSTS, 5, 400, 1000, 3)
    models = np.array([r.model for r in reqs])
    share = np.bincount(models, minlength=3) / len(models)
    assert share == pytest.approx([0.6, 0.3, 0.1], abs=0.05)
    assert max(r.due for r in reqs) < 400
    bursts = {}
    for r in reqs:
        bursts.setdefault(r.burst, []).append(r)
    assert len(bursts) == 200 and -1 not in bursts
    sizes = sorted(len(b) for b in bursts.values())
    assert sizes[0] == 4 and sizes[-1] == 12
    for b in bursts.values():
        assert len({r.model for r in b}) == 1
        assert max(r.due for r in b) - min(r.due for r in b) < 1.0


def test_model_shares_must_match_the_configuration():
    with pytest.raises(ValueError, match="model shares"):
        traffic.schedule(BURSTS, 1, 10, 100, 2)


def test_max_len_rounds_up_to_pages():
    assert traffic.max_len(MIX, 256) == 2048
    assert traffic.max_len(dict(MIX, output={"max": 64}), 256) == 2048
    assert traffic.max_len(dict(MIX, prompt={"max": 3840},
                                output={"max": 64}), 256) == 4096


@pytest.mark.parametrize("seed", [1, 2, SEED])
def test_orders_are_balanced_along_the_window(seed):
    # any 8 consecutive requests carry prompts from across the range, so a
    # short window cannot pile its longest prompts into one stretch
    reqs = traffic.schedule(MIX, seed, 40, 1000)
    p = np.array([len(r.tokens) for r in reqs], float)
    q1, q3 = np.percentile(p, [25, 75])
    for i in range(len(p) - 7):
        block = p[i:i + 8]
        assert block.min() <= q1 and block.max() >= q3
    gaps = np.diff([0.0] + [r.due for r in reqs])
    thirds = np.array_split(gaps, 3)
    assert max(t.sum() for t in thirds) < 1.3 * min(t.sum() for t in thirds)


@pytest.mark.parametrize("bursts,counts", [(3, [1, 1, 1]), (4, [2, 1, 1]),
                                           (5, [3, 1, 1]), (6, [3, 2, 1]),
                                           (10, [6, 3, 1]), (2, [1, 1, 0])])
def test_every_model_gets_a_burst_where_the_window_allows(bursts, counts):
    mix = dict(BURSTS, arrivals=dict(BURSTS["arrivals"], bursts_per_s=1.0))
    reqs = traffic.schedule(mix, SEED, bursts, 1000, 3)
    per_model = [len({r.burst for r in reqs if r.model == m})
                 for m in range(3)]
    assert per_model == counts
