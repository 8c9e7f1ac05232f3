"""The one traffic generator: a traffic mix is a JSON file of parameters.

    {"arrivals": {"kind": "poisson", "rate_per_s": 4.0}
               | {"kind": "bursts", "bursts_per_s": 0.8, "size": [4, 12],
                  "span_s": 1.0},
     "models": [0.6, 0.3, 0.1],            # popularity of the served models
     "prompt": {"median": 1020, "sigma": 0.6, "min": 64, "max": 1792},
     "output": {"median": 129, "sigma": 0.7, "min": 8, "max": 256},
     "check":  {"min_tokens": 512, "max_requests": 16}}

Lengths are lognormal (``median * exp(sigma * z)``, clipped to
``[min, max]``); arrivals are an open loop.  Every seed gets the SAME
multiset of sizes, gaps, burst sizes and model shares, drawn at evenly
spaced quantiles of each distribution, in one balanced order (see
``_order``) fixed for the mix; the seed draws the prompt token ids (and
the harness draws the weights from it).  So every seed offers the same
work at the same times, and run-to-run spread measures the system, not
the draw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()
# The order of sizes and arrivals is drawn once, from this fixed seed:
# a 51 s window holds only tens of requests, and with the order drawn
# from the run's seed six seeds spread ttft_p95_s by 67% while repeats
# of one seed agreed within 5% (one TPU v5e, chat mix at 0.64 req/s).
ORDER_SEED = 0


@dataclass
class Request:
    due: float                  # seconds after the window opens
    model: int                  # index into the configuration's models
    tokens: np.ndarray          # (S,) int32 prompt
    steps: int                  # output tokens asked for
    idx: int = 0
    burst: int = -1             # the burst it belongs to, -1 for none
    # filled in by the harness
    submitted: float = math.nan
    first: float = math.nan
    done: float = math.nan
    output: np.ndarray = field(default=None, repr=False)


def _grid(n: int) -> np.ndarray:
    """``n`` evenly spaced quantile levels in (0, 1)."""
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The multiset of ``n`` lognormal lengths of ``spec`` (sorted)."""
    z = np.array([_NORMAL.inv_cdf(u) for u in _grid(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _shares(weights, n: int) -> np.ndarray:
    """``n`` labels split by ``weights`` with largest remainders.  Where
    ``n`` allows, every label with a share gets at least one: a short
    window holds a few bursts, and rounding alone would leave the least
    popular model out of the window, and its loads with it."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    base = (w > 0).astype(np.int64)
    if n < base.sum():
        base[:] = 0
    rest = np.clip(w * n - base, 0.0, None)
    if rest.sum() > 0:
        rest *= (n - base.sum()) / rest.sum()
    counts = base + np.floor(rest).astype(np.int64)
    frac = rest - np.floor(rest)
    for i in np.argsort(-frac, kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.arange(len(w)), counts)


# Irrational steps of the orders below (golden ratio, sqrt 2, sqrt 3,
# sqrt 5): distinct, so prompt, answer and gap orders do not line up.
_STEPS = {"gap": 0.6180339887, "prompt": 0.4142135624,
          "output": 0.7320508076, "burst": 0.2360679775}


def _order(n: int, rng, what: str) -> np.ndarray:
    """An order of ``n`` sorted values in which every stretch of
    consecutive positions holds values from across the whole range:
    position i takes the rank of ``frac(i * step + u)``, u drawn from
    ``rng``.  A plain shuffle can pile the longest requests or the
    shortest gaps into one part of a short window."""
    keys = (np.arange(n) * _STEPS[what] + rng.random()) % 1.0
    return np.argsort(np.argsort(keys, kind="stable"), kind="stable")


def _starts(n: int, span: float, rng) -> np.ndarray:
    """``n`` Poisson arrival times inside ``[0, span)``: exponential gaps
    at evenly spaced quantiles, in a seeded balanced order, scaled so the
    mean rate is exactly ``n`` per ``span * n / (n + 1)``."""
    if n == 0:
        return np.zeros(0)
    gaps = (-np.log1p(-_grid(n)))[_order(n, rng, "gap")]
    return np.cumsum(gaps) * (span * n / (n + 1)) / gaps.sum()


def max_len(spec: dict, page: int) -> int:
    """Cache rows a request of this mix can need, rounded up to pages."""
    need = spec["prompt"]["max"] + spec["output"]["max"]
    return -(-need // page) * page


def schedule(spec: dict, seed: int, seconds: float, vocab: int,
             n_models: int = 1) -> list[Request]:
    """The requests of one window of ``seconds``, in due order.  The
    seed draws the prompts' token ids; sizes, arrivals and their order
    are the mix's own, the same for every seed."""
    rng = np.random.default_rng(ORDER_SEED)
    arr = spec["arrivals"]
    shares = spec.get("models", [1.0])
    if len(shares) != n_models:
        raise ValueError(f"traffic names {len(shares)} model shares, the "
                         f"configuration serves {n_models} models")
    if arr["kind"] == "poisson":
        n = int(round(arr["rate_per_s"] * seconds))
        due = _starts(n, seconds, rng)
        model = rng.permutation(_shares(shares, n))
        group = np.full(n, -1)
    elif arr["kind"] == "bursts":
        nb = max(1, int(round(arr["bursts_per_s"] * seconds)))
        lo, hi = arr["size"]
        sizes = lo + np.floor(_grid(nb) * (hi - lo + 1)).astype(np.int64)
        # each model's bursts spread evenly over the sizes, so every seed
        # sends each model the same requests; the seed orders the bursts
        labels = _shares(shares, nb)
        rank = np.empty(nb)
        for m in np.unique(labels):
            idx = np.flatnonzero(labels == m)
            rank[idx] = (np.arange(len(idx)) + 0.5) / len(idx)
        bmodel = labels[np.argsort(rank, kind="stable")]
        order = _order(nb, rng, "burst")
        sizes, bmodel = sizes[order], bmodel[order]
        span = arr["span_s"]
        starts = _starts(nb, max(seconds - span, 0.0), rng)
        due = np.concatenate([s + span * np.arange(k) / k
                              for s, k in zip(starts, sizes)])
        model = np.repeat(bmodel, sizes)
        group = np.repeat(np.arange(nb), sizes)
        n = len(due)
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    prompts = lengths(spec["prompt"], n)[_order(n, rng, "prompt")]
    outputs = lengths(spec["output"], n)[_order(n, rng, "output")]
    order = np.argsort(due, kind="stable")
    rng = np.random.default_rng(seed)
    reqs = []
    for i, j in enumerate(order):
        toks = rng.integers(0, vocab, int(prompts[j]), dtype=np.int64)
        reqs.append(Request(due=float(due[j]), model=int(model[j]),
                            tokens=toks.astype(np.int32),
                            steps=int(outputs[j]), idx=i,
                            burst=int(group[j])))
    return reqs
