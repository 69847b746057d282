"""Seeded traffic: one general generator per mix kind, driven only by
the parameters in `benchmark/traffic/<mix>.json`.

Every seed gets the SAME multiset of request sizes (a quantile grid of
the mix's distributions), in another order and with other token ids, so
that two seeds differ in arrangement and not in the amount of work.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    # SeedSequence takes any non-negative int, however large
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def lognormal_grid(n: int, median: float, sigma: float, lo: int, hi: int):
    """`n` integers at the (i + 0.5) / n quantiles of a log-normal with
    the given median and log-space sigma, clipped to [lo, hi]."""
    nd = NormalDist()
    qs = [(i + 0.5) / n for i in range(n)]
    vals = [median * math.exp(sigma * nd.inv_cdf(q)) for q in qs]
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def serve_requests(mix: dict, vocab: int, seed: int):
    """The run's request list: [(prompt ids int64, max_new int)], as
    many as `mix["requests"]`; a loop that needs more cycles through it.
    Prompt and reply lengths are shuffled apart."""
    n = int(mix["requests"])
    p, g = mix["prompt_len"], mix["max_new"]
    plens = lognormal_grid(n, p["median"], p["sigma"], p["min"], p["max"])
    news = lognormal_grid(n, g["median"], g["sigma"], g["min"], g["max"])
    r = _rng(seed, 1)
    plens = plens[r.permutation(n)]
    news = news[r.permutation(n)]
    toks = r.integers(1, vocab, size=int(plens.sum()), dtype=np.int64)
    out, at = [], 0
    for pl, mn in zip(plens, news):
        out.append((toks[at:at + pl], int(mn)))
        at += int(pl)
    return out


def arrival_times(mix: dict, seed: int, horizon_s: float):
    """Open-loop arrival instants in [0, horizon): gamma gaps with mean
    1/rate and coefficient of variation `cv` (cv = 1 is Poisson)."""
    rate = float(mix["rate_per_s"])
    cv = float(mix.get("arrival_cv", 1.0))
    shape = 1.0 / (cv * cv)
    r = _rng(seed, 2)
    n = int(rate * horizon_s * 1.5) + 16
    gaps = r.gamma(shape, 1.0 / (rate * shape), size=n)
    t = np.cumsum(gaps)
    return t[t < horizon_s]


def token_batches(seed: int, pool: int, batch: int, seq: int, vocab: int):
    """`pool` LM batches as one (pool, batch, seq + 1) int32 array: ids
    are [..., :-1], next-token labels [..., 1:]."""
    return _rng(seed, 3).integers(
        0, vocab, size=(pool, batch, seq + 1), dtype=np.int32)
