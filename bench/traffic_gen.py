"""The one request generator: an open-loop schedule from a traffic file.

Every seed gets the same work in another order: the inter-arrival gaps are
the n quantiles of an exponential distribution at the file's rate (a
Poisson process), the request sizes the n quantiles of a log-uniform
distribution on [min, max] rows, and the routes the file's mix rounded to
whole requests; the seed permutes each list and picks where in the data
pool each request's rows start.
"""
from __future__ import annotations

import math

import numpy as np

ROUTES = ("multimodal", "unimodal_A", "unimodal_B", "vfl_fallback")


def _counts(n: int, mix: dict) -> list:
    """Whole requests per route, by largest remainder."""
    want = [n * mix.get(r, 0.0) for r in ROUTES]
    base = [int(math.floor(w)) for w in want]
    order = sorted(range(len(ROUTES)), key=lambda i: base[i] - want[i])
    for i in order[: n - sum(base)]:
        base[i] += 1
    return base


def schedule(traffic: dict, seed: int, seconds: float, pool_rows: int) -> dict:
    """Arrays of length n = rate x seconds: ``due`` (s from the window's
    start), ``rows``, ``route`` (index into ROUTES) and ``offset`` (first
    pool row)."""
    if not isinstance(traffic["route_mix"], dict):
        raise ValueError("resolve the route mix first "
                         f"(got {traffic['route_mix']!r})")
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    lo, hi = traffic["rows"]["min"], traffic["rows"]["max"]
    if pool_rows < hi:
        raise ValueError(f"a pool of {pool_rows} rows cannot serve {hi}-row "
                         "requests")
    rows = np.floor(np.exp(math.log(lo) + rng.permutation(q)
                           * (math.log(hi + 1) - math.log(lo)))).astype(np.int64)
    rows = np.clip(rows, lo, hi)
    route = rng.permutation(np.repeat(np.arange(len(ROUTES)),
                                      _counts(n, traffic["route_mix"])))
    offset = rng.integers(0, pool_rows - rows + 1)
    return {"due": np.cumsum(gaps), "rows": rows, "route": route,
            "offset": offset}


def check_sample(sched: dict, seed: int, k: int) -> np.ndarray:
    """Indices of the requests whose answers are compared: ``k`` drawn
    from the seed, with the longest request among them."""
    n = len(sched["rows"])
    rng = np.random.default_rng([seed, 1])
    pick = set(rng.choice(n, size=min(k, n), replace=False).tolist())
    pick.add(int(np.argmax(sched["rows"])))
    return np.asarray(sorted(pick))
