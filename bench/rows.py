"""Depth rows and arrival times, drawn from the run's seed.

A stream of a traffic file names how its rows are drawn (``rows``):

* ``draw`` — the search driver whose rows the stream sends:
  ``"random_search"`` draws uniformly from [``lo``, ``hi``]^F, as the
  service's own ``repro.sweep.search.random_search`` does (its defaults are
  lo 1, hi 16);
* ``repeat_share`` (default 0) — the share of rows that repeat an earlier
  row of the same stream, as a successive-halving search resubmits its
  survivors; the scheduler's memo answers those.

Apart from the planned repeats, rows are unique across all the streams of
a run.
"""
from __future__ import annotations

import numpy as np


def _keys(D: np.ndarray, chunk: int = 4096) -> np.ndarray:
    """One 64-bit key per row of D: a weighted sum of its depths modulo
    2**64, with fixed random weights.  Equal rows get equal keys; two
    different rows share one with odds of about 2**-64."""
    w = np.random.default_rng(0).integers(1, 2**63, D.shape[1],
                                          dtype=np.uint64)
    return np.concatenate([
        (D[i:i + chunk].astype(np.uint64) * w).sum(axis=1, dtype=np.uint64)
        for i in range(0, len(D), chunk)] or [np.empty(0, np.uint64)])


def _random_search(rng, n: int, F: int, p: dict) -> np.ndarray:
    return rng.integers(int(p.get("lo", 1)), int(p.get("hi", 16)) + 1,
                        (n, F), dtype=np.int64)


DRAWS = {"random_search": _random_search}


def _repeat(D: np.ndarray, share: float, rng) -> np.ndarray:
    """D with about ``share`` of its rows (never the first) replaced by a
    copy of a uniformly chosen earlier row."""
    n = len(D)
    src = np.arange(n)
    rep = rng.random(n) < share
    rep[0] = False
    src[rep] = (rng.random(int(rep.sum())) * np.flatnonzero(rep)).astype(
        np.int64)
    while rep[src].any():               # a copy of a copy: follow it back
        src = np.where(rep[src], src[src], src)
    return D[src]


def draw_streams(F: int, wants, rng) -> list:
    """Rows for each ``(n, rows_spec)`` of ``wants``: an (n, F) int64
    array each, unique across the streams but for planned repeats."""
    out, seen = [], np.empty(0, np.uint64)
    for n, p in wants:
        draw = DRAWS[p["draw"]]
        D = np.empty((0, F), np.int64)
        while len(D) < n:
            X = draw(rng, n - len(D) + 16, F, p)
            k = _keys(X)
            _, first = np.unique(k, return_index=True)
            first = np.sort(first)
            X, k = X[first], k[first]
            fresh = ~np.isin(k, seen)
            D = np.concatenate([D, X[fresh]])
            seen = np.concatenate([seen, k[fresh]])
        D = D[:n]
        share = float(p.get("repeat_share", 0.0))
        out.append(_repeat(D, share, rng) if share > 0 else D)
    return out


def arrivals(rate: float, seconds: float, gap_seed: int, rng,
             burst: int = 1) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop stream of
    ``rate`` requests per second arriving ``burst`` at a time.  Every seed
    gets the same set of exponential gaps, drawn once from ``gap_seed``
    and scaled to fill the window, in its own order; so every run sends
    the same number of requests."""
    n = max(int(round(rate * seconds / burst)), 1)
    gaps = np.random.default_rng(gap_seed).exponential(burst / rate, n)
    gaps *= seconds / gaps.sum()
    gaps = rng.permutation(gaps)
    return np.repeat(np.concatenate([[0.0], np.cumsum(gaps)[:-1]]), burst)
