"""Seeded inputs of the airline on-time shape made in row blocks, in
parallel: ``benchmark/data_airline.py``'s population (which level is how
frequent, each level's effect on the response: drawn from its
``POPULATION_SEED`` in the same order) with the rows drawn block by
block, block ``k`` from ``default_rng([seed, k])``, on the host's cores.

The same columns, value ranges, NA share, response model and domains as
``airline_like``; only how the rows are drawn differs, so a frame of
tens of millions of rows is made in seconds (the numpy draws release the
GIL: one thread a block).  The intercept is fitted, as ``airline_like``
fits it, on the first rows of the frame.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.data_airline import (_FIT_ROWS, DIVERTED_SHARE, LEVELS,
                                    NA_SHARE, NAMES, POPULATION_SEED,
                                    POSITIVE_SHARE, AirlineData)

BLOCK_ROWS = 1 << 21


def _population():
    """``airline_like``'s population, drawn in its order: the three rank
    permutations, then the three sets of level effects."""
    pop = np.random.default_rng(POPULATION_SEED)
    perms = [pop.permutation(LEVELS[n]).astype(np.int32)
             for n in ("UniqueCarrier", "Origin", "Dest")]
    effects = [pop.normal(0.0, sd, LEVELS[n]) for n, sd in
               (("UniqueCarrier", 0.5), ("Origin", 0.8), ("Dest", 0.5))]
    return perms, effects


def _codes(rng, perm, rows: int, shift: float) -> np.ndarray:
    levels = len(perm)
    w = 1.0 / (np.arange(1, levels + 1) + shift)
    cdf = np.cumsum(w / w.sum())
    rank = np.minimum(np.searchsorted(cdf, rng.random(rows)), levels - 1)
    return perm[rank]


def _block(cols, z, u, a: int, b: int, seed: int, k: int, perms, effects):
    """Rows [a, b) from ``default_rng([seed, k])``: the columns, the
    logit without its intercept and the uniform draw of the label."""
    rng = np.random.default_rng([int(seed), int(k)])
    f32, n = np.float32, b - a
    cols[0][a:b] = rng.integers(1987, 2009, n)
    cols[1][a:b] = rng.integers(1, 13, n)
    cols[2][a:b] = rng.integers(1, 32, n)
    cols[3][a:b] = rng.integers(1, 8, n)
    dep_min = np.clip(rng.normal(13.5 * 60, 4.5 * 60, n), 5 * 60,
                      24 * 60 - 1).astype(np.int32)
    dist = np.clip(np.exp(rng.normal(6.4, 0.75, n)), 30.0,
                   4960.0).astype(f32)
    sched = 30.0 + dist / 7.5
    elapsed = np.round(sched * np.exp(rng.normal(0.0, 0.08, n))).astype(f32)
    arr_min = (dep_min + np.round(sched).astype(np.int32)) % (24 * 60)
    elapsed[rng.random(n) < NA_SHARE] = np.nan
    carrier = _codes(rng, perms[0], n, 1.0)
    cols[7][a:b] = rng.integers(1, 7000, n)
    origin = _codes(rng, perms[1], n, 3.5)
    dest = _codes(rng, perms[2], n, 3.5)
    diverted = (rng.random(n) < DIVERTED_SHARE).astype(f32)
    cols[4][a:b] = dep_min // 60 * 100 + dep_min % 60
    cols[5][a:b] = arr_min // 60 * 100 + arr_min % 60
    cols[6][a:b] = carrier
    cols[8][a:b] = elapsed
    cols[9][a:b] = origin
    cols[10][a:b] = dest
    cols[11][a:b] = dist
    cols[12][a:b] = diverted
    z[a:b] = (effects[0][carrier] + effects[1][origin] + effects[2][dest]
              + 0.055 * (dep_min / 60.0 - 13.5)
              + 0.25 * (np.log(dist.astype(np.float64)) - 6.4)
              + 1.2 * np.isnan(elapsed) + 0.8 * diverted)
    u[a:b] = rng.random(n, dtype=f32)


def airline_blocked(rows: int, seed: int, threads: int = 0) -> AirlineData:
    """``seed`` is any non-negative whole number; ``threads`` 0 = one a
    core."""
    threads = threads or os.cpu_count() or 1
    perms, effects = _population()
    cat = {6, 9, 10}
    cols = [np.empty(rows, np.int32 if j in cat else np.float32)
            for j in range(len(NAMES))]
    z = np.empty(rows, np.float64)
    u = np.empty(rows, np.float32)
    starts = range(0, rows, BLOCK_ROWS)
    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(lambda kb: _block(cols, z, u, kb[1],
                                      min(kb[1] + BLOCK_ROWS, rows), seed,
                                      kb[0], perms, effects),
                    enumerate(starts)))
    zf = z[:min(rows, _FIT_ROWS)]
    lo, hi = -10.0, 10.0
    for _ in range(50):
        b0 = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(zf + b0)))) < POSITIVE_SHARE:
            lo = b0
        else:
            hi = b0
    y = np.empty(rows, np.int32)

    def label(a):
        s = slice(a, min(a + BLOCK_ROWS, rows))
        y[s] = u[s] < 1.0 / (1.0 + np.exp(-(z[s] + b0)))

    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(label, starts))
    domains = {n: [f"{n[0]}{i:03d}" for i in range(k)]
               for n, k in LEVELS.items()}
    return AirlineData(list(NAMES), cols, domains, y)


GENERATORS = {"airline_blocked": airline_blocked}
