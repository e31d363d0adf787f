"""Seeded inputs of the airline on-time shape (ASA Data Expo 2009, as
NVIDIA gbm-bench ``prepare_airline`` reads it): 13 feature columns in the
source's order, three of them string columns that an H2O client parses
as enum, and the label ``ArrDelay > 0``.

A pure function of ``--seed``, column-major: each column is one
contiguous array, float32 (NaN = missing) for a numeric column, int32
level codes for an enum column, whose domain (sorted strings, as H2O's
parser leaves them) comes with it.  The POPULATION is the data set's and
fixed (which level is how frequent, and each level's effect on the
response: drawn once, from ``POPULATION_SEED``); ``--seed`` draws the
rows, as ``benchmark/data.py``'s generator fixes its signal and draws
its rows.  Everything the source does not fix is listed under
``assumed`` in the configuration file: level frequencies, value ranges,
the NA share and the response model.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np

NAMES = ("Year", "Month", "DayofMonth", "DayofWeek", "CRSDepTime",
         "CRSArrTime", "UniqueCarrier", "FlightNum", "ActualElapsedTime",
         "Origin", "Dest", "Distance", "Diverted")
# level counts of the three string columns in the full data (assumed)
LEVELS = {"UniqueCarrier": 29, "Origin": 347, "Dest": 352}
RESPONSE, RESPONSE_DOMAIN = "IsArrDelayed", ("NO", "YES")

POPULATION_SEED = 2009     # the Data Expo's year
NA_SHARE = 0.02            # of ActualElapsedTime
DIVERTED_SHARE = 0.002
POSITIVE_SHARE = 0.45
# rows drawn per chunk: bounds the float64 temporaries of the response
_CHUNK = 1 << 20
# rows the intercept is fitted on (the first ones of every run)
_FIT_ROWS = 1 << 18


class AirlineData(NamedTuple):
    names: List[str]                 # the 13 feature columns, in order
    cols: List[np.ndarray]           # float32 or int32 codes, (rows,) each
    domains: Dict[str, List[str]]    # enum column -> its levels
    y: np.ndarray                    # (rows,) int32 in {0, 1}

    @property
    def card(self) -> List[int]:
        """Level count per column, 0 for a numeric one."""
        return [len(self.domains.get(n, ())) for n in self.names]


def _zipf_codes(rng, pop, rows: int, levels: int,
                shift: float) -> np.ndarray:
    """Level codes with Zipf-like frequencies 1 / (rank + shift); which
    code has which rank is a permutation of the population's, so a
    level's frequency says nothing about its code."""
    w = 1.0 / (np.arange(1, levels + 1) + shift)
    cdf = np.cumsum(w / w.sum())
    rank = np.minimum(np.searchsorted(cdf, rng.random(rows)), levels - 1)
    return pop.permutation(levels).astype(np.int32)[rank]


def airline_like(rows: int, seed: int) -> AirlineData:
    """``seed`` is any non-negative whole number."""
    rng = np.random.default_rng(int(seed))
    pop = np.random.default_rng(POPULATION_SEED)
    f32 = np.float32
    year = rng.integers(1987, 2009, rows).astype(f32)
    month = rng.integers(1, 13, rows).astype(f32)
    dom = rng.integers(1, 32, rows).astype(f32)
    dow = rng.integers(1, 8, rows).astype(f32)
    # scheduled departure: a day-shaped mix over 05:00-23:59, as hhmm
    dep_min = np.clip(rng.normal(13.5 * 60, 4.5 * 60, rows), 5 * 60,
                      24 * 60 - 1).astype(np.int32)
    dist = np.clip(np.exp(rng.normal(6.4, 0.75, rows)), 30.0,
                   4960.0).astype(f32)
    # minutes in the air and on the ground: 30 + distance at 7.5 miles
    # a minute, with a spread
    sched = 30.0 + dist / 7.5
    elapsed = np.round(sched * np.exp(rng.normal(0.0, 0.08, rows))
                       ).astype(f32)
    arr_min = (dep_min + np.round(sched).astype(np.int32)) % (24 * 60)
    elapsed[rng.random(rows) < NA_SHARE] = np.nan
    carrier = _zipf_codes(rng, pop, rows, LEVELS["UniqueCarrier"], 1.0)
    flight = rng.integers(1, 7000, rows).astype(f32)
    origin = _zipf_codes(rng, pop, rows, LEVELS["Origin"], 3.5)
    dest = _zipf_codes(rng, pop, rows, LEVELS["Dest"], 3.5)
    diverted = (rng.random(rows) < DIVERTED_SHARE).astype(f32)

    def hhmm(minutes):
        return (minutes // 60 * 100 + minutes % 60).astype(f32)

    cols = [year, month, dom, dow, hhmm(dep_min), hhmm(arr_min), carrier,
            flight, elapsed, origin, dest, dist, diverted]

    # the response: one random effect a level for each enum, a
    # time-of-day term, a distance term and a missing-value term
    eff_c = pop.normal(0.0, 0.5, LEVELS["UniqueCarrier"])
    eff_o = pop.normal(0.0, 0.8, LEVELS["Origin"])
    eff_d = pop.normal(0.0, 0.5, LEVELS["Dest"])
    u = rng.random(rows, dtype=f32)

    def logits(s):
        return (eff_c[carrier[s]] + eff_o[origin[s]] + eff_d[dest[s]]
                + 0.055 * (dep_min[s] / 60.0 - 13.5)
                + 0.25 * (np.log(dist[s].astype(np.float64)) - 6.4)
                + 1.2 * np.isnan(elapsed[s]) + 0.8 * diverted[s])

    # the intercept that gives the positive share: bisection on the
    # first rows
    z = logits(slice(0, min(rows, _FIT_ROWS)))
    lo, hi = -10.0, 10.0
    for _ in range(50):
        b0 = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(z + b0)))) < POSITIVE_SHARE:
            lo = b0
        else:
            hi = b0
    y = np.empty(rows, np.int32)
    for a in range(0, rows, _CHUNK):
        s = slice(a, min(a + _CHUNK, rows))
        y[s] = u[s] < 1.0 / (1.0 + np.exp(-(logits(s) + b0)))
    domains = {n: [f"{n[0]}{i:03d}" for i in range(k)]
               for n, k in LEVELS.items()}
    return AirlineData(list(NAMES), cols, domains, y)


GENERATORS = {"airline_like": airline_like}
