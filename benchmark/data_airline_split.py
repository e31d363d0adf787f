"""The airline on-time shape as NVIDIA gbm-bench uses it: drawn once,
split 80 / 20 (``train_test_split(X, y, test_size=0.2)``), and each part
written to a file of its own, so that an H2O client that imports the two
files gets two frames with two sets of enum domains: the sorted level
strings present in THAT file.

A pure function of ``--seed``.  The population is
``benchmark/data_airline.py``'s (nothing there is edited: its columns,
level effects and response are used as they are) with one change, the
LEVEL TAIL: the published data's rarest airports have a handful of
flights in 22 years, where that population gives its rarest level 9,000
rows in 14 million, so that two files would always parse to equal
domains.  Here the ``TAIL_LEVELS`` rarest ``Origin`` and ``Dest`` levels
are ``TAIL_FACTOR`` times as rare and the rest renormalised: a row whose
origin or destination is a tail level is kept with probability
``TAIL_FACTOR``, which leaves the response given the columns as it was.
Expected rows a tail level: 0.9 in all 14,375,000, 0.75 in the training
part, 0.19 in the validation part, so every seed has levels that only
training holds (8-15 a column on the seeds read) and most seeds have
levels that only validation holds (0-6 a column, 0-9 rows in all).
Which levels are the tail is the population's (fixed); ``--seed`` draws
the rows and the split.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np

from benchmark.data_airline import (LEVELS, NAMES, POPULATION_SEED,
                                    airline_like)

TAIL_LEVELS = 24           # rarest ranks of Origin and of Dest
TAIL_FACTOR = 1e-4         # how much rarer they are than the population's
_TAILED = ("Origin", "Dest")


class Part(NamedTuple):
    """One file of the split.  An enum column holds level IDENTITIES:
    the population's level numbers, the same in both parts (identity i
    of column ``Origin`` is the string ``O<i:03d>``)."""
    cols: List[np.ndarray]      # float32, or int32 identities
    y: np.ndarray               # (rows,) int32 in {0, 1}

    def domain_ids(self, j: int) -> np.ndarray:
        """The sorted identities present in enum column ``j`` (below 0 =
        missing: no level): the frame's domain, as a parser leaves it."""
        ids = self.cols[j]
        return np.unique(ids[ids >= 0])


class AirlineSplit(NamedTuple):
    names: List[str]
    enum: Dict[str, int]        # enum column -> the population's level count
    train: Part
    valid: Part


def level_name(column: str, identity: int) -> str:
    return f"{column[0]}{identity:03d}"


def tail_ids() -> Dict[str, np.ndarray]:
    """The identities of each tailed column's rarest levels.  The
    population draws, in order, which identity has which frequency rank
    for the carrier, the origin and the destination
    (``data_airline._zipf_codes``: ``pop.permutation(levels)[rank]``)."""
    pop = np.random.default_rng(POPULATION_SEED)
    by_rank = {n: pop.permutation(LEVELS[n])
               for n in ("UniqueCarrier", "Origin", "Dest")}
    return {n: np.sort(by_rank[n][-TAIL_LEVELS:]) for n in _TAILED}


def tail_mass() -> float:
    """Share of the population's rows that hold a tail level."""
    keep = 1.0
    for n in _TAILED:
        w = 1.0 / (np.arange(1, LEVELS[n] + 1) + 3.5)
        keep *= 1.0 - w[-TAIL_LEVELS:].sum() / w.sum()
    return 1.0 - keep


def airline_split(rows: int, valid_rows: int, seed: int) -> AirlineSplit:
    """``rows`` training and ``valid_rows`` validation rows."""
    total = rows + valid_rows
    # enough of the population that ``total`` rows survive the thinning
    # (five standard deviations of room)
    p = 1.0 - tail_mass() * (1.0 - TAIL_FACTOR)
    draw = int(total / p + 5.0 * np.sqrt(total) + 64)
    data = airline_like(draw, seed)
    rng = np.random.default_rng([int(seed), 20])
    keep = np.ones(draw, bool)
    for n, ids in tail_ids().items():
        col = data.cols[data.names.index(n)]
        keep &= ~np.isin(col, ids) | (rng.random(draw) < TAIL_FACTOR)
    kept = np.flatnonzero(keep)[:total]
    if len(kept) < total:
        raise RuntimeError(f"{len(kept)} of {total} rows survived")
    in_valid = np.zeros(total, bool)
    in_valid[rng.permutation(total)[:valid_rows]] = True
    parts = []
    for mask in (~in_valid, in_valid):
        idx = kept[mask]
        parts.append(Part([c[idx] for c in data.cols], data.y[idx]))
    return AirlineSplit(list(NAMES), dict(LEVELS), parts[0], parts[1])


def as_frame_columns(split: AirlineSplit, part: Part):
    """The part as its own file parses: ``(cols, domains)`` with each
    enum column in the codes of its OWN domain, the sorted level strings
    present in this part."""
    cols, domains = [], {}
    for j, n in enumerate(split.names):
        if n not in split.enum:
            cols.append(part.cols[j])
            continue
        ids = part.domain_ids(j)
        domains[n] = [level_name(n, int(i)) for i in ids]
        cols.append(np.where(part.cols[j] >= 0,
                             np.searchsorted(ids, part.cols[j]),
                             -1).astype(np.int32))
    return cols, domains


GENERATORS = {"airline_like_split": airline_split}
