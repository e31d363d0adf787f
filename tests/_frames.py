"""Seeded mixed-type frames (numeric columns with missing values beside
enum columns of 3 / 29 / 352 levels) for the tests that need one."""

import numpy as np

from h2o_tpu.core.frame import Frame, Vec, T_CAT

CARDS = (3, 29, 352)
NAMES = ["n0", "c3", "n1", "c29", "c352", "n2"]
CARD = [0, 3, 0, 29, 352, 0]


def mixed_columns(seed: int, na_share: float, rows: int = 4000):
    """Three numeric columns (the first with missing values) and three
    enum columns of 3 / 29 / 352 levels; a logistic response on level
    effects, the numeric columns and missingness."""
    rng = np.random.default_rng(seed)
    num = [rng.normal(size=rows).astype(np.float32) for _ in range(3)]
    miss = rng.random(rows) < na_share
    num[0][miss] = np.nan
    cat = [rng.integers(0, k, rows).astype(np.int32) for k in CARDS]
    if na_share:
        cat[1][rng.random(rows) < na_share / 2] = -1     # a missing enum
    eff = [rng.normal(0.0, s, k) for k, s in zip(CARDS, (0.7, 0.6, 0.8))]
    z = (0.6 * np.nan_to_num(num[0]) + 0.8 * miss - 0.5 * num[1]
         + sum(e[np.maximum(c, 0)] for e, c in zip(eff, cat)))
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-z))).astype(np.int32)
    cols = [num[0], cat[0], num[1], cat[1], cat[2], num[2]]
    return cols, y


def frame_of(cols, y, names=NAMES, card=CARD):
    vecs = [Vec(c, T_CAT, domain=[f"L{i}" for i in range(k)]) if k
            else Vec(c) for c, k in zip(cols, card)]
    return Frame(list(names) + ["y"],
                 vecs + [Vec(y, T_CAT, domain=["no", "yes"])])
