"""The HIGGS shape with a signal in every column, for a random forest.

``benchmark/data.py``'s ``higgs_like`` (nothing there is edited) puts its
signal in columns 0..4 and only three of them carry it alone (columns 2
and 3 act through their product).  A random forest at H2O-3's defaults
draws 5 of 28 columns at each split and keeps a split only if it takes
more than ``min_split_improvement`` (1e-5) of the node's squared error;
on millions of rows a column without signal never does, so on that frame
54 % of the large nodes draw no useful column and stop, and a tree is a
stump or a deep tree by the draw of its first few nodes: the window's
work, and with it ``train_rate``, moves by tens of percent from seed to
seed.  Here every column beyond the fifth adds a weak linear term to
``higgs_like``'s logit (``DENSE_WEIGHT``, the sign alternating), so that
every large node finds a split and the trees grow to the depth and the
frontier's width.  ``DENSE_WEIGHT`` is an assumption, not a published
number: it sets how wide the trees grow (the configuration's
``assumed`` lists what tree 1 reads at 0.06, 0.12 and 0.24).

A pure function of ``--seed``: X is (cols, rows) float32 standard
normals, y (rows,) int32 from the logistic model.
"""

from __future__ import annotations

import numpy as np

from benchmark.data import _CHUNK

DENSE_WEIGHT = 0.12


def higgs_like_dense(rows: int, cols: int, seed: int):
    """``(X, y)``: ``higgs_like``'s columns and nonlinear signal in
    columns 0..4, plus ``DENSE_WEIGHT * (-1)**j * X[j]`` for every other
    column j in the logit.  ``seed`` is any non-negative whole number."""
    if cols < 5:
        raise ValueError("the HIGGS-like signal reads columns 0..4")
    rng = np.random.default_rng(int(seed))
    X = rng.standard_normal((cols, rows), dtype=np.float32)
    u = rng.random(rows, dtype=np.float32)
    w = (DENSE_WEIGHT * (-1.0) ** np.arange(5, cols)).astype(np.float32)
    y = np.empty(rows, np.int32)
    for a in range(0, rows, _CHUNK):
        s = slice(a, min(a + _CHUNK, rows))
        logits = (1.2 * X[0, s] - 0.8 * X[1, s] + X[2, s] * X[3, s]
                  + 0.5 * np.sin(3.0 * X[4, s]) + w @ X[5:, s])
        y[s] = u[s] < 1.0 / (1.0 + np.exp(-logits))
    return X, y


GENERATORS = {"higgs_like_dense": higgs_like_dense}
