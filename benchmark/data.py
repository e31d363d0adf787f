"""Seeded inputs for the benchmark's cells.

One generator per data shape, each a pure function of ``--seed``; the
program receives only the generated arrays.  Copied from
``bench.py:_make_data`` (the HIGGS-like 28-column generator) and made
column-major, so that each column is contiguous for landing and for the
plain reference (listed in PERF.md's Open questions: the original in
``bench.py`` is for a later PR to delete).
"""

from __future__ import annotations

import numpy as np

# rows drawn per chunk: bounds the float64 temporaries of the signal
_CHUNK = 1 << 20


def higgs_like(rows: int, cols: int, seed: int):
    """``(X, y)``: X is (cols, rows) float32 standard normals, y is
    (rows,) int32 drawn from a logistic model of a nonlinear signal in
    the first five columns (HIGGS shape: 28 columns, binary response).
    ``seed`` is any non-negative whole number."""
    if cols < 5:
        raise ValueError("the HIGGS-like signal reads columns 0..4")
    rng = np.random.default_rng(int(seed))
    X = rng.standard_normal((cols, rows), dtype=np.float32)
    u = rng.random(rows, dtype=np.float32)
    y = np.empty(rows, np.int32)
    for a in range(0, rows, _CHUNK):
        s = slice(a, min(a + _CHUNK, rows))
        logits = (1.2 * X[0, s] - 0.8 * X[1, s] + X[2, s] * X[3, s]
                  + 0.5 * np.sin(3.0 * X[4, s]))
        y[s] = u[s] < 1.0 / (1.0 + np.exp(-logits))
    return X, y


GENERATORS = {"higgs_like": higgs_like}
