"""The work a histogram tree needs, counted from shapes alone.

This is the ALGORITHM's work, the same whatever implements it: a PR that
replaces the one-hot matmul leaves the reading meaningful.  Per tree
level every row's bin indices, its node id and its two gradient
statistics are read once, and each (row, column) adds the two statistics
into one table cell.  What an implementation spends beyond that (the
one-hot contraction's 2*R*C*(B+1)*L*S FLOPs, sibling tables, f32 passes)
is its own choice and is not counted.
"""

from __future__ import annotations

import math
from typing import Dict


def bin_index_bytes(nbins: int) -> int:
    """Bytes of the narrowest whole-byte index that tells nbins bins and
    the missing-value bucket apart."""
    return max(1, math.ceil(math.log2(nbins + 1) / 8))


def level_work(rows: int, cols: int, nbins: int) -> Dict[str, float]:
    """One level of one tree: bytes read and additions made."""
    nbytes = rows * (cols * bin_index_bytes(nbins) + 4 + 2 * 4)
    ops = 2.0 * rows * cols * 2
    return {"bytes": float(nbytes), "ops": ops}


def tree_work(rows: int, cols: int, nbins: int, depth: int,
              fine_nbins: int = 0) -> Dict[str, float]:
    """One tree of ``depth`` levels.  With a fine grid (UniformAdaptive)
    rows are stored as fine-grid indices, so that width is what is read."""
    lw = level_work(rows, cols, max(nbins, fine_nbins))
    return {"bytes": lw["bytes"] * depth, "ops": lw["ops"] * depth}
