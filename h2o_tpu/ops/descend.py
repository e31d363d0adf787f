"""The walk down a built tree's node arrays — the ONE place that says
which way a row goes at a stored split.

Every reader of a finished tree (forest scoring, staged predictions,
RuleFit's rule matrix) calls :func:`descend`; the numpy walk in
``mojo/scorers.py`` is the reference the tests hold it to.  Training
does not: growth leaves every row on its final node
(``jit_engine.build_tree_traced``'s ``pos``, held to this walk by
``tests/test_grown_positions.py``).  Opens no ``jax.named_scope``: the
callers name the device time (``h2o.score.descent``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from h2o_tpu.ops.binpack import pick_bin


def _go_left(bs, node, b, th, na, fine_na: int, B: int):
    """Mixed split semantics: thr >= 0 -> adaptive numeric threshold in
    fine-bin units (NA routed by na); thr < 0 -> bitset membership
    (categorical splits, and every split of pre-adaptive models)."""
    nb = jnp.minimum(b, B)                       # NA (fine_na) -> slot B
    gl = bs[node, nb]
    if th is None:
        return gl
    tn = th[node]
    return jnp.where(tn >= 0,
                     jnp.where(b == fine_na, na[node], b < tn), gl)


def descend(bins, split_col, bitset, depth: int, child=None, thr=None,
            na_l=None, fine_na: int = -1) -> jax.Array:
    """Each row's final node id in ONE tree (traceable): bins (R, C),
    node arrays (H,) / (H, B+1).  ``child`` None = dense heap (children
    at 2n+1/2n+2), else explicit left-child pointers (right = left+1);
    ``thr``/``na_l`` carry adaptive numeric thresholds."""
    B = bitset.shape[-1] - 1
    node = jnp.zeros((bins.shape[0],), jnp.int32)
    for _ in range(depth):
        c = split_col[node]
        term = c < 0
        b = pick_bin(bins, jnp.maximum(c, 0))
        gl = _go_left(bitset, node, b, thr, na_l, fine_na, B)
        if child is None:
            nxt = 2 * node + jnp.where(gl, 1, 2)
        else:
            left = child[node]
            term = term | (left < 0)
            nxt = left + jnp.where(gl, 0, 1)
        node = jnp.where(term, node, nxt)
    return node
