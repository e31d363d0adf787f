"""Bin-dtype packing — the sanctioned narrow-dtype layer for binned
matrices.

The histogram hot path's dominant input is the pre-binned feature
matrix: (R, C) integers in ``[0, F]`` where ``F`` is the NA sentinel
(models/tree/shared_tree._bin_all maps NaN -> F and clips categorical
codes, including the -1 missing-level code, to ``[0, nbins-1]`` — every
stored value is non-negative BEFORE packing, so the unsigned range
holds the whole alphabet).  int32 everywhere wastes 2-4x the HBM
traffic the kernels actually need: QuantilesGlobal's B <= 64 fits
uint8, the adaptive fine grid's F <= 1024 fits int16.  This module is
the ONE place allowed to choose and apply the narrow dtype
(graftlint GL630 bans int32 re-widening of bin matrices everywhere
else), keeping the decode contract in a single screen of code:

DECODE CONTRACT
  * A packed matrix holds EXACTLY the same integers as the int32
    representation — no offset, no bias, no remap.  ``packed == int32``
    value-for-value; unpacking is a plain widening cast.
  * Values span ``[0, F]`` inclusive.  ``F`` (the NA sentinel) must fit
    the chosen dtype, hence :func:`bins_dtype_for` keys on the FINE bin
    count: uint8 iff F <= 255, int16 iff F <= 32767, else int32.
  * Kernels may widen IN-REGISTER inside a tile/block via
    :func:`widen_bins` (a fusing ``convert_element_type`` — XLA never
    materializes the widened copy in HBM); materializing a full int32
    copy of the matrix is exactly what packing exists to prevent.

Whether packing applies at all is the ``tree.bins_dtype`` autotuner
lever (env ``H2O_TPU_BINS_PACK``, tri-state like every PR 10 lever):
the parity gate proves the packed forest bitwise-identical to the
int32 reference before a packed candidate can win, and scoring is
dtype-agnostic either way (bin VALUES are identical under both
representations, so a checkpoint trained packed resumes bitwise under
int32 and vice versa).

This module packs the histogram matmul's INDEX side; its VALUE-side
twin is ``ops/statpack.py`` (quantized gradient/hessian stats, the
``tree.stats_dtype`` lever, GL631).  The two compose: with both levers
on, the one-hot contraction runs narrow-carrier × narrow-carrier into
an exact int32 table.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: dtypes the packer may select, narrowest first
PACKED_DTYPES = ("uint8", "int16", "int32")


def bins_dtype_for(fine_nbins: int):
    """Narrowest dtype holding every bin value in ``[0, fine_nbins]``
    (``fine_nbins`` itself is the NA sentinel and must fit)."""
    f = int(fine_nbins)
    if f <= 255:
        return jnp.uint8
    if f <= 32767:
        return jnp.int16
    return jnp.int32


def packed_dtype_name(fine_nbins: int, packed: bool) -> str:
    """The static ``out_dtype`` arg for ``_bin_all``: the packed dtype's
    name under the lever, the int32 reference otherwise."""
    return jnp.dtype(bins_dtype_for(fine_nbins)).name if packed \
        else "int32"


def cast_bins(b, out_dtype) -> jax.Array:
    """THE sanctioned narrowing cast (trace-safe; values must already
    satisfy the decode contract — non-negative, <= the NA sentinel)."""
    return lax.convert_element_type(b, jnp.dtype(out_dtype))


def widen_bins(b) -> jax.Array:
    """THE sanctioned in-register widen for arithmetic sites inside a
    kernel tile or scan block.  ``convert_element_type`` fuses into the
    consumer — the widened values live in registers/VMEM for the block,
    never as an int32 copy of the matrix in HBM."""
    return lax.convert_element_type(b, jnp.int32)


def pack_words(bins, max_code: int) -> jax.Array:
    """(R, C) bins in ``[0, max_code]`` -> uint32 words: (R, ceil(C / 2))
    where every code fits 16 bits, column ``j`` the low half of word
    ``j`` and column ``j + ceil(C / 2)`` its high half (zero where C is
    odd); else (R, C), a code a word.  The form in which a window level's
    blocks gather their rows (``ops/histogram.histogram_window_traced``):
    the chip's gather reads element by element, so half the elements of
    the int32 codes."""
    b = lax.convert_element_type(bins, jnp.uint32)
    if int(max_code) >= 1 << 16:
        return b
    C = b.shape[1]
    nw = -(-C // 2)
    hi = jnp.pad(b[:, nw:], ((0, 0), (0, 2 * nw - C)))
    return b[:, :nw] | (hi << 16)


def unpack_words(words, C: int, max_code: int) -> jax.Array:
    """:func:`pack_words`' inverse on a row block: (R, C) int32 codes,
    unpacked in-register like :func:`widen_bins`."""
    if int(max_code) < 1 << 16:
        words = jnp.concatenate([words & 0xFFFF, words >> 16], axis=1)
    return lax.convert_element_type(words[:, :C], jnp.int32)


def pick_bin(bins, c) -> jax.Array:
    """``bins[r, c[r]]`` for every row r: each row's bin in the column
    its node splits on, in the matrix's own dtype.  A compare-select
    over the C columns and a sum, one pass over the matrix that fuses
    into a single loop, NOT a per-row gather: on the chip the gather
    (``take_along_axis``) issues one access per row, took 0.26-0.32 s a
    level at 11.5M rows, and ran at one of three speeds from process to
    process by where the allocator had placed ``bins`` (PERF.md section
    6, PR 30), which no window of fixed work can be timed over.  Exact:
    one column matches a valid ``c``, none a negative one (the result is
    then 0, and every caller masks such rows)."""
    cols = jnp.arange(bins.shape[1], dtype=c.dtype)
    return jnp.sum(jnp.where(cols[None, :] == c[:, None], bins, 0),
                   axis=1, dtype=bins.dtype)


def bins_pack_enabled(bucket=None) -> bool:
    """Tri-state ``H2O_TPU_BINS_PACK``: ``1`` forces packing, ``0``
    forces the int32 reference, ``auto``/unset defers to the measured
    ``tree.bins_dtype`` decision (core/autotune.py — parity-gated
    bitwise, persisted next to the exec store; off-TPU the int32
    reference wins with zero probes).  Resolve OUTSIDE jit traces —
    the packed dtype is part of every downstream executable's aval
    signature."""
    from h2o_tpu.core.autotune import resolve_flag
    return resolve_flag("tree.bins_dtype", bucket)


def bins_bucket(rows: int, cols: int, fine_nbins: int):
    """The ``tree.bins_dtype`` lever's shape bucket: pow2 rows/cols so
    nearby workloads share a decision, exact fine bin count (it selects
    the dtype outright)."""
    from h2o_tpu.core.exec_store import bucket_pow2
    return (min(bucket_pow2(int(rows)), 1 << 20),
            bucket_pow2(int(cols)), int(fine_nbins))
