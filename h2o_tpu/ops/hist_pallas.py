"""Pallas TPU kernel for the (leaf, col, bin) histogram — fused one-hot
matmul.

The XLA path (ops/histogram.py) materializes each row block's one-hot
matrix ``binhot (blk, C*(B+1))`` in HBM before the MXU contraction — at
1M rows that is gigabytes of HBM traffic per level for what is logically
a throwaway intermediate.  This kernel builds the one-hot TILE-BY-TILE in
VMEM and feeds the MXU directly, so HBM sees only the true inputs
(bins, leaf, stats — ~R*(C+5)*4 bytes) and the true output
((C*(B+1), L*S) partials).  Reference hot loop:
ScoreBuildHistogram2.java:16-61 (same redesign rationale as
ops/histogram.py — TPUs hate scatter, so binning is a matmul).

Layout: inside a tile ROWS RIDE THE LANE AXIS.  The bins tile is
transposed once in VMEM to ``(C, TR)``; column c's one-hot slab
``hotT[c*B1p:(c+1)*B1p, :] = (binsT[c] == iota)`` is then a sublane
broadcast written at a tile-aligned sublane offset (``B1p`` = B+1
rounded up to the matmul dtype's sublane packing), and the A matrix is
built transposed too — ``aT[l*S+s, r] = [leaf[r]==l] * stats[s, r]`` by
2-D selects.  One ``hotT @ aT^T`` contraction per 128-lane slab of L*S
follows.  Mosaic has no lowering for what the first version used —
reshapes that merge a minor dim of 4 or 65 into the lane axis — and
chunking L*S keeps the compiler's matmul scratch independent of the
frontier width (measured with the deviceless v5e AOT compiler: scoped
need was 7.2 MiB at L=32 and 21 MiB at L=64 before chunking, 7.2 MiB
at every L after).

Grid: sequential over row tiles; every step accumulates into the SAME
output block (TPU grids execute in order, making read-modify-write on the
output block safe).  Tile height adapts to keep the working set under a
fixed byte budget whatever (C, B) the caller brings, and the same budget
(plus headroom) is handed to Mosaic as ``vmem_limit_bytes`` so an
under-count fails at compile time instead of spilling.

Validation: tests/ run the kernels in interpret mode; ``chip_smoke.py``
compiles them with Mosaic and compares them with the XLA path on the
chip; and the autotuner (core/autotune.py, ``hist.kernel`` lever)
parity-gates them on the live backend before they can win a bucket.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from h2o_tpu.ops.binpack import widen_bins

_LANE = 128

# Budget for what Mosaic allocates inside the kernel's scope: the hotT
# scratch (C*B1p, TR), the transposed bins / bucket scratch, one 128-row
# slab of aT and its cast, the double-buffered lane-padded input tiles,
# and the per-slab matmul result.  plan_tile_rows counts each of them;
# the compiler is told _VMEM_LIMIT_BYTES, so the plan has 8 MiB of
# headroom for temporaries it cannot see (v5e: 128 MiB of VMEM per
# core, 16 MiB default scoped limit).
_VMEM_WORKSET_BYTES = 24 * 2 ** 20
_VMEM_LIMIT_BYTES = 32 * 2 ** 20

# The (C*B1p, L*S) output window stays resident across the row-tile
# axis.  Mosaic places it OUTSIDE the scoped limit (single-buffered when
# it covers the whole output, double-buffered per column group
# otherwise), so it has a cap of its own.
_OUT_WINDOW_BYTES = 32 * 2 ** 20

_MAX_TILE_ROWS = 2048


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _sublanes(dtype) -> int:
    """Rows of one (sublane, lane) tile: 8 at 32 bits, 16 at 16, 32 at 8."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def matmul_dtype(stats_dtype, bf16: bool):
    """The contraction's input dtype: a quantized stats carrier
    (ops/statpack.py) is its own matmul dtype (integer dot, int32
    accumulator); f32 stats contract as f32 at HIGHEST precision, or as
    bf16 when the caller asked for ``bf16_histograms``."""
    if jnp.issubdtype(stats_dtype, jnp.integer):
        return jnp.dtype(stats_dtype)
    return jnp.dtype(jnp.bfloat16 if bf16 else jnp.float32)


def _acc_dtype(mm_dtype):
    """Accumulator (and output) dtype of the contraction."""
    return jnp.int32 if jnp.issubdtype(mm_dtype, jnp.integer) \
        else jnp.float32


def mosaic_supports(mm_dtype) -> bool:
    """Static rule on the matmul dtype.  v5e's MXU takes f32 (as bf16
    passes), bf16 and int8; an int16 contraction is refused by Mosaic
    (``Bad lhs/rhs type: 'vector<256x128xi16>' 'vector<128x128xi16>'``
    on ``tpu.matmul``), so the int16 stats carrier stays on the XLA
    path."""
    return jnp.dtype(mm_dtype) != jnp.dtype(jnp.int16)


def plan_tile_rows(C: int, B1: int, L: int, S: int, mm_dtype,
                   bins_itemsize: int = 4, stats_itemsize: int = 4,
                   c_total: int = None):
    """Row-tile height (128-multiple — rows ride the lane axis — capped
    at 2048) whose working set fits ``_VMEM_WORKSET_BYTES``, or None
    when even the 128-row minimum tile cannot or the output window
    exceeds its own cap — the caller must reject the fused kernel and
    stay on the portable XLA path.

    ``C`` is the number of columns whose one-hot is live at once (the
    adaptive kernel's column GROUP); ``c_total`` the width of the bins
    tile that is DMA'd and transposed (all columns; defaults to C).
    ``bins_itemsize`` is the PACKED bins dtype's width (ops/binpack.py)
    and ``stats_itemsize`` the stats carrier's (ops/statpack.py):
    narrow carriers shrink the input tiles, and a quantized carrier is
    also the one-hot's dtype, so packed callers plan TALLER tiles from
    the same budget."""
    c_total = C if c_total is None else c_total
    itemsize = jnp.dtype(mm_dtype).itemsize
    M = C * _round_up(B1, _sublanes(mm_dtype))
    windows = 1 if c_total == C else 2
    if windows * M * _round_up(L * S, _LANE) * 4 > _OUT_WINDOW_BYTES:
        return None
    # per 128-lane slab: the matmul result, and the read-modify-write
    # of the output window's slab
    fixed = 3 * M * _LANE * 4
    per_row = (M * itemsize                           # hotT scratch
               + _LANE * (4 + itemsize)               # aT slab + its cast
               + 2 * _round_up(c_total, 8) * 4        # widened + binsT
               + 8 * _round_up(C, 8) * 4              # bucket arithmetic
               + 2 * _round_up(c_total, _LANE) * bins_itemsize
               + 2 * _LANE * stats_itemsize           # stats tile x2
               + 4 * 8 * 4)                           # leaf x2, statsT
    avail = _VMEM_WORKSET_BYTES - fixed
    if avail < per_row * _LANE:
        return None
    return int(min(_MAX_TILE_ROWS, (avail // per_row // _LANE) * _LANE))


def min_tile_fits(C: int, B1: int, L: int = 1, S: int = 4,
                  c_total: int = None, mm_dtype=jnp.float32) -> bool:
    """True when the minimum (128-row) tile's working set fits the VMEM
    budget — eligibility gate for wide-feature AND wide-frontier shapes
    (ops/histogram.py falls back to the XLA path otherwise).  Planned at
    int32 bins and 4-byte stats: narrower input tiles only shrink the
    working set.  The matmul dtype is NOT monotone (a narrow one-hot
    pads B+1 up to 16 or 32 sublanes, which widens the output window),
    so callers pass the one they will run."""
    return plan_tile_rows(C, B1, L, S, mm_dtype,
                          c_total=c_total) is not None


class VMEMGateError(ValueError):
    """The fused kernel's combined working set exceeds VMEM even at the
    minimum tile.  The message carries the ``VMEM`` marker, so
    core/oom.is_kernel_compile_failure classifies it as a recoverable
    kernel rejection and ``kernel_fallback`` degrades the dispatch to
    the portable XLA path instead of failing the training job."""


def _tile_rows(C: int, B1: int, L: int, S: int, mm_dtype,
               bins_itemsize: int = 4, stats_itemsize: int = 4,
               c_total: int = None) -> int:
    """Working-set-bounded tile height; asserts eligibility was gated."""
    t = plan_tile_rows(C, B1, L, S, mm_dtype, bins_itemsize,
                       stats_itemsize, c_total)
    if t is None:
        raise VMEMGateError(
            f"hist_pallas working set exceeds VMEM at the minimum tile "
            f"(C={C}, B1={B1}, L={L}, S={S}) — _pallas_eligible should "
            f"have rejected this shape")
    return t


def _stats_t(stats_ref):
    """(S, TR) stats at 32 bits: a quantized carrier widens to int32
    first, so every select below runs on 32-bit layouts."""
    s = stats_ref[:]
    if jnp.issubdtype(s.dtype, jnp.integer):
        s = s.astype(jnp.int32)
    return s.T


def _a_slab_t(leaf_t, stats_t, n0: int, n: int):
    """Rows [n0, n0+n) of aT: ``aT[l*S+s, r] = [leaf[r]==l] *
    stats[s, r]``.  Selects, not products: a row with ``leaf < 0``
    matches no leaf and contributes exact zeros whatever its payload
    (padded rows carry NaN)."""
    S = stats_t.shape[0]
    row = n0 + lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    a = jnp.zeros((n, leaf_t.shape[1]), stats_t.dtype)
    for s in range(S):
        a = jnp.where(row % S == s, stats_t[s:s + 1, :], a)
    return jnp.where(leaf_t == row // S, a, jnp.zeros_like(a))


def _accumulate(bucket_ref, n_cols: int, leaf_t, stats_t, hot_ref, out_ref,
                mm_dtype):
    """out += hotT(bucket) @ aT^T for one row tile.  ``bucket_ref``
    holds the tile's bucket indices transposed, one column per sublane
    row."""
    TR = leaf_t.shape[1]
    LS = out_ref.shape[1]
    B1p = hot_ref.shape[0] // n_cols
    wide = _acc_dtype(mm_dtype)

    def one_col(c, carry):
        hot = bucket_ref[pl.ds(c, 1), :] == \
            lax.broadcasted_iota(jnp.int32, (B1p, TR), 0)
        hot_ref[pl.ds(pl.multiple_of(c * B1p, B1p), B1p), :] = \
            hot.astype(wide).astype(mm_dtype)
        return carry

    lax.fori_loop(0, n_cols, one_col, 0)

    def one_slab(n0, n: int):
        a_t = _a_slab_t(leaf_t, stats_t, n0, n).astype(mm_dtype)
        # the one-hot side is exact in any dtype; HIGHEST keeps the f32
        # stats side f32 (the MXU's default would round it to bf16)
        out_ref[:, pl.ds(n0, n)] += lax.dot_general(
            hot_ref[:], a_t,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=wide,
            precision=lax.Precision.HIGHEST
            if mm_dtype == jnp.float32 else None)      # (C*B1p, n)

    if LS <= _LANE:
        one_slab(0, LS)
    else:
        # the wrapper padded L*S up to whole slabs (_out_lanes)
        def body(k, carry):
            one_slab(pl.multiple_of(k * _LANE, _LANE), _LANE)
            return carry

        lax.fori_loop(0, LS // _LANE, body, 0)


def _hist_kernel(bins_ref, leaf_ref, stats_ref, out_ref, bt_ref, hot_ref,
                 *, mm_dtype):
    """One row tile: out += binhot(bins)^T @ (leafhot(leaf) ⊗ stats)."""
    C = bins_ref.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    # in-tile widen of the packed bins tile (ops/binpack.py): the
    # compare needs int32 operands, the widened values never leave VMEM
    bt_ref[pl.ds(0, C), :] = widen_bins(bins_ref[:]).T
    _accumulate(bt_ref, C, leaf_ref[:], _stats_t(stats_ref), hot_ref,
                out_ref, mm_dtype)


def _adaptive_kernel(bins_ref, leaf_ref, stats_ref, lo_ref, hi_ref,
                     off_ref, cat_ref, out_ref, bt_ref, bucket_ref,
                     hot_ref, *, n_leaves: int, nbins: int, fine_na: int,
                     mm_dtype):
    """Adaptive variant: fuses the fine-bin -> per-node bucket map
    (ops/histogram.py map_buckets, same all-integer arithmetic) into the
    one-hot build.  Grid is (col_groups, row_tiles): each column group
    owns its own output rows and sweeps all row tiles, accumulating.
    Every step sees the full-width bins tile and slices its group's
    columns out of the transposed copy (a sublane slice).

    Per-leaf range picks (lo/hi/off)[leaf] are one select per leaf on
    the (Cg, TR) tile — exact in int32, no matmul."""
    Cg = lo_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    leaf_t = leaf_ref[:]                              # (1, TR)
    # in-tile widen of the packed bins tile (ops/binpack.py): bucket
    # arithmetic below reaches x * nbins — int32 range, VMEM-local
    bt_ref[:] = widen_bins(bins_ref[:]).T             # (C, TR)
    g0 = pl.multiple_of(pl.program_id(0) * Cg, 8)
    fine = bt_ref[pl.ds(g0, Cg), :]                   # (Cg, TR)

    lo_b = jnp.zeros(fine.shape, jnp.int32)
    hi_b = jnp.zeros(fine.shape, jnp.int32)
    o_b = jnp.zeros(fine.shape, jnp.int32)
    for leaf in range(n_leaves):
        here = leaf_t == leaf
        lo_b = jnp.where(here, lo_ref[:, leaf:leaf + 1], lo_b)
        hi_b = jnp.where(here, hi_ref[:, leaf:leaf + 1], hi_b)
        o_b = jnp.where(here, off_ref[:, leaf:leaf + 1], o_b)
    span = jnp.maximum(hi_b - lo_b + 1, 1)
    x = jnp.clip(fine - lo_b, 0, span - 1)
    nb = jnp.clip((x * nbins + o_b) // span, 0, nbins - 1)
    out = jnp.where(cat_ref[:] != 0, jnp.minimum(fine, nbins), nb)
    bucket_ref[:] = jnp.where(fine == fine_na, nbins, out)

    _accumulate(bucket_ref, Cg, leaf_t, _stats_t(stats_ref), hot_ref,
                out_ref, mm_dtype)


def _out_lanes(LS: int) -> int:
    """Width of the output window: L*S itself up to one 128-lane slab,
    whole slabs beyond (the padding lanes match no leaf and stay 0)."""
    return LS if LS <= _LANE else _round_up(LS, _LANE)


def _pad_rows(bins, leaf, stats, TR: int):
    pad = (-bins.shape[0]) % TR
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        leaf = jnp.pad(leaf, (0, pad), constant_values=-1)
        stats = jnp.pad(stats, ((0, pad), (0, 0)))
    return bins, leaf.reshape(1, -1), stats


def _compiler_params(n_axes: int):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * n_axes,
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


@functools.partial(jax.jit, static_argnames=(
    "n_leaves", "nbins", "fine_na", "bf16", "interpret"))
def hist_pallas_adaptive(bins, leaf, stats, lo, hi, off, is_cat,
                         n_leaves: int, nbins: int, fine_na: int,
                         bf16: bool = False, interpret: bool = False):
    """(C*(B+1), L*S) adaptive-bucket histogram of one device shard.

    Matches map_buckets + the XLA accumulation exactly.  Columns are
    processed in groups (multiples of 8 — a group is a sublane slice of
    the transposed tile) sized so each group's one-hot fits the VMEM
    budget — the halving schedule's wide top levels (Bd up to
    nbins_top_level) stream column groups instead of materializing the
    full (R, C*(Bd+1)) one-hot in HBM."""
    R, C = bins.shape
    S = stats.shape[1]
    B1 = nbins + 1
    mm_dtype = matmul_dtype(stats.dtype, bf16)
    B1p = _round_up(B1, _sublanes(mm_dtype))
    # widest group (all columns), halved until the working set admits a
    # tile; the 8-column floor is what _pallas_eligible gated on
    c8 = _round_up(C, 8)
    Cg = c8
    while Cg > 8 and plan_tile_rows(Cg, B1, n_leaves, S, mm_dtype,
                                    bins.dtype.itemsize,
                                    stats.dtype.itemsize,
                                    _round_up(c8, Cg)) is None:
        Cg = _round_up(Cg // 2, 8)
    ncg = -(-C // Cg)
    cpad = ncg * Cg - C
    TR = _tile_rows(Cg, B1, n_leaves, S, mm_dtype, bins.dtype.itemsize,
                    stats.dtype.itemsize, ncg * Cg)
    if cpad:
        # padded columns carry the fine_na sentinel, so every row maps
        # to their NA bucket; those output rows are sliced off below
        bins = jnp.pad(bins, ((0, 0), (0, cpad)),
                       constant_values=fine_na)
        lo = jnp.pad(lo, ((0, 0), (0, cpad)))
        hi = jnp.pad(hi, ((0, 0), (0, cpad)))
        off = jnp.pad(off, ((0, 0), (0, cpad)))
        is_cat = jnp.pad(is_cat, (0, cpad))
    bins, leaf, stats = _pad_rows(bins, leaf, stats, TR)
    n_tiles = bins.shape[0] // TR
    LS = n_leaves * S
    LSp = _out_lanes(LS)

    def table(shape):
        return pl.BlockSpec(shape, lambda j, i: (j, 0),
                            memory_space=pltpu.VMEM)

    kernel = functools.partial(
        _adaptive_kernel, n_leaves=n_leaves, nbins=nbins,
        fine_na=fine_na, mm_dtype=mm_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(ncg, n_tiles),
        in_specs=[
            pl.BlockSpec((TR, ncg * Cg), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, TR), lambda j, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TR, S), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            table((Cg, n_leaves)), table((Cg, n_leaves)),
            table((Cg, n_leaves)), table((Cg, 1)),
        ],
        out_specs=table((Cg * B1p, LSp)),
        out_shape=jax.ShapeDtypeStruct(
            (ncg * Cg * B1p, LSp), _acc_dtype(mm_dtype)),
        scratch_shapes=[pltpu.VMEM((ncg * Cg, TR), jnp.int32),
                        pltpu.VMEM((Cg, TR), jnp.int32),
                        pltpu.VMEM((Cg * B1p, TR), mm_dtype)],
        compiler_params=_compiler_params(2),
        interpret=interpret,
    )(bins, leaf, stats, lo.T, hi.T, off.T,
      is_cat.astype(jnp.int32).reshape(-1, 1))
    return out.reshape(ncg * Cg, B1p, LSp)[:C, :B1, :LS].reshape(
        C * B1, LS)


@functools.partial(jax.jit, static_argnames=(
    "n_leaves", "nbins", "bf16", "interpret"))
def hist_pallas(bins, leaf, stats, n_leaves: int, nbins: int,
                bf16: bool = False, interpret: bool = False):
    """(C*(B+1), L*S) histogram of one device shard via the fused kernel.

    Same contract as the XLA path's accumulated ``_block_hist``: rows with
    ``leaf < 0`` contribute nothing; bin ``nbins`` is the NA bucket.
    Pads rows to a tile multiple internally (padded rows get leaf −1).
    """
    C = bins.shape[1]
    S = stats.shape[1]
    B1 = nbins + 1
    mm_dtype = matmul_dtype(stats.dtype, bf16)
    B1p = _round_up(B1, _sublanes(mm_dtype))
    TR = _tile_rows(C, B1, n_leaves, S, mm_dtype, bins.dtype.itemsize,
                    stats.dtype.itemsize)
    bins, leaf, stats = _pad_rows(bins, leaf, stats, TR)
    LS = n_leaves * S
    LSp = _out_lanes(LS)

    kernel = functools.partial(_hist_kernel, mm_dtype=mm_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(bins.shape[0] // TR,),
        in_specs=[
            pl.BlockSpec((TR, C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, TR), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TR, S), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((C * B1p, LSp), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (C * B1p, LSp), _acc_dtype(mm_dtype)),
        scratch_shapes=[pltpu.VMEM((_round_up(C, 8), TR), jnp.int32),
                        pltpu.VMEM((C * B1p, TR), mm_dtype)],
        compiler_params=_compiler_params(1),
        interpret=interpret,
    )(bins, leaf, stats)
    return out.reshape(C, B1p, LSp)[:, :B1, :LS].reshape(C * B1, LS)
