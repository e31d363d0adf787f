"""(leaf, col, bin) histogram accumulation — the hot kernel of tree building.

Reference (SURVEY §3.3 HOT LOOP #1): ``ScoreBuildHistogram2`` re-assigns rows
to leaves then accumulates per-(column, row-range) private ``DHistogram``
bins of (w, wY, wYY) with a no-CAS two-pass scheme, reduced elementwise
across nodes (ScoreBuildHistogram2.java:16-61, DHistogram.java:19-62).

TPU-native redesign: TPUs hate scatter, so bin accumulation is recast as
MATRIX MULTIPLICATION on the MXU.  The factored form keeps memory and flops
in check:

    A[r, l*S+s]   = [leaf[r]==l] * stats[r, s]        # (R, L*S) — L*S = 128
                                                      #  for L=32,S=4: one
                                                      #  full lane tile
    H[c*B+b, l*S+s] = sum_r [bin[r,c]==b] * A[r, ls]  # ONE matmul:
                                                      #  (C*B, R) @ (R, L*S)

accumulated over row blocks with ``lax.scan`` to bound the one-hot footprint.
Stats are (w, w*g, w*g^2, w*h): enough for variance-reduction split scoring
AND Newton leaf values — the reference needs a second MRTask (GammaPass,
gbm/GBM.java:464-528) for leaf values; here both come from one kernel.  The
cross-node reduce is a ``hpsum`` of the fixed-shape (L, C, B+1, S)
tensor — ICI on a flat mesh, one DCN combine per step on a two-level
mesh — replacing the reference's software binomial tree
(MRTask.java:94-117); the DCN cost is O(table), never O(rows).

The NA bucket is bin index B (DHistogram INT_NA analog), so split finding can
try NA-left vs NA-right.  The sibling-subtraction optimization (histogram the
LEFT children only, derive each right child as parent-minus-left — reference
DHistogram) lives in the GBM/DRF tree builders
(models/tree/jit_engine.py _hist_level_with_sibling): it halves this
kernel's matmul width on every level whose parent level was uncapped
(all levels >= 1 in the dense engine; the frontier engine's capped/top_k
levels and the uplift engine use the full histogram).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from h2o_tpu.core.cloud import cloud, hpsum, shard_map_compat
from h2o_tpu.ops.binpack import widen_bins

# stats slots
W, WG, WGG, WH = 0, 1, 2, 3
N_STATS = 4


def pallas_env_enabled(bucket=None) -> bool:
    """Tri-state H2O_TPU_HIST_PALLAS: ``1`` forces the fused Pallas
    kernel, ``0`` forces the portable XLA scan, and ``auto``/unset (the
    default) defers to the autotuner (core/autotune.py ``hist.kernel``
    lever): on TPU each candidate is compiled on the live backend,
    parity-gated against the XLA reference, timed, and the persisted
    winner applies — a Mosaic miscompile is disqualified instead of
    corrupting training; off-TPU the XLA reference wins with zero probe
    runs.  ``bucket`` optionally scopes the decision to a workload
    shape bucket (rows, C, nbins, L).  Resolve OUTSIDE jit traces (the
    engine's train_forest wrapper does) — a value read at trace time is
    baked into the executable cache key's shapes and a later flip would
    silently not apply."""
    from h2o_tpu.core.autotune import resolve_flag
    return resolve_flag("hist.kernel", bucket)


def _pallas_eligible(C: int, B1: int, n_leaves: int, S: int,
                     fine_map, allowed: bool,
                     mm_dtype=jnp.float32) -> bool:
    """Static choice of the fused Pallas kernel (ops/hist_pallas.py):
    TPU backend only (CPU tests keep the portable XLA path), a matmul
    dtype (``hist_pallas.matmul_dtype`` of the stats carrier) Mosaic has
    a matmul for (``hist_pallas.mosaic_supports``: int16 has none), and
    the kernel's per-tile working set and output window must fit VMEM
    (``hist_pallas.plan_tile_rows``).
    ``allowed`` is the env OPT-IN and must be resolved OUTSIDE the trace
    by the caller — it is part of the executable's static signature,
    never re-read here."""
    if allowed is None:
        raise TypeError(
            "pallas must be an explicit bool resolved outside the trace "
            "(pallas_env_enabled() at the jit boundary) — resolving the "
            "env inside a traced function bakes a stale value into the "
            "cached executable")
    if not allowed:
        return False
    from h2o_tpu.core.cloud import backend_is_tpu
    if not backend_is_tpu():
        return False
    from h2o_tpu.ops.hist_pallas import min_tile_fits, mosaic_supports
    if not mosaic_supports(mm_dtype):
        return False
    if fine_map is not None:
        # adaptive kernel streams column groups of 8 or more (width
        # never blocks it), but the per-leaf range picks unroll over the
        # live frontier — the halving schedule's wide-B levels are
        # exactly the small-L top levels where it matters most
        return n_leaves <= 128 and min_tile_fits(8, B1, n_leaves, S, C,
                                                 mm_dtype)
    return min_tile_fits(C, B1, n_leaves, S, mm_dtype=mm_dtype)


def _block_hist(bins_blk, leaf_blk, stats_blk, n_leaves: int, nbins: int,
                mm_dtype=jnp.float32):
    """One row block's histogram: (C*(B+1), L*S).

    bins_blk:  (R, C) packed int (uint8/int16/int32) in [0, B] (B = NA
               bucket) — the one-hot compare below promotes against the
               int32 iota in-register, so packed bins feed the MXU with
               no widened copy of the block
    leaf_blk:  (R,)  int32 in [0, L); negative = row inactive this pass
    stats_blk: (R, S) f32, OR a quantized integer carrier (int16/int8,
               ops/statpack.py) — integer stats flip the contraction to
               an integer dot_general with int32 accumulation: both
               operands at the carrier itemsize, the (C*B1, L*S) table
               exact by the statpack qmax row bound
    mm_dtype:  matmul input dtype (f32 path only); bf16 is one MXU pass
               where f32 (HIGHEST) is several, at the cost of ~3
               mantissa digits on the per-row stats (the one-hot side
               is exact either way).
    """
    B1 = nbins + 1
    C = bins_blk.shape[1]
    S = stats_blk.shape[1]
    quantized = jnp.issubdtype(stats_blk.dtype, jnp.integer)
    with jax.named_scope("h2o.tree.hist.onehot"):
        leafhot = (leaf_blk[:, None] == jnp.arange(n_leaves)[None, :])
        # zero stats of inactive rows BEFORE the product: padded rows carry
        # NaN payloads and 0 * NaN would poison the accumulator (the
        # quantized carrier has no NaN, but padded rows still must not
        # count; the weak 0 keeps the carrier dtype)
        stats_blk = jnp.where(leaf_blk[:, None] >= 0, stats_blk, 0)
        a = (leafhot[:, :, None] * stats_blk[:, None, :]).reshape(
            -1, n_leaves * S)                                 # (R, L*S)
        binhot = (bins_blk[:, :, None] ==
                  jnp.arange(B1)[None, None, :]).reshape(-1, C * B1)
        # (R, C*B1)
    with jax.named_scope("h2o.tree.hist.contract"):
        if quantized:
            # integer MXU path: one-hot cast to the SAME narrow carrier
            # in-register (values are 0/1 — exact), int32 accumulator.
            # Overflow-free by construction: statpack.stats_qmax bounds
            # |q| * rows below 2**31.
            return jax.lax.dot_general(
                binhot.astype(stats_blk.dtype), a,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)             # (C*B1, L*S)
        # the one-hot side is exact in any dtype; HIGHEST keeps the f32
        # stats side f32 — a TPU's default precision rounds f32 operands
        # to bf16, which is what ``bf16`` asks for and f32 must not get
        return jax.lax.dot_general(
            binhot.astype(mm_dtype), a.astype(mm_dtype),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST
            if mm_dtype == jnp.float32 else None)             # (C*B1, L*S)


@jax.named_scope("h2o.tree.hist.onehot")
def map_buckets(bins_blk, leaf_blk, lo, hi, off, is_cat, nbins: int,
                fine_na: int):
    """Fine bins -> per-NODE histogram buckets (UniformAdaptive/Random).

    Integer arithmetic throughout so training-time bucketing and the
    recovered fine threshold (jit_engine._numeric_thr) agree EXACTLY:
    bucket(x) = ((x - lo)*B + o) // span,  span = hi - lo + 1.

    lo/hi: (L, C) int32 per-node fine ranges; off: (L, C) int32 random
    boundary offsets in fine units (zeros = UniformAdaptive).
    Categorical columns pass their level code through; NA (fine_na) maps
    to bucket B.
    """
    # sanctioned block-local widen (ops/binpack.py): the bucket
    # arithmetic below needs int32 range (x * nbins reaches F * B); the
    # convert fuses into this block's ops — no packed->int32 copy of
    # the matrix ever lands in HBM
    bins_blk = widen_bins(bins_blk)
    lf = jnp.maximum(leaf_blk, 0)
    lo_b = lo[lf]                                # (R, C)
    hi_b = hi[lf]
    o_b = off[lf]
    span = jnp.maximum(hi_b - lo_b + 1, 1)
    x = jnp.clip(bins_blk - lo_b, 0, span - 1)
    nb = jnp.clip((x * nbins + o_b) // span, 0, nbins - 1)
    out = jnp.where(is_cat[None, :], jnp.minimum(bins_blk, nbins), nb)
    return jnp.where(bins_blk == fine_na, nbins, out)


def histogram_build_traced(bins, leaf, stats, n_leaves: int, nbins: int,
                           block_rows: int = 8192, bf16: bool = False,
                           fine_map=None, pallas: bool = False):
    """Traceable distributed histogram: (L, C, B+1, S) replicated on every
    device.  Nestable inside outer jit/scan programs (the fused tree engine
    calls this inside its per-tree scan body).

    bins:  (padded_rows, C) packed int (uint8/int16/int32), row-sharded
           — pre-binned features at the dtype the bin count permits
    leaf:  (padded_rows,)  int32, row-sharded — leaf assignment, <0 inactive
    stats: (padded_rows, S) f32, row-sharded — (w, wg, wgg, wh); OR the
           quantized int16/int8 carrier (ops/statpack.py), which flips
           the whole build — block matmuls, scan accumulator, and the
           hist.table cross-node reduce — to exact int32, so the table
           is identical under any block partition or mesh shape and the
           combine ships integer bytes (PR 18 ledger)
    fine_map: None for direct (global-grid) binning, else
    (lo, hi, off, is_cat, fine_na) enabling per-node adaptive bucket
    placement (map_buckets) fused into each row block.

    ``pallas`` must be an EXPLICIT bool resolved outside any enclosing
    trace (``pallas_env_enabled()`` at the jit boundary, where it is a
    static arg of the executable key): resolving H2O_TPU_HIST_PALLAS
    here — inside a traced function — would bake the value read at
    first-trace time into the cached executable, and a later env flip
    would silently hit the stale program.

    Padded/invalid rows must arrive with leaf < 0 (they then match no leaf
    one-hot and contribute nothing).
    """
    mesh = cloud().mesh
    C, S = bins.shape[1], stats.shape[1]
    B1 = nbins + 1

    if fine_map is None:
        extra_specs = ()
        extra = ()
    else:
        lo, hi, off, is_cat_m, fine_na = fine_map
        extra_specs = (P(), P(), P(), P())
        extra = (lo, hi, off, is_cat_m)

    from h2o_tpu.ops.hist_pallas import matmul_dtype
    use_pallas = _pallas_eligible(C, B1, n_leaves, S, fine_map,
                                  allowed=pallas,
                                  mm_dtype=matmul_dtype(stats.dtype, bf16))

    dp = cloud().data_pspec
    @functools.partial(shard_map_compat, mesh=mesh,
                       in_specs=(dp(None), dp(),
                                 dp(None)) + extra_specs,
                       out_specs=P(), check_vma=False)
    def run(b_sh, l_sh, s_sh, *rep):
        if use_pallas:
            if fine_map is None:
                from h2o_tpu.ops.hist_pallas import hist_pallas
                acc = hist_pallas(b_sh, l_sh, s_sh, n_leaves, nbins,
                                  bf16=bf16)
            else:
                from h2o_tpu.ops.hist_pallas import hist_pallas_adaptive
                acc = hist_pallas_adaptive(
                    b_sh, l_sh, s_sh, rep[0], rep[1], rep[2],
                    rep[3], n_leaves, nbins, fine_na, bf16=bf16)
            return hpsum(acc, "hist.table")
        R = b_sh.shape[0]
        blk = min(block_rows, R)
        nblk = R // blk
        b3 = b_sh[: nblk * blk].reshape(nblk, blk, -1)
        l3 = l_sh[: nblk * blk].reshape(nblk, blk)
        s3 = s_sh[: nblk * blk].reshape(nblk, blk, -1)

        mmd = jnp.bfloat16 if bf16 else jnp.float32

        def bucketize(bb, lb):
            if fine_map is None:
                return bb
            return map_buckets(bb, lb, rep[0], rep[1], rep[2], rep[3],
                               nbins, fine_na)

        def body(acc, xs):
            bb, lb, sb = xs
            return acc + _block_hist(bucketize(bb, lb), lb, sb, n_leaves,
                                     nbins, mmd), None

        acc_dtype = (jnp.int32
                     if jnp.issubdtype(s_sh.dtype, jnp.integer)
                     else jnp.float32)
        init = jnp.zeros((C * B1, n_leaves * S), acc_dtype)
        acc, _ = jax.lax.scan(body, init, (b3, l3, s3))
        rem = R - nblk * blk
        if rem:
            # the rows left over are PADDED to a whole block (inactive
            # rows, leaf -1), so that every contraction of the program has
            # the one shape.  Cut at its own, shorter shape the last block
            # was a contraction that one block in 1,404 ran, and at 6,656
            # rows x 13 columns x 338 bins the chip's compiler emitted one
            # that handed back a table of zeros: both trees of a job grew
            # no split (PERF.md, PR 34).  Cutting every block inside the
            # scan instead (the last one starting early and masked) costs
            # a seventh of a window: XLA then reads `bins` block by block
            # where it keeps one relayout of the whole matrix for the scan
            pad = blk - rem
            bb = jnp.pad(b_sh[nblk * blk:], ((0, pad), (0, 0)))
            lb = jnp.pad(l_sh[nblk * blk:], (0, pad), constant_values=-1)
            sb = jnp.pad(s_sh[nblk * blk:], ((0, pad), (0, 0)))
            acc = acc + _block_hist(bucketize(bb, lb), lb, sb, n_leaves,
                                    nbins, mmd)
        return hpsum(acc, "hist.table")

    # the block scan, its accumulator, the cross-node combine and the
    # table's relayout are the contraction's; the one-hot build inside
    # carries its own, deeper scope
    with jax.named_scope("h2o.tree.hist.contract"):
        h = run(bins, leaf, stats, *extra)          # (C*B1, L*S)
        return (h.reshape(C, B1, n_leaves, S)
                 .transpose(2, 0, 1, 3))            # (L, C, B+1, S)


_histogram_build_jit = jax.jit(
    histogram_build_traced,
    static_argnames=("n_leaves", "nbins", "block_rows", "bf16",
                     "pallas"))


def histogram_build(bins, leaf, stats, n_leaves: int, nbins: int,
                    block_rows: int = 8192, bf16: bool = False):
    """Public standalone entry: resolves the Pallas opt-IN env OUTSIDE
    the trace (it is a static jit arg, so toggling H2O_TPU_HIST_PALLAS
    between calls takes effect instead of hitting a stale executable).
    Dispatched through ``kernel_fallback``: a Mosaic/Pallas compile
    failure or VMEM-gate rejection degrades to the portable XLA
    executable (pallas=False is a distinct static-arg program) instead
    of failing the caller — closing the core/oom.py follow-up where this
    standalone entry had no fallback route."""
    from h2o_tpu.core.oom import kernel_fallback

    def run(use_pallas: bool):
        return _histogram_build_jit(bins, leaf, stats, n_leaves=n_leaves,
                                    nbins=nbins, block_rows=block_rows,
                                    bf16=bf16, pallas=use_pallas)

    return kernel_fallback("hist.standalone", run,
                           pallas=pallas_env_enabled())


def bin_features(matrix, split_points):
    """Map raw feature values to bin indices against per-column split points.

    split_points: (C, B-1) ascending thresholds (NaN-padded tails allowed);
    value v falls in bin = #thresholds <= v; NaN value -> NA bucket B.
    Matches DHistogram's bin() contract (values below range -> bin 0, above
    -> last bin).
    """
    v = matrix[:, :, None]                      # (R, C, 1)
    t = split_points[None, :, :]                # (1, C, B-1)
    b = jnp.sum((v >= t) & ~jnp.isnan(t), axis=2).astype(jnp.int32)
    nbins = split_points.shape[1] + 1
    return jnp.where(jnp.isnan(matrix), nbins, b)
