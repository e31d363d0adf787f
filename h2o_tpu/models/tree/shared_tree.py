"""SharedTree — histogram-based distributed tree induction.

Reference (hex/tree/**, SURVEY §2.2 + §3.3): the driver loop
``scoreAndBuildTrees`` builds each tree level-by-level; the fused
score+histogram MRTask ``ScoreBuildHistogram2`` re-assigns rows to leaves and
accumulates per-(leaf,col,bin) DHistograms; ``DTree.findBestSplitPoint``
(DTree.java:984) picks splits by squared-error reduction with NA-direction
handling and min_rows constraints; categorical splits are bitsets; trees are
stored compressed and walked by the scorer (CompressedTree.java).

TPU-native redesign:
- rows are pre-binned ONCE against global quantile split points (the
  QuantilesGlobal histogram_type; reference GuidedSplitPoints) — binning is
  a (R,C,B) comparison fused by XLA;
- the per-level histogram is the MXU one-hot matmul kernel
  (h2o_tpu/ops/histogram.py) with an ICI psum replacing the node tree-reduce;
- split finding is vectorized over ALL (leaf, col, bin, na-dir) candidates at
  once on replicated (L,C,B+1,4) histograms — the reference does this
  serially per leaf on the driver (DTree.java:616);
- EVERY split is a left-membership BITSET over bins: numeric splits are
  prefix bitsets in value order, categorical splits are prefix bitsets in
  target-mean order (the classic optimal-subset trick; reference enum splits
  are bitsets too, DTree.Split), NA direction is the bitset's NA-bucket bit;
- a tree is a fixed-shape heap array (split_col / bitset / value per node,
  node i's children at 2i+1, 2i+2) — the CompressedTree analog that scoring
  walks in D fixed descend steps, fully vectorized over rows;
- leaf values come out of the SAME histogram (Newton numerator/denominator
  slots), fusing the reference's separate GammaPass MRTask.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from h2o_tpu.core.cloud import cloud, shard_map_compat
from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame
from h2o_tpu.models.model import DataInfo, Model, ModelBuilder
from h2o_tpu.ops.binpack import (bins_bucket, bins_pack_enabled, cast_bins,
                                 packed_dtype_name)
from h2o_tpu.ops.descend import descend
from h2o_tpu.ops.histogram import histogram_build, onehot_width

EPS = 1e-10


class BinnedData(NamedTuple):
    # (R, C) packed int in [0, F]; F = NA bucket.  Dtype is the
    # narrowest the fine bin count permits under the tree.bins_dtype
    # lever (ops/binpack.py decode contract: same integers, narrower
    # carrier), int32 when the lever resolves to the reference.
    bins: jax.Array
    split_points: np.ndarray  # (C, F-1) f32 host copy (model artifact)
    split_points_dev: jax.Array
    is_cat: np.ndarray       # (C,) bool
    nbins: int               # histogram bucket count B (bitset width B+1)
    # fine-grid resolution F >= B (UniformAdaptive/Random: the uniform
    # top-level grid, reference nbins_top_level; QuantilesGlobal: F == B)
    fine_nbins: int = 0
    hist_type: str = "QuantilesGlobal"
    # (C,) int32: how many bins each column really has inside the one
    # table — a numeric column the stated ``nbins``, a categorical one a
    # bin per level (capped at nbins_cats); codes at or past it are
    # unseen levels and bin to the NA bucket
    col_nbins: Optional[np.ndarray] = None

    @property
    def fine(self) -> int:
        return self.fine_nbins or self.nbins


@functools.partial(jax.jit, static_argnames=("nbins",))
@jax.named_scope("h2o.bin.quantile")
def _quantile_split_points(matrix, nrows, nbins: int):
    """Per-column quantile split points via ONE batched sort.

    Sorts every column at once (XLA fuses into a single program; NaNs sort
    last so per-column valid counts index the true quantile ranks).  This is
    the QuantilesGlobal strategy computed the TPU way — a sort is far
    cheaper here than the reference's iterative histogram refinement per
    column (Quantile.java), which remains available for the public
    /3/Quantiles surface.
    """
    R, C = matrix.shape
    rowmask = (jnp.arange(R) < nrows)[:, None]
    mx = jnp.where(rowmask, matrix, jnp.nan)
    xs = jnp.sort(mx, axis=0)                        # NaNs last
    cnt = jnp.sum(rowmask & ~jnp.isnan(mx), axis=0)  # (C,)
    probs = jnp.arange(1, nbins) / nbins             # (B-1,)
    ranks = jnp.clip((probs[:, None] * (cnt[None, :] - 1)).astype(jnp.int32),
                     0, jnp.maximum(cnt[None, :] - 1, 0))
    sp = jnp.take_along_axis(xs, ranks, axis=0)      # (B-1, C)
    return sp.T                                      # (C, B-1)


def resolve_histogram_type(p) -> str:
    """AUTO means UniformAdaptive, exactly like the reference
    (DHistogram.java:19-62 — AUTO -> UniformAdaptive default)."""
    ht = str(p.get("histogram_type") or "AUTO")
    return "UniformAdaptive" if ht == "AUTO" else ht


def table_width(levels: int) -> int:
    """The bins an enum of ``levels`` levels asks of the shared table:
    ``levels`` rounded up to a size class, a multiple of an eighth of
    the power of two below it (337 -> 352, 257 -> 288, 33 -> 36, 8 ->
    8).  The table's width is a SHAPE of every program a train compiles,
    and an enum's level count is whatever the file holds: a file that
    lacks a few of a column's levels (a split of the same data, next
    month's import) then runs the programs its sibling compiled instead
    of a new set a level count, for at most an eighth more bins, which
    no row falls in (``col_nbins`` keeps the column's own count)."""
    step = max(1, (1 << max(levels, 1).bit_length() - 1) >> 3)
    return -(-levels // step) * step


def prepare_bins(di: DataInfo, nbins: int, nbins_cats: int,
                 histogram_type: str = "QuantilesGlobal",
                 nbins_top_level: int = 1024) -> BinnedData:
    """Feature binning for the tree engines.

    QuantilesGlobal: per-column global quantile grid of ``nbins - 1``
    thresholds (the one-shot batched sort) — F == B.

    UniformAdaptive / Random (reference DHistogram.java:19-62 AUTO
    default): a UNIFORM top-level fine grid of ``nbins_top_level`` bins
    over each column's [min, max]; the builders then place ``nbins``
    histogram buckets per NODE over the node's surviving fine range,
    refining resolution every level exactly like the reference's
    per-node DHistogram ranges (nbins_top_level halving schedule).

    Categorical columns always bin by level code, one bin a level up to
    ``nbins_cats`` (a code at or past that count — a level past the cap,
    or one the training domain does not hold — bins to the NA bucket).
    All columns share ONE table of width B = max(nbins, ``table_width``
    of the widest categorical): a numeric column fills its first
    ``nbins`` bins of it
    whatever B is (``col_nbins`` says which column has how many), and
    F >= B so codes and the NA sentinel (F) coexist in one packed matrix
    (uint8/int16/int32 by F under the ``tree.bins_dtype`` lever —
    ops/binpack.py: a table wider than 255 bins rides int16).  The
    adaptive grids place B buckets a node on every column (their bucket
    count is one number per level, not per column).
    """
    fr, xs = di.frame, di.x
    C = len(xs)
    is_cat = np.array([fr.vec(c).is_categorical for c in xs], bool)
    max_card = max([fr.vec(c).cardinality for c in di.cat_names] or [0])
    col_nbins = np.array(
        [min(fr.vec(c).cardinality, nbins_cats) if cat else nbins
         for c, cat in zip(xs, is_cat)], np.int32)
    B = max(nbins, table_width(min(max_card, nbins_cats)))
    adaptive = histogram_type in ("UniformAdaptive", "Random")
    # the width the XLA histogram contracts at for this table; an adaptive
    # job's buckets go through ``fine_map``, whose arm pads nothing, so it
    # carries no such field
    grid = {} if adaptive else {"onehot_bins": onehot_width(int(B) + 1)}
    with TimeLine.span("train", "bin", cat_cols=int(is_cat.sum()),
                       max_card=int(max_card), table_bins=int(B), **grid):
        if adaptive and _stream_blocks_enabled(fr, xs):
            # frame bigger than the HBM budget: never materialize the
            # full matrix — stream shard-aligned windows through binning
            return _prepare_bins_streamed(fr, xs, is_cat, B,
                                          max(int(nbins_top_level), B),
                                          histogram_type, col_nbins)
        m = fr.as_matrix(xs)
        if adaptive:
            F = max(int(nbins_top_level), B)
            mn = np.asarray(_col_min_max(m, jnp.int32(fr.nrows)))
            sp = _uniform_split_points(mn[0], mn[1], is_cat, C, F)
        else:
            F = B
            sp_raw = np.asarray(_quantile_split_points(
                m, jnp.int32(fr.nrows), nbins))
            # dedupe per column (repeated quantiles collapse to one
            # threshold); categorical columns get no thresholds, and a
            # table widened by a categorical column leaves the numeric
            # rows' tail NaN
            sp = np.full((C, B - 1), np.nan, np.float32)
            for j in range(C):
                if is_cat[j]:
                    continue
                qs = np.unique(sp_raw[j][~np.isnan(sp_raw[j])])
                sp[j, : len(qs)] = qs
        sp_dev = jax.device_put(jnp.asarray(sp), cloud().replicated)
        bins = bin_matrix(m, sp_dev, is_cat, F, col_nbins)
        return BinnedData(bins, sp, sp_dev, is_cat, B, F, histogram_type,
                          col_nbins)


def shared_bins(p: Dict, x, y, train: Frame,
                offset: Optional[str] = None) -> Optional[BinnedData]:
    """The ONE ``BinnedData`` of a cross-validated job
    (``ModelBuilder._cv_shared`` of the tree builders): ``prepare_bins``
    reads no weights (the split points are those of all rows), so the K
    fold models and the main model bin alike, to the bit, and one binning
    serves all of them.  None with a checkpoint, whose grid is the main
    model's alone: every model then bins for itself."""
    if p.get("checkpoint"):
        return None
    di = DataInfo(train, x, y, mode="tree",
                  weights=p.get("weights_column"), offset=offset)
    return prepare_bins(di, int(p["nbins"]), int(p["nbins_cats"]),
                        resolve_histogram_type(p),
                        int(p.get("nbins_top_level") or 1024))


def bin_matrix(matrix, split_points_dev, is_cat, fine_nbins: int,
               col_nbins=None, scoring: bool = False):
    """Bin raw values AND pack to the narrowest dtype the fine bin
    count permits — the one binning entry every trainer and scorer
    shares.  The ``tree.bins_dtype`` lever is resolved HERE, outside
    ``_bin_all``'s trace (the packed dtype is part of every downstream
    executable's aval signature, so a lever flip selects a different
    executable instead of silently hitting a stale one).  Scoring a
    model under a different lever state than it trained with is safe:
    packed and int32 matrices hold identical integers (ops/binpack.py
    decode contract), so descent and histograms agree bitwise.
    ``col_nbins`` is the model's ``col_nbins`` (BinnedData): None, from
    an artifact that predates it, keeps every categorical code.
    ``scoring``: the matrix is a frame being SCORED (a validation frame,
    ``predict``), binned under scope ``h2o.score.bin`` so that a profile
    tells it from the training frame's ``h2o.bin.assign``."""
    # a TRACED matrix means a caller is compiling its whole predict
    # around this call (serve/engine.py): the bins are an intermediate
    # of that program, not an HBM-resident input, and a lever cannot be
    # probed mid-trace — the int32 reference holds the same integers
    packed = not isinstance(matrix, jax.core.Tracer) and bins_pack_enabled(
        bins_bucket(matrix.shape[0], matrix.shape[1], fine_nbins))
    return _bin_all(matrix, split_points_dev, jnp.asarray(is_cat),
                    fine_nbins,
                    out_dtype=packed_dtype_name(fine_nbins, packed),
                    col_nbins=None if col_nbins is None
                    else jnp.asarray(col_nbins, jnp.int32),
                    scope="h2o.score.bin" if scoring else "h2o.bin.assign")


def bin_matrix_out(matrix, out: Dict):
    """``bin_matrix`` over a model-output dict: the one way a scorer
    bins raw values into a trained model's bin space."""
    return bin_matrix(matrix, jnp.asarray(out["split_points"]),
                      out["is_cat"], model_fine_na(out),
                      out.get("col_nbins"), scoring=True)


def bin_validation_frame(job, valid: Frame, x, domains, binned: BinnedData):
    """A validation frame's bins in the training frame's bin space:
    its columns ``x`` in the training ``domains`` (``adapt_frame``: an
    enum matched by level string, an unseen level NA), then ``bin_matrix``
    on the training split points.  Returns ``(bins, prepared)``;
    ``prepared`` is the span ``train.valid.prepare``'s ring event and the
    device count of rows with an unseen level, which the scorer writes
    into the event with its first scoring point's sync (no sync here,
    before the first launch)."""
    from h2o_tpu.models.model import adapt_frame
    with TimeLine.span("train", "valid.prepare",
                       rows=int(valid.nrows)) as ev:
        ad = adapt_frame(valid, x, domains, warn=job.warn)
        ev.update(cat_cols=int(np.sum(binned.is_cat)),
                  remapped_cols=len(ad.remapped),
                  unseen_levels=ad.unseen_levels,
                  unseen_rows=0 if ad.unseen_rows is None else None)
        bins = bin_matrix(ad.matrix, binned.split_points_dev,
                          binned.is_cat, binned.fine, binned.col_nbins,
                          scoring=True)
    return bins, (ev, ad.unseen_rows)


def final_validation_metrics(model, valid: Frame, scorer):
    """The validation metrics that end ``train()``, under span
    ``train.final_metrics.valid``: from the F the per-block scorer
    carried to the last kept tree (``source`` = ``carried_F``: the metric
    kernels, nothing binned or descended again), else by scoring the
    finished forest (``rescore``: no scorer ran, as with no scoring
    interval, stopping rule or runtime budget)."""
    ntrees = int(model.output["ntrees_actual"])
    carried = scorer is not None and scorer.is_validation and \
        scorer.ntrees == ntrees
    with TimeLine.span("train", "final_metrics.valid",
                       source="carried_F" if carried else "rescore"):
        if carried:
            return scorer.valid_metrics(scorer.F, ntrees)
        return model.model_metrics(valid)


@functools.partial(jax.jit, static_argnames=("nbins", "out_dtype", "scope"))
def _bin_all(matrix, split_points, is_cat, nbins: int,
             out_dtype: str = "int32", col_nbins=None,
             scope: str = "h2o.bin.assign"):
    with jax.named_scope(scope):
        return _bin_all_traced(matrix, split_points, is_cat, nbins,
                               out_dtype, col_nbins)


def _bin_all_traced(matrix, split_points, is_cat, nbins: int,
                    out_dtype: str, col_nbins):
    """Raw values -> bin indices in [0, nbins]; nbins = NA bucket.

    A categorical code at or past its column's ``col_nbins`` (a level
    the training domain does not hold) is missing as a NaN is: it takes
    the NA bucket, so every descent sends it the node's NA side.

    Wide fine grids (UniformAdaptive's 1024 thresholds) use a per-column
    searchsorted instead of the (R, C, F-1) one-hot compare — log(F)
    work per value and no quadratic-ish temporary.

    ``out_dtype`` is the PACKING boundary: intermediates are int32
    (register-level, fused), the returned matrix is the narrow carrier.
    This function plus ops/binpack.py form the sanctioned packing layer
    (graftlint GL630 bans bin-matrix int32 widening everywhere else)."""
    if split_points.shape[1] > 63:
        t_sorted = split_points                  # NaN tails sort last
        num_bins = jax.vmap(
            lambda t, v: jnp.searchsorted(t, v, side="right"),
            in_axes=(0, 1), out_axes=1)(t_sorted, matrix)
        nan_counts = jnp.sum(jnp.isnan(split_points), axis=1)[None, :]
        num_bins = jnp.minimum(num_bins,
                               split_points.shape[1] - nan_counts)
    else:
        v = matrix[:, :, None]
        t = split_points[None, :, :]
        num_bins = jnp.sum((v >= t) & ~jnp.isnan(t), axis=2)
    cat_bins = jnp.clip(matrix, 0, nbins - 1).astype(jnp.int32)
    b = jnp.where(is_cat[None, :], cat_bins, num_bins)
    missing = jnp.isnan(matrix)
    if col_nbins is not None:
        missing = missing | (is_cat[None, :] & (matrix >= col_nbins[None, :]))
    return cast_bins(jnp.where(missing, nbins, b), out_dtype)


@jax.jit
@jax.named_scope("h2o.bin.quantile")
def _col_min_max(matrix, nrows):
    """Per-column (min, max) over valid rows, NaN-blind — the uniform
    fine grid's span (DHistogram find_maxEx/min analog)."""
    R = matrix.shape[0]
    rowmask = (jnp.arange(R) < nrows)[:, None]
    mx = jnp.where(rowmask & ~jnp.isnan(matrix), matrix, jnp.nan)
    return jnp.stack([jnp.nanmin(mx, axis=0), jnp.nanmax(mx, axis=0)])


def _uniform_split_points(col_min, col_max, is_cat, C: int,
                          F: int) -> np.ndarray:
    """The UniformAdaptive fine-grid thresholds from per-column (min,
    max) — ONE shared implementation so the streamed (blocked min/max)
    and full-matrix paths produce bit-identical split points."""
    span = np.where(col_max > col_min, col_max - col_min, 1.0)
    sp = np.full((C, F - 1), np.nan, np.float32)
    grid = (np.arange(1, F, dtype=np.float64)[None, :] / F)
    vals = (col_min[:, None] + grid * span[:, None]).astype(np.float32)
    for j in range(C):
        if not is_cat[j]:
            sp[j] = vals[j]
    return sp


# -- streamed binning: frames bigger than the HBM budget ---------------------

def _stream_blocks_enabled(fr: Frame, xs) -> bool:
    """Stream windows instead of materializing the full matrix?

    ``H2O_TPU_TIER_STREAM``: ``auto`` (default) streams when an HBM
    budget is set and the estimated f32 matrix exceeds it; ``1`` forces
    streaming (tests/drills); ``0`` disables.  Streaming requires the
    canonical layout (not ragged) and every column sharing the frame's
    capacity — the shard-aligned window math assumes ONE row layout."""
    from h2o_tpu.config import tier_stream_mode
    mode = tier_stream_mode()
    if mode in ("0", "off", "false", "no"):
        return False
    if fr.is_ragged:
        return False
    R = fr.padded_rows
    for c in xs:
        v = fr.vec(c)
        if v._device_rows() != R or v.host_data is not None:
            return False
    if mode in ("1", "on", "true", "yes"):
        return True
    from h2o_tpu.core.memory import manager
    budget = manager().budget
    return budget > 0 and R * len(xs) * 4 > budget


def _blk_neg_minmax(m):
    """Per-shard (min, -max) of a window — combined with pmin across
    shards and np.minimum across windows.  min is EXACT (no accumulation
    rounding), so any block partition reproduces the full-matrix
    nanmin/nanmax bit-for-bit; all-NaN columns come back (+inf, +inf)
    and are mapped to NaN by the caller, matching nanmin on empty."""
    ok = ~jnp.isnan(m)
    big = jnp.asarray(jnp.inf, m.dtype)
    return jnp.stack([jnp.min(jnp.where(ok, m, big), axis=0),
                      jnp.min(jnp.where(ok, -m, big), axis=0)])


def _build_window_scatter():
    """AOT-cached scatter: write a binned window into the full packed
    bins buffer at per-shard row offset ``start``.  Provably shard-local
    (dynamic_update_slice on each shard's own rows, no collectives);
    ``start`` is a TRACED operand, so ONE executable serves every
    window — zero steady-state recompiles."""
    mesh = cloud().mesh

    def body(buf, blk, start):
        return jax.lax.dynamic_update_slice_in_dim(buf, blk, start,
                                                   axis=0)

    dp = cloud().data_pspec
    return shard_map_compat(
        body, mesh=mesh,
        in_specs=(dp(None), dp(None), P()),
        out_specs=dp(None), check_vma=False)


def _scatter_window(buf, blk, w0: int):
    from h2o_tpu.core.exec_store import (aval_key, code_fingerprint,
                                         exec_store)
    key = ("tier_scatter", aval_key(buf), aval_key(blk))
    # site="tier.block": the scatter shares the streaming site's ladder
    # identity — its dispatch-level ladder sweeps (donation-aware);
    # the window-shrink rung lives in the caller's tier.block ladder
    return exec_store().dispatch(
        "tier", key, _build_window_scatter,
        (buf, blk, jnp.int32(w0)),
        site="tier.block",
        donate_argnums=(0,),
        persist=f"tier:scatter:{buf.dtype}:{blk.shape[0]}",
        content=code_fingerprint(_build_window_scatter))


def _prepare_bins_streamed(fr: Frame, xs, is_cat: np.ndarray, B: int,
                           F: int, histogram_type: str,
                           col_nbins: np.ndarray) -> BinnedData:
    """UniformAdaptive/Random binning without ever materializing the
    full matrix: pass 1 streams windows through a blocked min/max, pass
    2 bins each window and scatters it into the packed bins buffer.
    Both passes run under the OOM ladder at site ``tier.block`` (the
    window is the shrink quantum) and produce a BinnedData BITWISE equal
    to the full-matrix path — the bounded-HBM drill's contract."""
    from h2o_tpu.core import landing
    from h2o_tpu.core.mrtask import FrameBlockStreamer, map_reduce_blocked
    from h2o_tpu.core.oom import oom_ladder
    C = len(xs)
    R = fr.padded_rows
    streamer = FrameBlockStreamer(fr, xs)
    try:
        acc = map_reduce_blocked(_blk_neg_minmax, streamer, reduce="min")
        col_min, nmx = acc[0], acc[1]
        col_max = -nmx
        empty = (col_min == np.inf) & (nmx == np.inf)
        col_min = np.where(empty, np.nan, col_min).astype(np.float32)
        col_max = np.where(empty, np.nan, col_max).astype(np.float32)
        sp = _uniform_split_points(col_min, col_max, is_cat, C, F)
        sp_dev = jax.device_put(jnp.asarray(sp), cloud().replicated)
        packed = bins_pack_enabled(bins_bucket(R, C, F))
        dt = packed_dtype_name(F, packed)
        is_cat_dev = jnp.asarray(is_cat)
        col_nbins_dev = jnp.asarray(col_nbins, jnp.int32)
        buf = landing.reshard_rows(jnp.zeros((R, C), dt),
                                   cloud().matrix_sharding())
        L = streamer.per_shard_rows
        pos = 0
        streamer.stage(0, streamer.window)
        while pos < L:

            def attempt():
                # window re-derived inside: a ladder shrink between
                # retries must land a smaller block
                q = streamer.window
                w0 = min(pos, max(0, L - q))
                blk = streamer.device_block(w0, w0 + q)
                bb = _bin_all(blk, sp_dev, is_cat_dev, F, out_dtype=dt,
                              col_nbins=col_nbins_dev)
                return w0, bb, w0 + q

            w0, bb, pos = oom_ladder("tier.block", attempt,
                                     shrink=streamer.shrink)
            # tail-clamp overlap rewrites identical values (elementwise
            # binning), so the buffer stays bitwise-stable
            buf = _scatter_window(buf, bb, w0)
            if pos < L:
                q = streamer.window
                n0 = min(pos, L - q)
                streamer.stage(n0, n0 + q)
    finally:
        streamer.close()
    return BinnedData(buf, sp, sp_dev, is_cat, B, F, histogram_type,
                      col_nbins)


# ---------------------------------------------------------------------------
# RNG-state serialization (iteration checkpoints, core/recovery.py)
# ---------------------------------------------------------------------------

def rng_key_to_np(key) -> np.ndarray:
    """Typed PRNG key -> raw uint32 host array (checkpointable)."""
    return np.asarray(jax.random.key_data(key))


def rng_key_from_np(data: np.ndarray):
    """Inverse of rng_key_to_np — resumed builds continue the exact
    random stream, so an interrupted+resumed forest is bitwise equal to
    an uninterrupted one."""
    return jax.random.wrap_key_data(jnp.asarray(data))


# ---------------------------------------------------------------------------
# split finding
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("min_rows", "use_mono",
                                             "newton", "reg_lambda"))
@jax.named_scope("h2o.tree.split")
def find_splits(hist, is_cat, col_allowed, min_rows: float = 10.0,
                min_split_improvement: float = 1e-5, mono=None,
                use_mono: bool = False, newton: bool = False,
                reg_lambda: float = 0.0):
    """Best split per leaf from (L, C, B+1, 4) histograms.

    Returns per-leaf: do_split, col, bitset (B+1 left-membership incl NA
    bit), left/right Newton stats (wg, wh, w) for child values, and the
    leaf's own (wg, wh, w) for terminal values.

    ``mono`` ((C,) int, ±1/0) + ``use_mono`` enable monotone constraints
    (reference hex/tree/DTree.java:984 findBestSplitPoint monotone
    handling): candidate splits whose child values violate the declared
    direction are rejected; the builder additionally clamps child values
    to parent bounds (the XGBoost two-part scheme this engine's
    force_newton path matches).

    ``hist`` must be f32: a quantized build (ops/statpack.py) must
    dequantize ONCE per level at the table — never per row and never
    implicitly here, where an integer table would silently promote
    through every ratio below.  The guard fires at trace time.
    """
    if jnp.issubdtype(jnp.asarray(hist).dtype, jnp.integer):
        raise TypeError(
            "find_splits received an integer (quantized) histogram "
            "table — dequantize once per level at the table with "
            "ops/statpack.dequant_table before split finding")
    L, C, B1, _ = hist.shape
    B = B1 - 1
    w, wg, wgg, wh = (hist[..., k] for k in range(4))

    # order bins: numeric -> natural, categorical -> by mean gradient
    with jax.named_scope("h2o.tree.split.order"):
        mean = wg[..., :B] / jnp.maximum(w[..., :B], EPS)
        empty = w[..., :B] <= 0
        key = jnp.where(empty, jnp.inf, mean)
        natural = jnp.broadcast_to(
            jnp.arange(B, dtype=jnp.float32)[None, None, :], key.shape)
        order = jnp.argsort(jnp.where(is_cat[None, :, None], key, natural),
                            axis=2)                          # (L, C, B)

        def sort_take(x):
            return jnp.take_along_axis(x[..., :B], order, axis=2)

        sw, swg, swgg, swh = map(sort_take, (w, wg, wgg, wh))
    # the scan over ordered prefixes: sums, gains, the arg-max
    with jax.named_scope("h2o.tree.split.scan"):
        cw, cwg, cwgg, cwh = (jnp.cumsum(x, axis=2)
                              for x in (sw, swg, swgg, swh))
        naw, nawg, nawgg, nawh = (x[..., B] for x in (w, wg, wgg, wh))
        tot_w = cw[..., -1] + naw
        tot_wg = cwg[..., -1] + nawg
        tot_wgg = cwgg[..., -1] + nawgg
        tot_wh = cwh[..., -1] + nawh

        def se(w_, wg_, wgg_):
            return wgg_ - wg_ ** 2 / jnp.maximum(w_, EPS)

        se_parent = se(tot_w, tot_wg, tot_wgg)               # (L, C)

        def side_gain(na_left):
            lw = cw + (naw[..., None] if na_left else 0.0)
            lwg = cwg + (nawg[..., None] if na_left else 0.0)
            lwgg = cwgg + (nawgg[..., None] if na_left else 0.0)
            lwh = cwh + (nawh[..., None] if na_left else 0.0)
            rw = tot_w[..., None] - lw
            rwg = tot_wg[..., None] - lwg
            rwgg = tot_wgg[..., None] - lwgg
            rwh = tot_wh[..., None] - lwh
            gain = (se_parent[..., None] - se(lw, lwg, lwgg)
                    - se(rw, rwg, rwgg))
            ok = (lw >= min_rows) & (rw >= min_rows)
            if use_mono:
                # reject splits whose child values violate the declared
                # direction (increasing: right >= left)
                if newton:
                    lv = lwg / jnp.maximum(lwh + reg_lambda, EPS)
                    rv = rwg / jnp.maximum(rwh + reg_lambda, EPS)
                else:
                    lv = lwg / jnp.maximum(lw, EPS)
                    rv = rwg / jnp.maximum(rw, EPS)
                m = mono[None, :, None].astype(jnp.float32)
                ok = ok & ((m == 0) | (m * (rv - lv) >= 0))
            return jnp.where(ok, gain, -jnp.inf)

        gains = jnp.stack([side_gain(False), side_gain(True)], axis=-1)
        # candidate axis: (L, C, B, 2) — the last split index B-1 sends
        # every bin left: valid only with the NA bucket on the right and
        # min_rows rows in it ("missing or not"), else it self-eliminates
        gains = jnp.where(col_allowed[..., None, None], gains, -jnp.inf)
        flat = gains.reshape(L, -1)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        col = (best // (B * 2)).astype(jnp.int32)
        rem = best % (B * 2)
        split_b = (rem // 2).astype(jnp.int32)
        na_left = (rem % 2).astype(jnp.bool_)

        thresh = jnp.maximum(
            min_split_improvement *
            jnp.max(jnp.maximum(se_parent, 0.0), axis=1), EPS)
        do_split = best_gain > thresh

    # gather chosen column's per-leaf arrays
    li = jnp.arange(L)
    with jax.named_scope("h2o.tree.split.order"):
        order_c = order[li, col]                              # (L, B)
        rank = jnp.argsort(order_c, axis=1)                   # inverse perm
    bitset_bins = rank <= split_b[:, None]                    # (L, B)
    bitset = jnp.concatenate([bitset_bins, na_left[:, None]], axis=1)

    def pick(cum, na):
        base = cum[li, col, split_b]
        return base + jnp.where(na_left, na[li, col], 0.0)

    lw, lwg, lwh = pick(cw, naw), pick(cwg, nawg), pick(cwh, nawh)
    lwgg = pick(cwgg, nawgg)
    leaf_stats = dict(w=tot_w[li, col], wg=tot_wg[li, col],
                      wh=tot_wh[li, col], wgg=tot_wgg[li, col])
    left_stats = dict(w=lw, wg=lwg, wh=lwh, wgg=lwgg)
    right_stats = dict(w=leaf_stats["w"] - lw, wg=leaf_stats["wg"] - lwg,
                       wh=leaf_stats["wh"] - lwh,
                       wgg=leaf_stats["wgg"] - lwgg)
    return dict(do_split=do_split, gain=best_gain, col=col, bitset=bitset,
                split_b=split_b, na_left=na_left,
                leaf=leaf_stats, left=left_stats, right=right_stats)


# ---------------------------------------------------------------------------
# tree storage + scoring
# ---------------------------------------------------------------------------

class Forest(NamedTuple):
    """Stacked compressed trees: (T, K, N) node arrays.  ``child`` None =
    dense heap (children at 2n+1/2n+2), else left-child pool pointers
    (right = left+1) from the sparse-frontier engine."""
    split_col: jax.Array   # int32, -1 = terminal
    bitset: jax.Array      # bool (T, K, N, B+1) — left membership
    value: jax.Array       # f32 node value (terminal prediction)
    depth: int
    nbins: int
    child: object = None   # int32 (T, K, N) or None


@functools.partial(jax.jit, static_argnames=("depth", "fine_na"))
@jax.named_scope("h2o.score.descent")
def forest_score(bins, split_col, bitset, value, depth: int, child=None,
                 thr=None, na_l=None, fine_na: int = -1):
    """Sum of tree outputs per (row, k-slot): bins (R,C) -> (R, K).

    One descent implementation only: the per-tree values come from
    forest_tree_values (same scan) and are summed over trees — scoring
    and staged predictions can never diverge."""
    vals = forest_tree_values(bins, split_col, bitset, value, depth,
                              child=child, thr=thr, na_l=na_l,
                              fine_na=fine_na)              # (T, K, R)
    return jnp.sum(vals, axis=0).T                          # (R, K)


@functools.partial(jax.jit, static_argnames=("depth", "fine_na"))
@jax.named_scope("h2o.score.descent")
def forest_tree_values(bins, split_col, bitset, value, depth: int,
                       child=None, thr=None, na_l=None, fine_na: int = -1):
    """Per-TREE outputs (T, K, R) — forest_score without the sum, for
    staged predictions (GBMModel.StagedPredictionsTask)."""
    T, K, H = split_col.shape
    R = bins.shape[0]

    def one_tree(carry, tk):
        sc, bs, vl = tk[0], tk[1], tk[2]
        rest = list(tk[3:])
        ch = rest.pop(0) if child is not None else None
        th = rest.pop(0) if thr is not None else None
        na = rest.pop(0) if thr is not None else None
        return carry, vl[descend(bins, sc, bs, depth, child=ch, thr=th,
                                 na_l=na, fine_na=fine_na)]

    xs = (split_col.reshape(T * K, H),
          bitset.reshape(T * K, H, -1),
          value.reshape(T * K, H))
    if child is not None:
        xs = xs + (child.reshape(T * K, H),)
    if thr is not None:
        xs = xs + (thr.reshape(T * K, H), na_l.reshape(T * K, H))
    _, vals = jax.lax.scan(one_tree, 0, xs)
    return vals.reshape(T, K, R)


def model_fine_na(out: Dict) -> int:
    """The NA bin sentinel of a model's stored binning (fine grid when
    adaptive, else the histogram bucket count)."""
    return int(out.get("fine_nbins") or out["nbins"])


def forest_thr_args(out: Dict) -> Dict:
    """kwargs carrying the adaptive numeric-threshold arrays (absent on
    pre-adaptive models — pure-bitset descent)."""
    if out.get("thr_bin") is None:
        return dict(thr=None, na_l=None, fine_na=-1)
    return dict(thr=jnp.asarray(out["thr_bin"]),
                na_l=jnp.asarray(out["na_left"]),
                fine_na=model_fine_na(out))


def forest_score_out(bins, out: Dict, depth: int = None) -> jax.Array:
    """forest_score over a model-output dict (handles both node layouts;
    models saved before the frontier engine have no "child" key)."""
    ch = out.get("child")
    return forest_score(
        bins, jnp.asarray(out["split_col"]), jnp.asarray(out["bitset"]),
        jnp.asarray(out["value"]),
        int(depth if depth is not None else out["max_depth"]),
        child=jnp.asarray(ch) if ch is not None else None,
        **forest_thr_args(out))


def forest_predict_frame(forest: Forest, binned_bins) -> jax.Array:
    return forest_score(binned_bins, forest.split_col, forest.bitset,
                        forest.value, forest.depth, child=forest.child)


# ---------------------------------------------------------------------------
# single-tree build (host loop over levels, jitted steps)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("newton",))
def _node_value(wg, wh, w, newton: bool):
    """Leaf value: Newton wg/wh (GammaPass analog) or plain mean wg/w."""
    denom = jnp.where(newton, jnp.maximum(wh, EPS), jnp.maximum(w, EPS))
    return wg / denom
