"""Fully-jitted tree training — the whole boosting loop as ONE XLA program.

The reference drives tree building from a host loop (SharedTree.java driver,
one MRTask round-trip per level).  A first TPU port did the same and was
dominated by dispatch latency: ~20 host<->device round-trips per tree.  The
TPU-native answer is to move the ENTIRE loop into XLA:

- levels are unrolled statically inside the traced function (D is a static
  param, so each level gets its exact leaf count L=2^d — no padding waste);
- trees are a ``lax.scan`` over per-tree RNG keys, with the f-vector as
  carry and the compressed tree arrays as stacked scan outputs;
- gradients, histograms (MXU one-hot matmuls + ICI psum), split finding,
  row routing, leaf values, and the f update all fuse into the scan body.

One dispatch trains the whole model.  The host only sees the final
(T, K, H) tree arrays.

TWO ENGINES, ONE OUTPUT CONTRACT:

- **dense heap** (``build_tree_traced``): level d allocates exactly
  L = 2^d histogram rows and heap slots; node n's children sit at
  2n+1 / 2n+2 (``child`` is None in the output).  Optimal for shallow
  trees — no scatter, purely static offsets.
- **sparse frontier** (``build_tree_frontier``): the live frontier is
  capped at ``max_live_leaves`` slots per level (LightGBM-style);
  nodes live in a grows-with-splits pool with an explicit ``child``
  pointer array (left child id; right = left+1).  When the frontier
  overflows, the children with the largest residual impurity
  (wgg − wg²/w) stay live and the rest become terminal leaves — a
  best-first criterion.  This is the TPU answer to the reference's
  sparse CompressedTree (hex/tree/DTree.java:891-935 compress():
  cost scales with actual leaves, not 2^depth): histograms are
  (K_live, C, B+1, 4) however deep the tree goes, so stock DRF's
  default max_depth=20 trains unclamped with bounded memory.  Its deep
  levels (64 nodes and more) build the histogram by node windows over
  rows sorted by node (``ops/histogram.histogram_window_traced``: the
  cost does not grow with the frontier's width; a block gathers its
  rows' bins as words packed once a block of trees by
  ``binpack.pack_words``), and the levels from
  ``frontier_loop_start`` on run as ONE loop body at the cap's width,
  so a depth-20 tree compiles as about seven levels.

``train_forest`` picks the engine statically: dense when every level
fits inside ``max_live_leaves`` (2^(D-1) <= cap — the two engines
build IDENTICAL trees in that regime), frontier beyond.  Depth is
still sanity-clamped at ``H2O_TPU_MAX_TREE_DEPTH`` (default 30).

ROUTING (``_route_level``, one rule for both engines): each row picks
its node's split record from the level's L nodes by compare-select-sums
over the left sets packed into W uint32 words (about L * (W + 1)
candidates a row, a few passes over the rows), not by per-row gathers,
which the chip issues one access at a time.  The form is a static
function of (L, W): select up to ``ROUTE_SELECT_MAX`` = 20,480
candidates, gather past it.  Read standalone on a v5e chip (PERF.md
section 6, PR 41): at 128 nodes the select takes 11.1 / 41.6 ms a level
where the gather takes 146 / 295 ms (5.25M x 28 rows at 256 slots /
11.5M x 13 at 354); the gather wins from 4,096 nodes at 256 slots and
2,048 at 354.  So every level of a depth-8 tree selects, and a frontier
of thousands of nodes gathers.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from h2o_tpu.models.distributions import get_distribution
from h2o_tpu.models.tree.shared_tree import find_splits, node_sq_err
from h2o_tpu.ops import statpack
from h2o_tpu.ops.binpack import pack_words, pick_bin
from h2o_tpu.ops.histogram import histogram_build_traced as _shard_histogram
from h2o_tpu.ops.histogram import (N_STATS, WINDOW_LEAVES, _pallas_eligible,
                                   hist_form, histogram_window_traced,
                                   window_level)

EPS = 1e-10


def max_supported_depth() -> int:
    import os
    return int(os.environ.get("H2O_TPU_MAX_TREE_DEPTH", "30"))


def max_live_leaves() -> int:
    """Frontier width cap (H2O_TPU_MAX_LIVE_LEAVES, default 4096): levels
    wider than this run the sparse-frontier engine's best-first
    selection; histogram memory is bounded by (cap, C, B+1, 4)."""
    import os
    return int(os.environ.get("H2O_TPU_MAX_LIVE_LEAVES", "4096"))


def clamp_depth(requested: int, log=None) -> int:
    """Sanity-clamp a requested max_depth (module docstring).  Since the
    sparse-frontier engine the cap defaults to 30 (cost grows linearly
    with depth, so only absurd requests clamp).  Never silent: logs a
    warning; builders also record ``effective_max_depth`` in the model
    output and a client-visible warning."""
    cap = max_supported_depth()
    if requested > cap:
        if log is not None:
            log.warning(
                "max_depth=%d exceeds the engine depth limit; clamped "
                "to %d (H2O_TPU_MAX_TREE_DEPTH; see "
                "models/tree/jit_engine.py design note)", requested, cap)
        return cap
    return int(requested)


def plan_engine(depth: int) -> int:
    """Static engine choice for a given tree depth: 0 = dense heap
    (every level fits in the frontier cap — identical trees, cheaper
    indexing), else the frontier width cap for the sparse engine."""
    cap = max_live_leaves()
    if depth < 1 or 2 ** (depth - 1) <= cap:
        return 0
    return cap


def frontier_plan(depth: int, cap: int):
    """Live-frontier width per level: doubles until the cap."""
    widths, width = [], 1
    for _ in range(depth):
        widths.append(width)
        width = min(2 * width, cap)
    return widths


def pool_size(depth: int, kleaves: int) -> int:
    """Node-pool slots for one tree: dense heap when kleaves == 0, else
    root + two child slots per possibly-split frontier node."""
    if kleaves <= 0:
        return 2 ** (depth + 1) - 1
    return 1 + 2 * sum(frontier_plan(depth, kleaves))


def _adaptive_ranges_init(L: int, C: int, F: int):
    """Root fine ranges: the whole top-level grid."""
    return (jnp.zeros((L, C), jnp.int32),
            jnp.full((L, C), F - 1, jnp.int32))


def _rand_offsets(key, L: int, C: int, lo, hi, random_mode: bool):
    """Random-histogram boundary offsets in fine units, per (leaf, col)
    (DHistogram random split points analog: every node's bucket
    boundaries shift by a random fraction of a bucket)."""
    if not random_mode:
        return jnp.zeros((L, C), jnp.int32)
    span = jnp.maximum(hi - lo + 1, 1)
    u = jax.random.uniform(key, (L, C))
    return jnp.minimum((u * span.astype(jnp.float32)).astype(jnp.int32),
                       span - 1)


def _numeric_thr(s, lo, hi, off, B: int):
    """Chosen bucket boundary -> EXACT fine-bin threshold: go-left is
    bucket(x) < k  <=>  x < lo + ceil((k*span - o)/B) (all-integer, the
    same arithmetic map_buckets applies)."""
    L = lo.shape[0]
    li = jnp.arange(L)
    colc = s["col"]
    lo_c = lo[li, colc]
    hi_c = hi[li, colc]
    o_c = off[li, colc]
    span = jnp.maximum(hi_c - lo_c + 1, 1)
    k = s["split_b"] + 1
    return lo_c + (k * span - o_c + B - 1) // B


def _refine_ranges(hist, lo, hi, off, B: int):
    """Observed-range tightening from the level's own histograms
    (DHistogram per-node min/max): the fine sub-range actually covered
    by non-empty buckets — free adaptivity for EVERY column, not just
    the split one."""
    wb = hist[..., 0][:, :, :B]                    # (L, C, B) weights
    have = wb > 0
    anyb = jnp.any(have, axis=2)
    first = jnp.argmax(have, axis=2).astype(jnp.int32)
    last = (B - 1 - jnp.argmax(have[:, :, ::-1], axis=2)).astype(jnp.int32)
    span = jnp.maximum(hi - lo + 1, 1)
    # bucket j covers fine [lo + ceil((j*span-o)/B), lo + ceil(((j+1)*
    # span-o)/B) - 1]
    lo_edge = lo + jnp.maximum((first * span - off + B - 1) // B, 0)
    hi_edge = lo + jnp.clip(((last + 1) * span - off + B - 1) // B,
                            1, span) - 1
    new_lo = jnp.where(anyb, lo_edge, lo)
    new_hi = jnp.where(anyb, jnp.maximum(hi_edge, lo_edge), hi)
    return new_lo, new_hi


def _child_ranges(new_lo, new_hi, s, thr_leaf, is_cat, do_split):
    """Children inherit the refined parent range; the split column is
    additionally truncated at the threshold (left: [lo, thr-1], right:
    [thr, hi]).  Returns (2L, C) interleaved left/right."""
    L, C = new_lo.shape
    li = jnp.arange(L)
    colc = s["col"]
    num_split = do_split & ~is_cat[colc]
    big = jnp.int32(1 << 28)
    lo2 = jnp.stack([new_lo, new_lo], axis=1).reshape(2 * L, C)
    hi2 = jnp.stack([new_hi, new_hi], axis=1).reshape(2 * L, C)
    thr_hi = jnp.where(num_split, thr_leaf - 1, big)     # left child cap
    thr_lo = jnp.where(num_split, thr_leaf, -big)        # right child floor
    hi2 = hi2.at[2 * li, colc].min(thr_hi)
    lo2 = lo2.at[2 * li + 1, colc].max(thr_lo)
    # degenerate guards (empty side): keep ranges ordered
    lo2 = jnp.minimum(lo2, hi2)
    return lo2, hi2


def matmul_route_enabled() -> bool:
    """Tri-state H2O_TPU_MATMUL_ROUTE: ``1`` forces the matmul router,
    ``0`` forces the gather router, ``auto``/unset (the default) defers
    to the autotuner (core/autotune.py ``tree.matmul_route`` lever) —
    on TPU both routers are probed on the live backend with a bitwise
    parity gate and the persisted winner applies; elsewhere the gather
    reference wins with zero probe runs.  This replaces the old blind
    "auto = on-if-TPU" rule with a measured decision.  Resolve OUTSIDE
    jit traces (static arg) like the sibling/pallas flags."""
    from h2o_tpu.core.autotune import resolve_flag
    return resolve_flag("tree.matmul_route")


# largest lookup table the matmul router will one-hot over; beyond this
# (deep frontier pools, wide adaptive root grids) the (R, table)
# intermediates outgrow the gathers they replace — the adaptive halving
# schedule's top levels (Bd up to nbins_top_level=1024) would otherwise
# materialize multi-GB (R, Bd+1) picks
_MM_ROUTE_MAX_TABLE = 128

_HI = jax.lax.Precision.HIGHEST


def _mm_pick(hot, table):
    """Exact per-row table lookup as a matmul: ``table[idx]`` with
    ``hot = onehot(idx)``.  Every row of ``hot`` has at most one nonzero,
    so the f32 contraction is exact (ints < 2**24, incl. -1 sentinels).
    TPUs serialize per-row random gathers; this rides the MXU instead."""
    return jax.lax.dot_general(
        hot.astype(jnp.float32), table.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())), precision=_HI)


def _mm_route_level(bins, lf, s, do_split, L: int, Bd: int, cat_choice,
                    adaptive: bool, thr_leaf, F: int):
    """Gather-free analog of the per-level routing block: returns
    (go_left, do_split[lf]) using one-hot matmuls over the (L, ·) split
    tables and a masked reduction for the per-row column pick.  Bitwise
    identical to the gather path (all contractions have one nonzero
    term per row).  ``cat_choice`` is the caller's is_cat[s["col"]]."""
    R, C = bins.shape
    leafhot = lf[:, None] == jnp.arange(L)[None, :]          # (R, L)
    colhot = (s["col"][:, None] ==
              jnp.arange(C)[None, :])                        # (L, C)
    # bins[r, col[lf[r]]]: pick the leaf's column per row
    P = _mm_pick(leafhot, colhot)                            # (R, C)
    b = jnp.sum(bins.astype(jnp.float32) * P, axis=1).astype(jnp.int32)
    # bitset[lf, b]: leaf-pick the bitset row, then mask-reduce bucket b
    T = _mm_pick(leafhot, s["bitset"])                       # (R, B+1)
    bcl = jnp.minimum(b, Bd) if adaptive else b
    gset = jnp.sum(
        T * (bcl[:, None] == jnp.arange(T.shape[1])[None, :]),
        axis=1) > 0.5
    if adaptive:
        # numeric thresholds + NA direction + split-kind, all leaf-picked
        tbl = jnp.stack([thr_leaf.astype(jnp.float32),
                         s["na_left"].astype(jnp.float32),
                         cat_choice.astype(jnp.float32),
                         do_split.astype(jnp.float32)], axis=1)
        V = _mm_pick(leafhot, tbl)                           # (R, 4)
        gthr = jnp.where(b == F, V[:, 1] > 0.5, b < V[:, 0])
        go_left = jnp.where(V[:, 2] > 0.5, gset, gthr)
        do_lf = V[:, 3] > 0.5
    else:
        go_left = gset
        do_lf = _mm_pick(leafhot, do_split.astype(jnp.float32)[:, None]
                         )[:, 0] > 0.5
    return go_left, do_lf


# The routing forms' crossover, in candidates a row: a level of L nodes
# whose left sets pack into W 32-bit words costs the select form about
# L * (W + 1) compare-selects a row, the gather form a fixed number of
# per-row accesses whatever L is.  One level standalone on a v5e chip,
# int32 bins, ms select / gather (PERF.md section 6, PR 41):
#   5.25M x 28, 256 slots (W 8):  L 128 11.1 / 146; 1024 40.8 / 148;
#                                 2048 81.1 / 148;  4096 159 / 148
#   11.5M x 13, 354 slots (W 12): L 128 41.6 / 295; 1024 146 / 303;
#                                 2048 350 / 303;   4096 693 / 303
# so the select wins up to about 23,000 candidates at W 12 and 34,000 at
# W 8; the rule keeps it to 20,480, under both.
ROUTE_SELECT_MAX = 20480


def route_words(n_slots: int) -> int:
    """32-bit words one node's left set of ``n_slots`` bits packs into."""
    return -(-int(n_slots) // 32)


def route_selects(L: int, n_slots: int) -> bool:
    """Whether a level of ``L`` nodes with ``n_slots``-bit left sets is
    routed by the select form (else by per-row gathers): the crossover
    ``ROUTE_SELECT_MAX`` on ``L * (W + 1)``, a static shape rule."""
    return L * (route_words(n_slots) + 1) <= ROUTE_SELECT_MAX


def _level_widths(kw: Dict):
    """The width each level of one tree of ``train_forest(**kw)`` runs
    at: ``2^d`` in the dense engine; in the frontier engine the plan's,
    the looped levels at the cap's."""
    D, B = int(kw["max_depth"]), int(kw["nbins"])
    kleaves = int(kw.get("kleaves") or 0)
    if kleaves <= 0:
        return [2 ** d for d in range(D)]
    d0 = frontier_loop_start(D, kleaves, B, int(kw.get("fine_nbins") or B),
                             bool(kw.get("adaptive")))
    return frontier_plan(D, kleaves)[:d0] + [kleaves] * (D - d0)


def route_plan(kw: Dict):
    """``(levels, select_levels)`` of one tree of ``train_forest(**kw)``:
    the levels growth routes and how many of them take the select form,
    from the (L, slots) each engine's level hands ``_route_level`` (the
    matmul router's levels are not the select's), counted on the host
    for the ``train.block.launch`` span."""
    D, B = int(kw["max_depth"]), int(kw["nbins"])
    kleaves = int(kw.get("kleaves") or 0)
    adaptive = bool(kw.get("adaptive"))
    F = int(kw.get("fine_nbins") or B)
    widths = _level_widths(kw)
    selects = 0
    for d, L in enumerate(widths):
        Bd = max(B, F >> d) if adaptive else B
        mm = bool(kw.get("mm_route")) and Bd < _MM_ROUTE_MAX_TABLE and \
            (2 * L if kleaves > 0 else L) <= _MM_ROUTE_MAX_TABLE
        selects += not mm and route_selects(L, Bd + 1)
    return D, selects


def window_levels(kw: Dict) -> int:
    """The levels of one tree of ``train_forest(**kw)`` that build the
    window histogram (the frontier engine's of ``window_level`` width),
    counted on the host for the ``train.block.launch`` span."""
    if int(kw.get("kleaves") or 0) <= 0:
        return 0
    return sum(window_level(L) for L in _level_widths(kw))


def hist_narrow_levels(kw: Dict) -> int:
    """The levels of one tree of ``train_forest(**kw)`` whose histogram
    contracts in the narrow form (``histogram.hist_form``), from the
    nodes each engine's level builds: the left children on a level
    sibling subtraction completes, a window of nodes on a window level.
    The quantized and bfloat16 tables, and a level the Pallas kernel
    takes, have one form.  Counted on the host for the
    ``train.block.launch`` span."""
    if kw.get("bf16") or (kw.get("stats_dtype") or "f32") != "f32":
        return 0
    D, B = int(kw["max_depth"]), int(kw["nbins"])
    C = int(kw["bins"].shape[1])
    kleaves = int(kw.get("kleaves") or 0)
    adaptive = bool(kw.get("adaptive"))
    F = int(kw.get("fine_nbins") or B)
    sib = bool(kw.get("sibling", True)) and not adaptive
    widths = _level_widths(kw)
    narrow = 0
    for d, L in enumerate(widths):
        if kleaves > 0 and window_level(L):
            built = min(WINDOW_LEAVES, L)
        else:
            built = L // 2 if sib and d >= 1 and L == 2 * widths[d - 1] \
                else L
            Bd = max(B, F >> d) if adaptive else B
            if kw.get("hist_pallas") and _pallas_eligible(
                    C, Bd + 1, built, N_STATS, () if adaptive else None,
                    allowed=True):
                continue
        narrow += hist_form(built * N_STATS) == "bf16x3"
    return narrow


def _pick(idx, table):
    """``table[idx]`` for every row, by a compare-select-sum over the
    table's entries (``ops/binpack.pick_bin``'s idiom): one fused loop
    over the rows, no per-row gather.  Exact: one entry matches an
    index in range (none matches one out of range: 0)."""
    keys = jnp.arange(table.shape[0], dtype=idx.dtype)
    return jnp.sum(jnp.where(keys[None, :] == idx[:, None], table[None, :],
                             0), axis=1, dtype=table.dtype)


def _pack_words(bitset):
    """(L, S) bool left sets -> (L, W) uint32: bit ``j`` of a node's set
    is bit ``j & 31`` of its word ``j >> 5``."""
    L, S = bitset.shape
    W = route_words(S)
    bits = jnp.pad(bitset, ((0, 0), (0, 32 * W - S))).reshape(L, W, 32)
    return jnp.sum(bits.astype(jnp.uint32) <<
                   jnp.arange(32, dtype=jnp.uint32), axis=2,
                   dtype=jnp.uint32)


def _route_level(bins, lf, s, do_split, Bd: int, cat_choice=None,
                 adaptive: bool = False, thr_leaf=None, F: int = -1,
                 carry=None):
    """A level's routing from the level's split record — the ONE
    statement of the rule during growth (both engines, uplift and the
    tuner's probe call it; ``_mm_route_level`` is its bitwise twin):
    returns (go_left, do_split[lf]).  A row takes its node's split
    column's bin; a bitset split looks the bin up in the node's left set
    (slot ``Bd`` = NA), an adaptive numeric split compares it with the
    node's fine-bin threshold and sends bin ``F`` (NA) by the node's
    ``na_left``.  ``lf`` is each row's node on the level, 0 for a row
    the caller masks.  ``carry`` ((L, k) int32) are further values each
    row takes from its node, returned third (the frontier engine's next
    slots of a node's two children).

    Two forms of one rule, bit for bit the same output on every row.
    Both pack each node's column, ``do_split``, ``na_left`` and
    ``cat_choice`` into one int32 record and its left set into W uint32
    words.  The SELECT form picks a row's record by compare-select-sums
    over the L nodes (and its word over the L * W words): integer
    selects with one match, each one loop over the rows.  The GATHER
    form takes a row's whole node (record, threshold, words, carry) by
    ONE row gather of an (L, 2 + W + k) table, which the chip issues one
    access a row (the gathers it replaced, one a field, read 0.05-0.15 s
    a level at 5.25M rows, 0.10-0.30 s at 11.5M: PERF.md §6).  The
    level's static shape picks the form
    (``route_selects``, under the crossover read on the chip: every
    level of a depth-8 tree at 256 or 354 slots selects; a frontier of
    2,048 nodes at 354 slots or 4,096 at 256 gathers)."""
    L, S = s["bitset"].shape
    W = route_words(S)
    if not route_selects(L, S):
        col = s["col"].astype(jnp.int32)
        flags = do_split.astype(jnp.int32)
        if adaptive:
            flags = flags + 2 * cat_choice.astype(jnp.int32) + \
                4 * s["na_left"].astype(jnp.int32)
        parts = [(col * 8 + flags)[:, None],
                 (thr_leaf if adaptive else col).astype(jnp.int32)[:, None],
                 jax.lax.bitcast_convert_type(_pack_words(s["bitset"]),
                                              jnp.int32)]
        if carry is not None:
            parts.append(carry.astype(jnp.int32))
        row = jnp.concatenate(parts, axis=1)[lf]         # one row gather
        rec = row[:, 0]
        b = pick_bin(bins, rec >> 3)
        bb = jnp.minimum(b.astype(jnp.int32), min(Bd, S - 1) if adaptive
                         else S - 1)
        words = jax.lax.bitcast_convert_type(row[:, 2:2 + W], jnp.uint32)
        word = jnp.sum(jnp.where(jnp.arange(W)[None, :] == (bb >> 5)[:, None],
                                 words, 0), axis=1, dtype=jnp.uint32)
        gset = ((word >> (bb & 31).astype(jnp.uint32)) & 1) > 0
        if adaptive:
            gthr = jnp.where(b == F, (rec & 4) > 0, b < row[:, 1])
            go_left = jnp.where((rec & 2) > 0, gset, gthr)
        else:
            go_left = gset
        out = (go_left, (rec & 1) > 0)
        return out if carry is None else out + (row[:, 2 + W:],)
    col = s["col"].astype(jnp.int32)
    flags = do_split.astype(jnp.int32)
    if adaptive:
        flags = flags + 2 * cat_choice.astype(jnp.int32) + \
            4 * s["na_left"].astype(jnp.int32)
    rec = _pick(lf, col * 8 + flags)
    b = pick_bin(bins, rec >> 3)
    # the gather clamps an index past the set's last slot to it
    bb = jnp.minimum(b.astype(jnp.int32), min(Bd, S - 1) if adaptive
                     else S - 1)
    word = _pick(lf * W + (bb >> 5), _pack_words(s["bitset"]).reshape(-1))
    gset = ((word >> (bb & 31).astype(jnp.uint32)) & 1) > 0
    if adaptive:
        gthr = jnp.where(b == F, (rec & 4) > 0, b < _pick(lf, thr_leaf))
        go_left = jnp.where((rec & 2) > 0, gset, gthr)
    else:
        go_left = gset
    out = (go_left, (rec & 1) > 0)
    if carry is None:
        return out
    return out + (jnp.stack([_pick(lf, carry[:, j])
                             for j in range(carry.shape[1])], axis=1),)


# Counter-based draws (DRF).  Tree t's row bag and each node's mtries
# columns are a pure function of integers, so a plain reference can
# restate them bit for bit and any block partition draws the same:
#
#   mix(x)   = lowbias32: x ^= x >> 16; x *= 0x7feb352d; x ^= x >> 15;
#              x *= 0x846ca68b; x ^= x >> 16          (uint32, wrapping)
#   hash(w1, ..., wn) = h_n, h_0 = mix(k0), h_1 = mix(h_0 ^ k1),
#              h_{i+1} = mix(h_i ^ w_i)               (k0, k1: the master
#              key's two words, (0, seed mod 2**32) for a builder's seed)
#   bag:     row r is in tree t's bag iff hash(1, t, r) >> 8 <
#            floor(sample_rate * 2**24)
#   mtries:  column c is allowed at the node in slot s of level d iff
#            fewer than k columns j have v_j < v_c, or v_j == v_c and
#            j < c, where v_j = hash(2, t, d, s, j) >> 8
#
# t is the absolute tree index, r the row's index in the frame, s the
# node's frontier slot (a dense level's node index).
BAG_STREAM, MTRIES_STREAM = 1, 2


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def counter_hash(seed_words, *words):
    """``hash(words)`` of the comment above, broadcast over the words."""
    h = _mix32(seed_words[1] ^ _mix32(seed_words[0]))
    for w in words:
        h = _mix32(h ^ jnp.asarray(w).astype(jnp.uint32))
    return h


def seed_words(key):
    """The master key's two uint32 words (a typed key or a raw one)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.reshape(-1)[-2:].astype(jnp.uint32)


def counter_bag(words, t, R: int, sample_rate: float):
    """(R,) bool: tree ``t``'s row bag."""
    cut = jnp.uint32(int(sample_rate * (1 << 24)))
    rows = jnp.arange(R, dtype=jnp.uint32)
    return (counter_hash(words, BAG_STREAM, t, rows) >> 8) < cut


def counter_mtries(words, t, d: int, L: int, C: int, k: int):
    """(L, C) bool: the mtries columns of each node of level ``d``."""
    slot = jnp.arange(L, dtype=jnp.uint32)[:, None]
    col = jnp.arange(C, dtype=jnp.uint32)[None, :]
    v = counter_hash(words, MTRIES_STREAM, t, d, slot, col) >> 8  # (L, C)
    before = (v[:, None, :] < v[:, :, None]) | (
        (v[:, None, :] == v[:, :, None]) &
        (jnp.arange(C)[None, None, :] < jnp.arange(C)[None, :, None]))
    return jnp.sum(before, axis=2) < k


def _node_val(wg, wh, w, newton: bool, reg_lambda: float = 0.0):
    if newton:
        return wg / jnp.maximum(wh + reg_lambda, EPS)
    # the node's mean response.  x / x is 1 exactly, which the chip's
    # division (a reciprocal, then a product) misses by up to two ulps: a
    # pure node of a random forest would vote 1 - 2**-23, and the votes'
    # out-of-bag mean would round near 1, where a wrong vote's log-loss
    # is most sensitive
    return jnp.where((wg == w) & (w > 0), 1.0, wg / jnp.maximum(w, EPS))


def sibling_subtract_enabled() -> bool:
    """The reference's DHistogram sibling-subtraction optimization
    (ScoreBuildHistogram2/DHistogram: histogram one child, derive the
    other as parent-minus-child).  Here it halves the one-hot matmul
    width at every level >= 1: only LEFT children are histogrammed and
    right = parent − left.  Exact in infinite precision (a split
    partitions its parent's rows); in f32 it reorders accumulation, so
    an escape hatch remains (H2O_TPU_SIBLING_SUBTRACT=0).  The knob is
    tri-state: ``1`` forces subtraction on, ``0`` off, ``auto``/unset
    defers to the autotuner's ``tree.sibling_subtract`` lever — whose
    REFERENCE variant is ``on`` (the pre-tuner default), so behavior is
    unchanged wherever probing is gated off (CPU tiers,
    H2O_TPU_AUTOTUNE=0)."""
    from h2o_tpu.core.autotune import resolve_flag
    return resolve_flag("tree.sibling_subtract")


@jax.named_scope("h2o.tree.hist.contract")
def _hist_level_with_sibling(bins, slot, stats, L: int, B: int, cfg,
                             parent_hist, parent_split):
    """Level-d histograms via sibling subtraction.

    ``slot`` numbers children as 2*parent+{0,1} (both engines use this
    interleaved layout on subtraction-eligible levels).  Histograms are
    built for the L/2 LEFT children only; each right child is its
    parent's histogram minus the left sibling (masked to split parents —
    unsplit parents' children have no rows and must stay zero).

    With quantized stats (ops/statpack.py) both tables are exact int32
    and the subtraction happens in INTEGER space — bitwise equal to the
    unsubtracted build (tests/test_stats_pack.py proves it), a claim
    the f32 path cannot make.  The weak ``0`` below keeps the table
    dtype either way."""
    half = L // 2
    left_slot = jnp.where((slot >= 0) & (slot % 2 == 0), slot // 2, -1)
    left = _shard_histogram(bins, left_slot, stats, half, B,
                            cfg["block_rows"], cfg["bf16"],
                            pallas=cfg.get("pallas"))
    right = jnp.where(parent_split[:, None, None, None],
                      parent_hist - left, 0)
    return jnp.stack([left, right], axis=1).reshape(L, *left.shape[1:])


def _level_mtries(key, draws, d: int, L: int, C: int, k_cols: int):
    """(key, (L, C) allowed columns) of one level: the counter rule where
    ``draws`` = (seed words, tree index) is given, else uniforms from
    ``key``."""
    if k_cols >= C:
        return key, jnp.ones((L, C), bool)
    if draws is not None:
        return key, counter_mtries(draws[0], draws[1], d, L, C, k_cols)
    key, sub = jax.random.split(key)
    r = jax.random.uniform(sub, (L, C))
    kth = jnp.sort(r, axis=1)[:, k_cols - 1][:, None]
    return key, r <= kth


def build_tree_traced(bins, stats, leaf0, key, is_cat, cfg: Dict,
                      tree_col_mask=None, mono=None, inv_scale=None,
                      draws=None):
    """Traceable single-tree build.  Returns (split_col, bitset, value,
    varimp, node_gain, node_w, thr, na_left, pos), shapes (H,), (H, B+1),
    (H,), (C,), (H,) x4, (R,) with H = 2^(D+1)-1.
    varimp accumulates each split's SE-reduction gain into its column —
    the reference's relative-importance convention (SharedTreeModel
    varimp from squared-error improvements).

    ``pos`` is EVERY row's final node in this tree (heap index), the
    node ``ops/descend.descend`` would reach over the returned arrays:
    growth routes all R rows level by level, and ``leaf0`` (0 = the tree
    is grown on this row, -1 = sampled out or inactive) only decides
    which rows the histograms see.  The caller's F update reads
    ``value[pos]`` and descends nothing.

    ``inv_scale`` non-None means ``stats`` is the quantized integer
    carrier (ops/statpack.py): tables come back exact int32 and are
    dequantized ONCE per level at the table before split finding —
    never per row; ``prev_hist`` stays integer so sibling subtraction
    is exact."""
    D = cfg["max_depth"]
    B = cfg["nbins"]
    C = bins.shape[1]
    H = 2 ** (D + 1) - 1
    k_cols = cfg["k_cols"]
    newton = cfg["newton"]
    reg_lambda = cfg.get("reg_lambda", 0.0)

    split_col = jnp.full((H,), -1, jnp.int32)
    bitset = jnp.zeros((H, B + 1), bool)
    value = jnp.zeros((H,), jnp.float32)
    varimp = jnp.zeros((C,), jnp.float32)
    node_gain = jnp.zeros((H,), jnp.float32)   # per-split SE reduction
    node_w = jnp.zeros((H,), jnp.float32)      # per-node cover (TreeSHAP)
    thr_arr = jnp.full((H,), -1, jnp.int32)    # adaptive numeric splits
    na_arr = jnp.zeros((H,), bool)
    grown = leaf0 >= 0
    pos = jnp.zeros(leaf0.shape, jnp.int32)
    use_mono = bool(cfg.get("use_mono")) and mono is not None
    # monotone value bounds per live leaf (XGBoost-style two-part scheme:
    # find_splits rejects violating splits, these clamp child values)
    lo_b = jnp.full((1,), -jnp.inf, jnp.float32)
    hi_b = jnp.full((1,), jnp.inf, jnp.float32)

    adaptive = bool(cfg.get("adaptive", False))
    F = int(cfg.get("fine_nbins") or B)
    random_mode = bool(cfg.get("hist_random", False))
    if adaptive:
        rlo, rhi = _adaptive_ranges_init(1, C, F)

    # sibling subtraction needs identical bucket edges for parent and
    # children — global-grid binning only; per-node adaptive ranges
    # change the edges every level
    sib = bool(cfg.get("sibling", True)) and not adaptive
    prev_hist = prev_do = None
    for d in range(D):                       # static unroll — exact L per level
        L = 2 ** d
        off = L - 1
        # reference halving schedule (nbins_top_level): F buckets at the
        # root, halving per level down to nbins — per-level histogram
        # cost L * Bd stays ~constant
        Bd = max(B, F >> d) if adaptive else B
        with jax.named_scope("h2o.tree.route"):
            # this level's slot of every row whose node is on it (-1: the
            # row's node stopped splitting higher up); the histograms see
            # the rows the tree is grown on
            leaf = jnp.where(pos >= off, pos - off, -1)
            slot = jnp.where(grown, leaf, -1)
        if adaptive:
            with jax.named_scope("h2o.tree.split"):
                key, sub = jax.random.split(key)
                roff = _rand_offsets(sub, L, C, rlo, rhi, random_mode)
            hist = _shard_histogram(
                bins, slot, stats, L, Bd, cfg["block_rows"], cfg["bf16"],
                fine_map=(rlo, rhi, roff, is_cat, F),
                pallas=cfg.get("pallas"))
        elif sib and d >= 1:
            hist = _hist_level_with_sibling(bins, slot, stats, L, B, cfg,
                                            prev_hist, prev_do)
        else:
            hist = _shard_histogram(bins, slot, stats, L, B,
                                    cfg["block_rows"], cfg["bf16"],
                                    pallas=cfg.get("pallas"))
        # the ONE integer->f32 crossing per level: split finding and
        # range refinement read the dequantized table, sibling
        # subtraction keeps the exact integer one
        with jax.named_scope("h2o.tree.hist.contract"):
            hist_f = hist if inv_scale is None else \
                statpack.dequant_table(hist, inv_scale)
        with jax.named_scope("h2o.tree.split"):
            key, col_allowed = _level_mtries(key, draws, d, L, C, k_cols)
            if tree_col_mask is not None:
                col_allowed = col_allowed & tree_col_mask[None, :]
            s = find_splits(hist_f, is_cat, col_allowed,
                            min_rows=cfg["min_rows"],
                            min_split_improvement=cfg["min_split_improvement"],
                            mono=mono, use_mono=use_mono, newton=newton,
                            reg_lambda=reg_lambda)
            live = s["leaf"]["w"] > 0
            do_split = s["do_split"] & live
            term = live & ~do_split
            leaf_vals = _node_val(s["leaf"]["wg"], s["leaf"]["wh"],
                                  s["leaf"]["w"], newton, reg_lambda)
            lvals = _node_val(s["left"]["wg"], s["left"]["wh"],
                              s["left"]["w"], newton, reg_lambda)
            rvals = _node_val(s["right"]["wg"], s["right"]["wh"],
                              s["right"]["w"], newton, reg_lambda)
            if use_mono:
                leaf_vals = jnp.clip(leaf_vals, lo_b, hi_b)
                lvals = jnp.clip(lvals, lo_b, hi_b)
                rvals = jnp.clip(rvals, lo_b, hi_b)
                m = mono[s["col"]].astype(jnp.float32)         # (L,)
                mid = 0.5 * (lvals + rvals)
                l_hi = jnp.where(m > 0, jnp.minimum(hi_b, mid), hi_b)
                r_lo = jnp.where(m > 0, jnp.maximum(lo_b, mid), lo_b)
                l_lo = jnp.where(m < 0, jnp.maximum(lo_b, mid), lo_b)
                r_hi = jnp.where(m < 0, jnp.minimum(hi_b, mid), hi_b)
                lo_b = jnp.stack([l_lo, r_lo], axis=1).reshape(2 * L)
                hi_b = jnp.stack([l_hi, r_hi], axis=1).reshape(2 * L)

            varimp = varimp.at[s["col"]].add(
                jnp.where(do_split, jnp.maximum(s["gain"], 0.0), 0.0))
            # record splits + terminal values at this level's heap slots
            node_gain = jax.lax.dynamic_update_slice(
                node_gain,
                jnp.where(do_split, jnp.maximum(s["gain"], 0.0), 0.0), (off,))
            split_col = jax.lax.dynamic_update_slice(
                split_col, jnp.where(do_split, s["col"], -1), (off,))
            cat_choice = is_cat[s["col"]]
            if adaptive:
                thr_leaf = _numeric_thr(s, rlo, rhi, roff, Bd)
                num_split = do_split & ~cat_choice
                thr_arr = jax.lax.dynamic_update_slice(
                    thr_arr, jnp.where(num_split, thr_leaf, -1), (off,))
                na_arr = jax.lax.dynamic_update_slice(
                    na_arr, num_split & s["na_left"], (off,))
                # numeric nodes carry the fine threshold; their BUCKET
                # bitsets are per-node artifacts and must not be stored.
                # Cat splits: codes live in the first B buckets whatever Bd
                # is; keep membership [:B] + the NA bit
                bset_store = jnp.concatenate(
                    [s["bitset"][:, :B], s["bitset"][:, Bd: Bd + 1]], axis=1)
                bset_w = bset_store & (do_split & cat_choice)[:, None]
            else:
                thr_leaf = None
                bset_w = s["bitset"] & do_split[:, None]
            bitset = jax.lax.dynamic_update_slice(bitset, bset_w, (off, 0))
            value = jax.lax.dynamic_update_slice(
                value, jnp.where(term, leaf_vals, 0.0), (off,))
            node_w = jax.lax.dynamic_update_slice(
                node_w, jnp.where(live, s["leaf"]["w"], 0.0), (off,))
            # pre-write child values (interleaved left/right) at the next level
            child_vals = jnp.stack([lvals, rvals], axis=1).reshape(2 * L)
            child_mask = jnp.repeat(do_split, 2)
            coff = 2 * L - 1
            cur = jax.lax.dynamic_slice(value, (coff,), (2 * L,))
            value = jax.lax.dynamic_update_slice(
                value, jnp.where(child_mask, child_vals, cur), (coff,))
            # pre-write child covers too (the depth-D level never runs the
            # loop body, so its weights only exist via this write)
            child_ws = jnp.stack([s["left"]["w"], s["right"]["w"]],
                                 axis=1).reshape(2 * L)
            cur_w = jax.lax.dynamic_slice(node_w, (coff,), (2 * L,))
            node_w = jax.lax.dynamic_update_slice(
                node_w, jnp.where(child_mask, child_ws, cur_w), (coff,))

        with jax.named_scope("h2o.tree.route"):
            # route EVERY row standing on this level, grown-on or not, the
            # last level included: where its node splits it moves to the
            # child's heap slot, else it stays where it is for good
            active = leaf >= 0
            lf = jnp.maximum(leaf, 0)
            if cfg.get("mm_route") and L <= _MM_ROUTE_MAX_TABLE and \
                    (Bd if adaptive else B) < _MM_ROUTE_MAX_TABLE:
                go_left, do_lf = _mm_route_level(
                    bins, lf, s, do_split, L, Bd if adaptive else B,
                    cat_choice, adaptive, thr_leaf, F)
            else:
                go_left, do_lf = _route_level(
                    bins, lf, s, do_split, Bd, cat_choice, adaptive,
                    thr_leaf, F)
            child = 2 * lf + jnp.where(go_left, 0, 1)
            pos = jnp.where(active & do_lf, 2 * L - 1 + child, pos)
        with jax.named_scope("h2o.tree.split"):
            if adaptive and d + 1 < D:
                new_lo, new_hi = _refine_ranges(hist_f, rlo, rhi, roff, Bd)
                rlo, rhi = _child_ranges(new_lo, new_hi, s, thr_leaf,
                                         is_cat, do_split)
        prev_hist, prev_do = hist, do_split
    return (split_col, bitset, value, varimp, node_gain, node_w,
            thr_arr, na_arr, pos)


def build_tree_frontier(bins, stats, slot0, key, is_cat, cfg: Dict,
                        tree_col_mask=None, mono=None, inv_scale=None,
                        draws=None, packed=None):
    """Traceable single-tree build with a CAPPED live frontier.

    Like ``build_tree_traced`` but the per-level leaf set is bounded by
    cfg["max_live_leaves"]: when a level's split children outnumber the
    cap, the children with the largest residual impurity (wgg − wg²/w,
    the upper bound on any further split's SE reduction) stay live and
    the rest finalize as leaves: ``top_k``, a tie to the lower child
    index, and the kept children take their frontier slots in child
    order.  Below the cap the two builders produce identical trees (the
    selection is the identity there).

    Nodes live in a pool of ``pool_size(D, cap)`` slots with an explicit
    left-``child`` pointer (right = left+1) — the sparse-CompressedTree
    analog (reference hex/tree/DTree.java:891-935); a child the cap cut
    to a leaf holds ``child`` -2 (every reader takes a negative pointer
    for a leaf).  Levels of ``histogram.window_level`` width take the
    window form of the histogram (``histogram_window_traced``: cost
    bounded by a node window, not by the level's width; ``packed``: the
    bins as ``binpack.pack_words`` packs them, which its blocks gather).
    Returns
    (split_col (N,), bitset (N, B+1), value (N,), child (N,),
    varimp (C,), frontier (3,), node_gain (N,), node_w (N,), thr (N,),
    na_left (N,), pos (R,)); ``frontier`` counts the children the cap
    cut, the children of split nodes above the last level, and the
    levels whose children outnumbered the cap.

    ``pos`` is every row's final node as a pool id, as in
    ``build_tree_traced``: all R rows are routed (``slot0`` -1 only
    keeps a row out of the histograms), the last level included; a row
    whose child fell off the frontier ends AT that child, whose value is
    pre-written.
    """
    D = cfg["max_depth"]
    B = cfg["nbins"]
    C = bins.shape[1]
    cap = cfg["max_live_leaves"]
    k_cols = cfg["k_cols"]
    newton = cfg["newton"]
    reg_lambda = cfg.get("reg_lambda", 0.0)
    widths = frontier_plan(D, cap)
    N = 1 + 2 * sum(widths)
    adaptive = bool(cfg.get("adaptive", False))
    F = int(cfg.get("fine_nbins") or B)
    random_mode = bool(cfg.get("hist_random", False))
    use_mono = bool(cfg.get("use_mono")) and mono is not None
    sib = bool(cfg.get("sibling", True)) and not adaptive
    d0 = frontier_loop_start(D, cap, B, F, adaptive)

    # pool arrays + one trash slot at index N (empty frontier slots write
    # there; duplicates all carry inert -1/0 payloads), and room past it
    # for the looped levels' child runs, which are written at the cap's
    # width (their tails hold the same inert payloads)
    P = N + 1 + (2 * cap if d0 < D else 0)
    st = dict(
        split_col=jnp.full((P,), -1, jnp.int32),
        bitset=jnp.zeros((P, B + 1), bool),
        value=jnp.zeros((P,), jnp.float32),
        child=jnp.full((P,), -1, jnp.int32),
        node_gain=jnp.zeros((P,), jnp.float32),
        node_w=jnp.zeros((P,), jnp.float32),     # per-node cover (TreeSHAP)
        thr_pool=jnp.full((P,), -1, jnp.int32),  # adaptive numeric thr
        na_pool=jnp.zeros((P,), bool),
        varimp=jnp.zeros((C,), jnp.float32),
        frontier=jnp.zeros((1,), jnp.int32),     # pool ids of live leaves
        slot=jnp.zeros(slot0.shape, jnp.int32),  # per-row frontier slot
        pos=jnp.zeros(slot0.shape, jnp.int32),   # per-row pool id
        key=key,
        cut=jnp.int32(0), split_children=jnp.int32(0),
        capped=jnp.int32(0))
    if use_mono:
        st["lo_b"] = jnp.full((1,), -jnp.inf, jnp.float32)
        st["hi_b"] = jnp.full((1,), jnp.inf, jnp.float32)
    if adaptive:
        st["rlo"], st["rhi"] = _adaptive_ranges_init(1, C, F)
    grown = slot0 >= 0                             # rows the histograms see

    def level(st, d, L, Ln, Bd, base, last, capped_lvl, sib_prev=None):
        """One level of ``L`` frontier slots whose children start at pool
        id ``base``; ``Ln`` slots on the next level.  ``d``, ``base``,
        ``last`` (the tree's last level) and ``capped_lvl`` (its split
        children may outnumber the next level) are Python values on an
        unrolled level and traced ones in the loop."""
        st = dict(st)
        frontier, slot = st["frontier"], st["slot"]
        with jax.named_scope("h2o.tree.route"):
            hslot = jnp.where(grown, slot, -1)
        roff = None
        if adaptive:
            with jax.named_scope("h2o.tree.split"):
                st["key"], sub = jax.random.split(st["key"])
                roff = _rand_offsets(sub, L, C, st["rlo"], st["rhi"],
                                     random_mode)
        fine_map = (st["rlo"], st["rhi"], roff, is_cat, F) if adaptive \
            else None
        if window_level(L):
            hist = histogram_window_traced(bins, hslot, stats, L, Bd,
                                           cfg["bf16"], fine_map=fine_map,
                                           words=packed)
        elif adaptive:
            hist = _shard_histogram(
                bins, hslot, stats, L, Bd, cfg["block_rows"], cfg["bf16"],
                fine_map=fine_map, pallas=cfg.get("pallas"))
        elif sib_prev is not None:
            # uncapped transition: children sit at 2*parent+{0,1} in
            # parent order (identity selection), so the dense sibling
            # subtraction applies verbatim; capped levels (top_k
            # reshuffles slots) fall back to the full histogram
            hist = _hist_level_with_sibling(bins, hslot, stats, L, B, cfg,
                                            *sib_prev)
        else:
            hist = _shard_histogram(bins, hslot, stats, L, B,
                                    cfg["block_rows"], cfg["bf16"],
                                    pallas=cfg.get("pallas"))
        # dequantize once per level at the table (see build_tree_traced)
        with jax.named_scope("h2o.tree.hist.contract"):
            hist_f = hist if inv_scale is None else \
                statpack.dequant_table(hist, inv_scale)
        with jax.named_scope("h2o.tree.split"):
            st["key"], col_allowed = _level_mtries(st["key"], draws, d, L, C,
                                                   k_cols)
            if tree_col_mask is not None:
                col_allowed = col_allowed & tree_col_mask[None, :]
            s = find_splits(hist_f, is_cat, col_allowed,
                            min_rows=cfg["min_rows"],
                            min_split_improvement=cfg["min_split_improvement"],
                            mono=mono, use_mono=use_mono, newton=newton,
                            reg_lambda=reg_lambda,
                            natural=bool(cfg.get("numeric_only")))
            live = s["leaf"]["w"] > 0
            do_split = s["do_split"] & live
            term = live & ~do_split
            leaf_vals = _node_val(s["leaf"]["wg"], s["leaf"]["wh"],
                                  s["leaf"]["w"], newton, reg_lambda)
            lvals = _node_val(s["left"]["wg"], s["left"]["wh"],
                              s["left"]["w"], newton, reg_lambda)
            rvals = _node_val(s["right"]["wg"], s["right"]["wh"],
                              s["right"]["w"], newton, reg_lambda)
            if use_mono:
                lo_b, hi_b = st["lo_b"], st["hi_b"]
                leaf_vals = jnp.clip(leaf_vals, lo_b, hi_b)
                lvals = jnp.clip(lvals, lo_b, hi_b)
                rvals = jnp.clip(rvals, lo_b, hi_b)
                m = mono[s["col"]].astype(jnp.float32)
                mid = 0.5 * (lvals + rvals)
                l_hi = jnp.where(m > 0, jnp.minimum(hi_b, mid), hi_b)
                r_lo = jnp.where(m > 0, jnp.maximum(lo_b, mid), lo_b)
                l_lo = jnp.where(m < 0, jnp.maximum(lo_b, mid), lo_b)
                r_hi = jnp.where(m < 0, jnp.minimum(hi_b, mid), hi_b)
                lo_c = jnp.stack([l_lo, r_lo], axis=1).reshape(2 * L)
                hi_c = jnp.stack([l_hi, r_hi], axis=1).reshape(2 * L)

            st["varimp"] = st["varimp"].at[s["col"]].add(
                jnp.where(do_split, jnp.maximum(s["gain"], 0.0), 0.0))
            # write this level's frontier nodes into the pool (scatter at
            # traced pool ids; trash-slot writes are inert)
            gain_pos = jnp.where(do_split, jnp.maximum(s["gain"], 0.0), 0.0)
            child_ptr = base + 2 * jnp.arange(L, dtype=jnp.int32)
            st["split_col"] = st["split_col"].at[frontier].set(
                jnp.where(do_split, s["col"], -1))
            cat_choice = is_cat[s["col"]]
            if adaptive:
                thr_leaf = _numeric_thr(s, st["rlo"], st["rhi"], roff, Bd)
                num_split = do_split & ~cat_choice
                st["thr_pool"] = st["thr_pool"].at[frontier].set(
                    jnp.where(num_split, thr_leaf, -1))
                st["na_pool"] = st["na_pool"].at[frontier].set(
                    num_split & s["na_left"])
                bset_store = jnp.concatenate(
                    [s["bitset"][:, :B], s["bitset"][:, Bd: Bd + 1]], axis=1)
                bset_w = bset_store & (do_split & cat_choice)[:, None]
            else:
                thr_leaf = None
                bset_w = s["bitset"] & do_split[:, None]
            st["bitset"] = st["bitset"].at[frontier].set(bset_w)
            st["value"] = st["value"].at[frontier].set(
                jnp.where(term, leaf_vals, 0.0))
            st["child"] = st["child"].at[frontier].set(
                jnp.where(do_split, child_ptr, -1))
            st["node_gain"] = st["node_gain"].at[frontier].set(gain_pos)
            st["node_w"] = st["node_w"].at[frontier].set(
                jnp.where(live, s["leaf"]["w"], 0.0))
            # pre-write child values at their (fresh, contiguous) pool slots
            cvals = jnp.stack([lvals, rvals], axis=1).reshape(2 * L)
            cmask = jnp.repeat(do_split, 2)
            st["value"] = jax.lax.dynamic_update_slice(
                st["value"], jnp.where(cmask, cvals, 0.0), (base,))
            cw = jnp.stack([s["left"]["w"], s["right"]["w"]],
                           axis=1).reshape(2 * L)
            st["node_w"] = jax.lax.dynamic_update_slice(
                st["node_w"], jnp.where(cmask, cw, 0.0), (base,))

        if last is not True:
            with jax.named_scope("h2o.tree.split"):
                # best-first frontier selection: keep the children with the
                # most residual impurity; the rest are finished leaves
                se_l, se_r = (node_sq_err(s[k]["w"], s[k]["wg"], s[k]["wgg"])
                              for k in ("left", "right"))
                cse = jnp.stack([se_l, se_r], axis=1).reshape(2 * L)
                ckey = jnp.where(cmask, jnp.maximum(cse, 0.0), -jnp.inf)
                ident = jnp.arange(Ln, dtype=jnp.int32)   # identity: dense
                if capped_lvl is False:
                    sel = ident
                else:
                    # the kept children take the first slots in child
                    # order (top_k fills past them with -inf candidates)
                    kv, top = jax.lax.top_k(ckey, Ln)
                    top = top.astype(jnp.int32)
                    top = jnp.sort(jnp.where(kv > -jnp.inf, top,
                                             top + 2 * L)) % (2 * L)
                    sel = top if capped_lvl is True else \
                        jnp.where(capped_lvl, top, ident)
                sel_valid = jnp.take(ckey, sel) > -jnp.inf
                st["frontier"] = jnp.where(sel_valid, base + sel, N)
                inv = jnp.full((2 * L,), -1, jnp.int32).at[sel].set(
                    jnp.where(sel_valid,
                              jnp.arange(Ln, dtype=jnp.int32), -1))
                on = jnp.logical_not(last)
                if capped_lvl is not False:
                    # children the cap cut to leaves: child -2, counted
                    lost = cmask & (inv < 0) & capped_lvl & on
                    st["child"] = jax.lax.dynamic_update_slice(
                        st["child"], jnp.where(lost, -2, -1), (base,))
                    st["cut"] = st["cut"] + jnp.sum(lost, dtype=jnp.int32)
                    st["capped"] = st["capped"] + \
                        jnp.any(lost).astype(jnp.int32)
                st["split_children"] = st["split_children"] + jnp.where(
                    on, jnp.sum(cmask, dtype=jnp.int32), 0)
        with jax.named_scope("h2o.tree.route"):
            # route EVERY row on the frontier, grown-on or not, the last
            # level included: a split parent's rows follow the split to a
            # child's pool slot; a row whose child fell off the frontier
            # ends there (slot -1)
            active = slot >= 0
            sl = jnp.maximum(slot, 0)
            mm = bool(cfg.get("mm_route")) and \
                2 * L <= _MM_ROUTE_MAX_TABLE and \
                (Bd if adaptive else B) < _MM_ROUTE_MAX_TABLE
            if mm:
                go_left, do_sl = _mm_route_level(
                    bins, sl, s, do_split, L, Bd if adaptive else B,
                    cat_choice, adaptive, thr_leaf, F)
            elif last is True:
                go_left, do_sl = _route_level(
                    bins, sl, s, do_split, Bd, cat_choice, adaptive,
                    thr_leaf, F)
            else:
                # each row takes its node's two children's next slots
                # with the node's record: no per-row lookup of its child
                go_left, do_sl, nxt = _route_level(
                    bins, sl, s, do_split, Bd, cat_choice, adaptive,
                    thr_leaf, F, carry=inv.reshape(L, 2))
            cand = 2 * sl + jnp.where(go_left, 0, 1)
            moved = active & do_sl
            st["pos"] = jnp.where(moved, base + cand, st["pos"])
            if last is not True:
                if mm:
                    candhot = cand[:, None] == jnp.arange(2 * L)[None, :]
                    inv_c = _mm_pick(candhot, inv.astype(jnp.float32)[:, None]
                                     )[:, 0].astype(jnp.int32)
                else:
                    inv_c = jnp.where(go_left, nxt[:, 0], nxt[:, 1])
                st["slot"] = jnp.where(moved, inv_c, -1)
        if last is not True:
            with jax.named_scope("h2o.tree.split"):
                if use_mono:
                    st["lo_b"] = jnp.take(lo_c, sel)
                    st["hi_b"] = jnp.take(hi_c, sel)
                if adaptive:
                    new_lo, new_hi = _refine_ranges(hist_f, st["rlo"],
                                                    st["rhi"], roff, Bd)
                    clo, chi = _child_ranges(new_lo, new_hi, s, thr_leaf,
                                             is_cat, do_split)
                    st["rlo"] = jnp.take(clo, sel, axis=0)
                    st["rhi"] = jnp.take(chi, sel, axis=0)
        return st, (hist, do_split)

    base, prev = 1, None                           # next free pool slot
    for d in range(d0):                            # static unroll
        L = widths[d]
        Ln = widths[d + 1] if d + 1 < D else 0
        sib_prev = prev if (sib and d >= 1 and L == 2 * widths[d - 1]) \
            else None
        st, prev = level(st, d, L, Ln, max(B, F >> d) if adaptive else B,
                         base, d + 1 == D, 2 * L > Ln, sib_prev)
        base += 2 * L
    if d0 < D:
        # the levels from d0 on share one shape, the cap's: ONE compiled
        # body in a loop, each level's arrays padded to the cap (an empty
        # slot holds no row and splits nothing)
        def pad(a, fill):
            return jnp.concatenate([a, jnp.full((cap - a.shape[0],) +
                                                a.shape[1:], fill, a.dtype)])
        st["frontier"] = pad(st["frontier"], N)
        if use_mono:
            st["lo_b"], st["hi_b"] = pad(st["lo_b"], -jnp.inf), \
                pad(st["hi_b"], jnp.inf)
        if adaptive:
            st["rlo"], st["rhi"] = pad(st["rlo"], 0), pad(st["rhi"], F - 1)
        st["base"], st["width"] = jnp.int32(base), jnp.int32(widths[d0])

        def body(d, st):
            b, w = st["base"], st["width"]
            st, _ = level(st, d, cap, cap, B, b, d == D - 1, 2 * w > cap)
            st["base"], st["width"] = b + 2 * w, jnp.minimum(2 * w, cap)
            return st

        st = jax.lax.fori_loop(d0, D, body, st)
    return (st["split_col"][:N], st["bitset"][:N], st["value"][:N],
            st["child"][:N], st["varimp"],
            jnp.stack([st["cut"], st["split_children"], st["capped"]]),
            st["node_gain"][:N], st["node_w"][:N], st["thr_pool"][:N],
            st["na_pool"][:N], st["pos"])


def frontier_loop_start(depth: int, cap: int, nbins: int, fine: int,
                        adaptive: bool) -> int:
    """The first level the sparse-frontier engine runs in its loop at the
    cap's shape: the first whose width takes the window form of the
    histogram and whose bucket count is ``nbins`` (an adaptive tree's
    halving schedule is over); ``depth`` where there is none."""
    for d, L in enumerate(frontier_plan(depth, cap)):
        if window_level(L) and (not adaptive or (fine >> d) <= nbins):
            return d
    return depth


def _hist_bucket(args, kwargs):
    """Shape bucket for the hist.kernel lever from a train_forest call:
    (pow2 rows, pow2 cols, nbins, live leaves).  None (→ the lever's
    default bucket) when the bins matrix isn't identifiable."""
    bins = kwargs.get("bins", args[0] if args else None)
    if bins is None or getattr(bins, "ndim", 0) != 2:
        return None
    from h2o_tpu.core.autotune import hist_bucket
    R, C = bins.shape
    L = min(1 << int(kwargs.get("max_depth", 5)), max_live_leaves())
    return hist_bucket(int(R), int(C), int(kwargs.get("nbins", 64)), L)


def _stats_bucket(args, kwargs):
    """Shape bucket for the tree.stats_dtype lever from a train_forest
    call: (pow2 rows, pow2 cols, nbins).  None (→ the lever's default
    bucket) when the bins matrix isn't identifiable."""
    bins = kwargs.get("bins", args[0] if args else None)
    if bins is None or getattr(bins, "ndim", 0) != 2:
        return None
    R, C = bins.shape
    return statpack.stats_bucket(int(R), int(C),
                                 int(kwargs.get("nbins", 64)))


def resolve_train_levers(train_kwargs: dict) -> dict:
    """Resolve the tunable-lever flags ONCE (driver entry) so a
    multi-block training run — and its recovery/speculative re-
    dispatches — uses one stable, already-probed decision per lever
    instead of re-resolving at every block boundary.  Flags the caller
    pinned explicitly are left alone."""
    if train_kwargs.get("sibling") is None:
        train_kwargs["sibling"] = sibling_subtract_enabled()
    if train_kwargs.get("hist_pallas") is None:
        from h2o_tpu.ops.histogram import pallas_env_enabled
        train_kwargs["hist_pallas"] = pallas_env_enabled(
            _hist_bucket((), train_kwargs))
    if train_kwargs.get("mm_route") is None:
        train_kwargs["mm_route"] = matmul_route_enabled()
    if train_kwargs.get("stats_dtype") is None:
        train_kwargs["stats_dtype"] = statpack.resolve_stats_dtype(
            _stats_bucket((), train_kwargs))
    return train_kwargs


class TrainedForest(NamedTuple):
    split_col: jax.Array   # (T, K, N)
    bitset: jax.Array      # (T, K, N, B+1)
    value: jax.Array       # (T, K, N)
    f_final: jax.Array     # (R, K) link-scale training predictions
    varimp: jax.Array      # (C,) summed split-gain importance
    node_gain: jax.Array   # (T, K, N) per-split gain (FeatureInteraction)
    node_w: jax.Array      # (T, K, N) per-node training cover (TreeSHAP)
    thr_bin: jax.Array     # (T, K, N) adaptive numeric thr (-1 = bitset)
    na_left: jax.Array     # (T, K, N) NA direction for thr splits
    child: object = None   # (T, K, N) left-child pool ptrs; None = dense
    # (R, K + 1) mode "drf": each row's out-of-bag vote sums and the
    # number of trees it was out of the bag of, carried like F
    oob: object = None
    # (T, K, 3) sparse-frontier engine: children the cap cut, children
    # of split nodes above the last level, levels the cap cut at
    frontier: object = None


def train_forest(*args, sibling: Optional[bool] = None,
                 hist_pallas: Optional[bool] = None,
                 donate: Optional[bool] = None, **kwargs):
    """Public entry: resolves the sibling-subtraction and Pallas-histogram
    flags from the env OUTSIDE the trace (they are static jit args — part
    of the executable cache key — so toggling H2O_TPU_SIBLING_SUBTRACT /
    H2O_TPU_HIST_PALLAS between trainings takes effect instead of hitting
    a stale cached program).

    ``donate`` selects the F0-donating executable (None = the store's
    backend donation policy): the forest accumulator F is the hot carry
    of the whole training loop, and donating it lets XLA update it in
    place across blocks instead of allocating a fresh (R, K) HBM buffer
    per block.  Callers that still need the passed-in F0 AFTER the call
    (speculative async blocks under early stopping, recovery checkpoints
    of the pre-block F) must pass donate=False.

    Both executables (donating / non-donating) live in the unified
    executable store (core/exec_store.py) over the ONE traced body —
    donation must never silently change which program a
    recompile-sensitive flag flip hits.  Shape polymorphism stays at the
    jit level (the static-argname signature), so persistence for this
    entry rides the XLA persistent compile cache rather than
    executable serialization.

    A Mosaic/Pallas kernel-compile failure with the autotuned/forced
    fused histogram enabled degrades to the portable XLA histogram path
    (a recorded OOM-ladder event) instead of taking training down with
    no fallback."""
    if sibling is None:
        sibling = sibling_subtract_enabled()
    if hist_pallas is None:
        from h2o_tpu.ops.histogram import pallas_env_enabled
        hist_pallas = pallas_env_enabled(_hist_bucket(args, kwargs))
    if "mm_route" not in kwargs or kwargs["mm_route"] is None:
        kwargs["mm_route"] = matmul_route_enabled()
    if "stats_dtype" not in kwargs or kwargs["stats_dtype"] is None:
        kwargs["stats_dtype"] = statpack.resolve_stats_dtype(
            _stats_bucket(args, kwargs))
    from h2o_tpu.core.diag import DispatchStats
    from h2o_tpu.core.exec_store import exec_store
    from h2o_tpu.core.oom import kernel_fallback
    DispatchStats.note_dispatch("tree_block")
    bins_arg = kwargs.get("bins", args[0] if args else None)
    if bins_arg is not None and getattr(bins_arg, "ndim", 0) == 2:
        from h2o_tpu.ops.histogram import N_STATS
        statpack.note_train(kwargs["stats_dtype"],
                            int(bins_arg.shape[0]), N_STATS,
                            int(kwargs.get("ntrees", 1)))

    # the traced body bakes cloud().mesh into its shard_map (the
    # histogram collective), and jit's TRACE cache keys on shapes only —
    # so the store entry must key on the mesh, or a Cloud.reform to a
    # different shape would replay a jaxpr built for the old device set
    from h2o_tpu.core.cloud import cloud
    mesh_fp = (cloud().mesh.devices.shape,
               tuple(d.id for d in cloud().mesh.devices.ravel()))

    def run(pallas: bool):
        fn = exec_store().get_or_build(
            "tree_block", ("train_forest", mesh_fp),
            lambda: _train_forest_impl,
            jit_kwargs={"static_argnames": _TF_STATIC},
            donate_argnames=("F0",), donate=donate)
        return fn(*args, sibling=sibling, hist_pallas=pallas,
                  mesh_fp=mesh_fp, **kwargs)

    return kernel_fallback("tree.block", run, pallas=hist_pallas)


def program_signature(kwargs: Dict) -> tuple:
    """A hashable stand-in for the key jit gives a block program of
    ``train_forest(**kwargs)``: an array by its shape and dtype, any
    other argument as it is (its ``repr`` where it cannot be hashed)."""
    out = []
    for k, v in sorted(kwargs.items()):
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            v = ("array", tuple(v.shape), str(v.dtype))
        else:
            try:
                hash(v)
            except TypeError:
                v = repr(v)
        out.append((k, v))
    return tuple(out)


_TF_STATIC = ("dist_name", "K", "ntrees", "max_depth", "nbins",
              "k_cols", "newton", "sample_rate", "learn_rate",
              "learn_rate_annealing", "min_rows",
              "min_split_improvement", "block_rows", "bf16",
              "mode", "tweedie_power", "quantile_alpha",
              "huber_alpha", "reg_lambda",
              "col_sample_rate_per_tree", "use_mono",
              "kleaves", "custom_dist", "sibling",
              "adaptive", "fine_nbins", "hist_random",
              "hist_pallas", "mm_route", "stats_dtype", "mesh_fp",
              "numeric_only")


def _train_forest_impl(bins, yv, w, active, F0, is_cat, key, *,
                       dist_name: str,
                 K: int, ntrees: int, max_depth: int, nbins: int,
                 k_cols: int, newton: bool, sample_rate: float,
                 learn_rate: float, learn_rate_annealing: float,
                 min_rows: float, min_split_improvement: float,
                 block_rows: int = 8192, bf16: bool = False,
                 mode: str = "gbm", tweedie_power: float = 1.5,
                 quantile_alpha: float = 0.5,
                 huber_alpha: float = 0.9, reg_lambda: float = 0.0,
                 col_sample_rate_per_tree: float = 1.0,
                 mono=None, use_mono: bool = False,
                 t0: int = 0, kleaves: int = 0,
                 custom_dist=None,
                 sibling: bool = True,
                 adaptive: bool = False, fine_nbins: int = 0,
                 hist_random: bool = False,
                 hist_pallas: bool = False,
                 mm_route: bool = False,
                 stats_dtype: str = "f32",
                 mesh_fp=None, oob0=None,
                 numeric_only: bool = False) -> TrainedForest:
    """The WHOLE forest training loop as one XLA program.

    ``mesh_fp`` is a STATIC fingerprint of the cloud mesh, unused in the
    body: the histogram collective traces ``cloud().mesh`` into its
    shard_map, and jax's trace cache is shared across jit wrappers of
    the same function and keyed on avals (shapes, not device sets) — so
    after a Cloud.reform/boot to a new mesh shape, an unchanged
    signature would replay a jaxpr built for the OLD device set.

    mode="gbm": boosting — stats from distribution gradients at current F,
    f updated after each iteration, leaf values scaled by learn_rate.
    mode="drf": bagging — stats fixed on the response, no f update (F output
    accumulates raw votes; caller divides by ntrees); bags and mtries
    columns by the counter rule (``counter_bag``, ``counter_mtries``),
    and with ``oob0`` ((R, K + 1), like F0) each row's out-of-bag vote
    sums and tree count carried beside F (``TrainedForest.oob``).
    kleaves=0: dense heap engine; >0: sparse-frontier engine with that
    live-leaf cap (module docstring).  ``sibling`` (static; resolved by
    the train_forest wrapper) enables histogram sibling subtraction.
    ``stats_dtype`` (static; resolved outside the trace like the other
    levers) selects the per-tree stats carrier: "f32" is the bitwise
    pre-lever reference (no quantization noise is even DRAWN, so the
    program is identical), "int16"/"int8" quantize each tree's stats
    with stochastic rounding (ops/statpack.py) and run the whole level
    loop on exact int32 tables.  ``numeric_only`` (static; the driver
    sets it from the host's ``is_cat`` for the sparse-frontier engine
    alone): no column is categorical, so the frontier's split search
    sorts no bins (``find_splits(natural=True)``).
    """
    cfg = dict(max_depth=max_depth, nbins=nbins, k_cols=k_cols,
               newton=newton, min_rows=min_rows,
               min_split_improvement=min_split_improvement,
               block_rows=block_rows, bf16=bf16, reg_lambda=reg_lambda,
               use_mono=use_mono, max_live_leaves=kleaves,
               sibling=sibling, adaptive=adaptive,
               fine_nbins=fine_nbins, hist_random=hist_random,
               pallas=hist_pallas, mm_route=mm_route,
               numeric_only=numeric_only)
    R = bins.shape[0]

    def stats_for(kcls, F):
        wa = jnp.where(active, w, 0.0)
        if mode == "drf":
            if K > 1:
                g = (yv == kcls).astype(jnp.float32)
            else:
                g = jnp.nan_to_num(yv)
            return jnp.stack([wa, wa * g, wa * g * g, wa], axis=1)
        if dist_name == "multinomial":
            p = jax.nn.softmax(F, axis=1)[:, kcls]
            yk = (yv == kcls).astype(jnp.float32)
            g = yk - p
            h = jnp.maximum(p * (1.0 - p), EPS)
        elif dist_name == "custom":
            # user CDistributionFunc (core/udf.py CustomDistribution):
            # traced through jit like any engine distribution
            g = jnp.nan_to_num(custom_dist.gradient(yv, F[:, 0]))
            h = jnp.nan_to_num(custom_dist.hessian(yv, F[:, 0]))
        else:
            dist = get_distribution(dist_name, tweedie_power=tweedie_power,
                                    quantile_alpha=quantile_alpha,
                                    huber_alpha=huber_alpha)
            g = jnp.nan_to_num(dist.gradient(yv, F[:, 0]))
            h = jnp.nan_to_num(dist.hessian(yv, F[:, 0]))
        return jnp.stack([wa, wa * g, wa * g * g, wa * h], axis=1)

    C = bins.shape[1]
    # static quantization ceiling: R is the padded row count, a Python
    # int at trace time, so the int32-overflow bound is baked in
    qmax = (statpack.stats_qmax(R, stats_dtype)
            if stats_dtype != "f32" else 0)

    # DRF draws its bags and mtries columns by the counter rule, and
    # carries each row's out-of-bag votes where the caller hands ``oob0``
    counter = mode == "drf"
    words = seed_words(key) if counter else None

    # the window levels gather their rows' bins as packed words: packed
    # once here, outside the tree loop (the bins do not change)
    packed = None
    if window_levels(dict(cfg, kleaves=kleaves)):
        with jax.named_scope("h2o.tree.partition"):
            packed = pack_words(bins, fine_nbins or nbins)

    def tree_step(carry, xs):
        F, oob = (carry, None) if oob0 is None else carry
        t_idx, key_t = xs
        draws = (words, t_idx.astype(jnp.uint32)) if counter else None
        # the tree's inputs: its keys, row/column samples, and (below)
        # the per-row statistics
        with jax.named_scope("h2o.tree.stats"):
            ks, kc, kcol = jax.random.split(key_t, 3)
            if col_sample_rate_per_tree < 1.0:
                # per-TREE column subsample (colsample_bytree); keep >= 1
                rc = jax.random.uniform(kcol, (C,))
                kth = jnp.sort(rc)[max(
                    1, int(round(col_sample_rate_per_tree * C))) - 1]
                tree_cols = rc <= kth
            else:
                tree_cols = None
            if sample_rate >= 1.0:
                samp = jnp.ones((R,), bool)
            elif counter:
                samp = counter_bag(words, draws[1], R, sample_rate)
            else:
                samp = jnp.where(
                    jax.random.uniform(ks, (R,)) < sample_rate, True, False)
            leaf0 = jnp.where(samp & active, 0, -1).astype(jnp.int32)
        scale = learn_rate * (learn_rate_annealing ** t_idx) \
            if mode == "gbm" else 1.0
        if mode == "gbm" and dist_name == "multinomial":
            scale = scale * (K - 1) / K
        scs, bss, vls, chs, frs, preds, vis, gns, nws, ths, nas = \
            [], [], [], [], [], [], [], [], [], [], []
        for kcls in range(K):                    # static unroll over classes
            with jax.named_scope("h2o.tree.stats"):
                kc, kk = jax.random.split(kc)
                stats = stats_for(kcls, F)
                if stats_dtype != "f32":
                    # quantize ONCE per (tree, class) against the per-class
                    # key kk — which descends from the absolute-tree-index
                    # fold_in below, so any block partition and any mesh
                    # shape draws the identical rounding noise
                    stats, inv_sc = statpack.quantize_stats(
                        stats, kk, stats_dtype, qmax)
                else:
                    inv_sc = None
            if kleaves > 0:
                sc, bs, vl, ch, vi, fr, gn, nw, th, na, pos = \
                    build_tree_frontier(bins, stats, leaf0, kk, is_cat, cfg,
                                        tree_cols, mono=mono,
                                        inv_scale=inv_sc, draws=draws,
                                        packed=packed)
                frs.append(fr)
            else:
                sc, bs, vl, vi, gn, nw, th, na, pos = build_tree_traced(
                    bins, stats, leaf0, kk, is_cat, cfg, tree_cols,
                    mono=mono, inv_scale=inv_sc, draws=draws)
                ch = None
            with jax.named_scope("h2o.tree.split"):
                vl = vl * scale
            scs.append(sc)
            bss.append(bs)
            vls.append(vl)
            chs.append(ch)
            vis.append(vi)
            gns.append(gn)
            nws.append(nw)
            ths.append(th)
            nas.append(na)
            with jax.named_scope("h2o.tree.predict"):
                # growth left every row on its final node: the tree's
                # update is a lookup, not a descent of the tree just grown
                preds.append(vl[pos])
        with jax.named_scope("h2o.tree.predict"):
            F = F + jnp.stack(preds, axis=1)
            if oob is not None:
                # the rows out of this tree's bag take its votes
                out_bag = (active & ~samp).astype(jnp.float32)[:, None]
                oob = oob + jnp.concatenate(
                    [jnp.stack(preds, axis=1) * out_bag, out_bag], axis=1)
        with jax.named_scope("h2o.tree.split"):
            out = (jnp.stack(scs), jnp.stack(bss), jnp.stack(vls),
                   sum(vis), jnp.stack(gns), jnp.stack(nws),
                   jnp.stack(ths), jnp.stack(nas))
            if kleaves > 0:
                out = out + (jnp.stack(chs), jnp.stack(frs))
        return (F if oob is None else (F, oob)), out

    # Per-tree keys fold the ABSOLUTE tree index into the forest master
    # key (not a per-block split): tree t's stream depends only on
    # (master key, t), so ANY partition of the forest into blocks —
    # including a mid-run block-size halving by the OOM degradation
    # ladder (models/tree/driver.py) — reproduces the identical forest
    # bit for bit.  t0 stays a TRACED scalar: per-block calls with
    # varying tree offsets reuse one compiled program.
    with jax.named_scope("h2o.tree.stats"):
        ti = jnp.arange(ntrees, dtype=jnp.int32) + jnp.int32(t0)
        keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(ti)
        ts = ti.astype(jnp.float32)
    carry, outs = jax.lax.scan(tree_step, F0 if oob0 is None
                               else (F0, oob0), (ts, keys))
    F_final, oob = (carry, None) if oob0 is None else carry
    if oob0 is not None:
        # the carries leave row-sharded, as the caller hands them in: the
        # next block's call is then the same program
        from h2o_tpu.core.cloud import cloud
        from jax.sharding import NamedSharding
        rows = NamedSharding(cloud().mesh, cloud().data_pspec(None))
        F_final = jax.lax.with_sharding_constraint(F_final, rows)
        oob = jax.lax.with_sharding_constraint(oob, rows)
    if kleaves > 0:
        sc, bs, vl, vi, gn, nw, th, na, ch, fr = outs
    else:
        (sc, bs, vl, vi, gn, nw, th, na), ch, fr = outs, None, None
    with jax.named_scope("h2o.tree.split"):
        vi = jnp.sum(vi, axis=0)
    return TrainedForest(sc, bs, vl, F_final, vi, gn, nw, th, na, ch, oob,
                         fr)


# The donating/non-donating executable pair over this one traced body
# lives in core/exec_store.py (train_forest fetches per call) — the
# default hist_pallas=False above means only the env-resolving wrapper
# can enable the Mosaic-untested fused kernel; a bare _train_forest_impl
# call stays on the portable XLA histogram path.
