"""Fused Rapids verb regions — one shard_map program per fusable chain.

The lazy Rapids planner (rapids/plan.py) walks an expression tree,
recognizes a fusable verb chain (filter / na.omit stages feeding a
sort, a group-by, or each other) and lowers the WHOLE region here as
ONE exec-store-cached shard_map collective instead of one dispatch per
verb.  This is the planning half of the reference's AstExec whole-tree
execution (water/rapids) applied to the PR 8 shard collectives
(core/munge.py).

What fusion buys, concretely:

- **Raggedness flows through the region.**  The eager per-verb chain
  repacks every RAGGED intermediate (the mask-evaluation densify in
  rapids/interp.py ``_dense``, na.omit's ``as_matrix``) — one balanced
  ``all_to_all`` per stage.  The fused program keeps every row on its
  home shard as a masked candidate and emits AT MOST ONE balanced
  exchange at the region boundary (the sample sort's round-2 placement,
  or the single rank-route of a filter-only region).
- **Host count syncs collapse.**  Each eager filter/na.omit syncs its
  per-shard survivor counts and each group-by syncs its group count;
  the fused region syncs exactly once at the boundary.
- **Collectives dedup.**  The per-stage compaction ``all_gather``s of a
  filter chain collapse into the terminal verb's existing collectives:
  a filter feeding a sort contributes only a ``keep`` predicate to the
  sort's key ranking (its compaction IS the sort's placement); filters
  feeding a group-by fold into the factorize validity mask.

Bitwise parity contract (the ``H2O_TPU_RAPIDS_FUSE=0`` oracle): every
fused program reproduces the eager chain's result ROW FOR ROW.
- A sort-terminal region orders surviving rows by (keys, original row
  order).  Masking instead of compacting preserves the per-shard
  relative order and the shard-id-dominant global index order, so the
  local lexsorts, splitter selections and routing land every row at
  the identical global position the eager chain lands it.
- A filter-only region reproduces the eager chain's LAYOUT too: the
  eager chain repacks after each stage, so its final raggedness is
  "stage-k compaction over the stage-(k-1) canonical positions" — the
  fused kernel routes stage-(k-1) survivors to those canonical slots
  (the one boundary exchange) and compacts the final predicate
  locally, yielding the same shard_counts and prefix contents.
- A group-by-terminal region (single predicate stage, canonical base —
  the repack-free eager shape) folds the predicate into the factorize
  validity, so per-shard partial sums accumulate the same values in
  the same order as the eager group-by over the ragged filtered frame.

Every fused executable dispatches through ``ExecStore.dispatch`` under
the ``rapids.fuse`` phase (GL310 lint-enforced): exec-store caching,
AOT persistence, GL7xx IR audit coverage and the OOM ladder all apply.
A fused-region OOM that exhausts the ladder degrades to the unfused
per-verb chain via ``oom.fused_fallback`` — a counted resilience rung,
still bitwise (the eager chain IS the parity oracle).

The ``rapids.fuse`` autotuner lever (fused vs per-verb, measured per
chain-kind x row bucket, bitwise parity gate) picks fusion boundaries;
``H2O_TPU_RAPIDS_FUSE`` forces it either way (config.py).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from h2o_tpu.core.cloud import (cloud, hall_gather, hall_gather_inner,
                                hpsum_slices, hshard_index,
                                shard_map_compat)
from h2o_tpu.core.diag import DispatchStats
from h2o_tpu.core.exec_store import (aval_key, code_fingerprint,
                                     exec_store)
from h2o_tpu.core.frame import Frame, _row_pad
from h2o_tpu.core.munge import (_bucket_rows, _factorize_block,
                                _frame_bucket, _group_table, _lex_ge,
                                _local_lexsort, _pad_rows,
                                _payload_matrix, _payload_to_vecs,
                                _route, sort_oversample)

PHASE = "rapids.fuse"


# ---------------------------------------------------------------------------
# predicate evaluation inside fused bodies.  The tables mirror the
# rapids interpreter's _BINOPS/_UNOPS exactly — the planner only admits
# operators listed here, so fused mask values are bitwise the eager
# mask values (including the NaN semantics: NaN > 0 is False, NaN != 0
# is True — both paths share the jnp formulas).
# ---------------------------------------------------------------------------

_PRED_BINOPS = {
    "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply, "/": jnp.divide,
    "<": lambda a, b: (a < b).astype(jnp.float32),
    "<=": lambda a, b: (a <= b).astype(jnp.float32),
    ">": lambda a, b: (a > b).astype(jnp.float32),
    ">=": lambda a, b: (a >= b).astype(jnp.float32),
    "==": lambda a, b: (a == b).astype(jnp.float32),
    "!=": lambda a, b: (a != b).astype(jnp.float32),
    "&": lambda a, b: ((a != 0) & (b != 0)).astype(jnp.float32),
    "|": lambda a, b: ((a != 0) | (b != 0)).astype(jnp.float32),
}

_PRED_UNOPS = {
    "!": lambda a: (a == 0).astype(jnp.float32),
    "is.na": lambda a: jnp.isnan(a).astype(jnp.float32),
    "abs": jnp.abs, "floor": jnp.floor, "ceiling": jnp.ceil,
    "sqrt": jnp.sqrt, "exp": jnp.exp, "log": jnp.log,
}


def _pred_value(payload, e):
    """Evaluate one static predicate expression over the transport
    matrix.  ``("col", j, is_cat)`` reads column j through the same
    as_float view the eager path uses (cat NA code -> NaN)."""
    tag = e[0]
    if tag == "col":
        d = payload[:, e[1]]
        return jnp.where(d < 0, jnp.nan, d) if e[2] else d
    if tag == "const":
        return e[1]
    if tag == "bin":
        return _PRED_BINOPS[e[1]](_pred_value(payload, e[2]),
                                  _pred_value(payload, e[3]))
    if tag == "un":
        return _PRED_UNOPS[e[1]](_pred_value(payload, e[2]))
    if tag == "notna":
        ok = jnp.ones(payload.shape[0], bool)
        for j, is_cat in enumerate(e[1]):
            d = payload[:, j]
            na = jnp.isnan(d) | (d < 0) if is_cat else jnp.isnan(d)
            ok = ok & ~na
        return ok.astype(jnp.float32)
    raise ValueError(f"bad fused predicate node {e!r}")


def _keep_mask(payload, valid, stages):
    """Conjoined survivor mask of a pred-stage prefix — each stage's
    mask applies exactly as the eager filter kernel applies it:
    ``keep &= (mask_value > 0)``."""
    keep = valid
    for _kind, expr in stages:
        keep = keep & (_pred_value(payload, expr) > 0)
    return keep


def _fused_sort_keys(payload, sort_spec):
    """The _sort_key_matrix transform computed from the transport
    matrix: descending negates, NAs (NaN / cat code < 0) -> -inf so
    they group FIRST both directions."""
    ks = []
    for j, asc, is_cat in sort_spec:
        d = payload[:, j]
        na = jnp.isnan(d)
        if is_cat:
            na = na | (d < 0)
        k = d if asc else -d
        ks.append(jnp.where(na, -jnp.inf, k))
    return jnp.stack(ks, axis=1)


def _fused_factor_keys(payload, gmeta):
    """The _factor_key_matrix transform from the transport matrix: cat
    codes as-is (NA=-1 its own first group), numeric NaN -> -inf."""
    ks = []
    for j, is_cat in gmeta:
        d = payload[:, j]
        if not is_cat:
            d = jnp.where(jnp.isnan(d), -jnp.inf, d)
        ks.append(d)
    return jnp.stack(ks, axis=1)


def _fused_agg_vals(payload, ameta, B: int):
    """Aggregate columns through the as_float view (cat NA -> NaN)."""
    cols = []
    for j, is_cat in ameta:
        d = payload[:, j]
        cols.append(jnp.where(d < 0, jnp.nan, d) if is_cat else d)
    return jnp.stack(cols, axis=1) if cols else \
        jnp.zeros((B, 0), jnp.float32)


# ---------------------------------------------------------------------------
# fused shard_map builders (phase "rapids.fuse").  Each mirrors its
# core/munge.py per-verb twin with the pred-stage masks folded into the
# verb's own validity — no extra collectives, no intermediate
# compaction, no per-stage host syncs.
# ---------------------------------------------------------------------------


def _build_fused_sort(B: int, Pc: int, n: int, S: int, spec):
    """Filter chain + sort as ONE sample-sort collective: the stages'
    masks replace the compactions (filter folds into the key ranking),
    and the per-shard merged-run counts ride back replicated so the
    region's single host sync reads the surviving row count.  The body
    is _build_shard_sort with ``valid := keep`` — splitter sampling,
    routing and the balanced round-2 placement are bitwise the eager
    compact-then-sort order because masking preserves both the
    per-shard relative order and the shard-dominant global row index
    order that break ties."""
    stages, sort_spec = spec
    K = len(sort_spec)
    L = B // n
    mesh = cloud().mesh

    def kern(payload, valid):
        keep = _keep_mask(payload, valid, stages)
        keys = _fused_sort_keys(payload, sort_spec)
        i = hshard_index()
        gidx = i * L + jnp.arange(L, dtype=jnp.int32)
        inval = ~keep
        order = _local_lexsort(keys, gidx, inval, K)
        ks = jnp.take(keys, order, axis=0)
        gs = jnp.take(gidx, order)
        cnt = jnp.sum(keep.astype(jnp.int32))
        pos = (jnp.arange(S) * jnp.maximum(cnt, 1)) // S
        samp_k = jnp.take(ks, jnp.clip(pos, 0, L - 1), axis=0)
        samp_g = jnp.take(gs, jnp.clip(pos, 0, L - 1))
        samp_ok = (cnt > 0) & (pos < cnt)
        all_k = hall_gather(samp_k, "sort.splitters").reshape(n * S, K)
        all_g = hall_gather(samp_g, "sort.splitters").reshape(n * S)
        all_ok = hall_gather(samp_ok, "sort.splitters").reshape(n * S)
        sorder = _local_lexsort(all_k, all_g, ~all_ok, K)
        sk = jnp.take(all_k, sorder, axis=0)
        sg = jnp.take(all_g, sorder)
        nsamp = jnp.sum(all_ok.astype(jnp.int32))
        spos = (jnp.arange(1, n) * jnp.maximum(nsamp, 1)) // n
        split_k = jnp.take(sk, jnp.clip(spos, 0, n * S - 1), axis=0)
        split_g = jnp.take(sg, jnp.clip(spos, 0, n * S - 1))
        split_ok = (spos < jnp.maximum(nsamp, 1)) & (nsamp > 0)
        ge = _lex_ge(keys[:, None, :], gidx[:, None],
                     split_k[None, :, :], split_g[None, :], K)
        dest = jnp.sum((ge & split_ok[None, :]).astype(jnp.int32),
                       axis=1)
        dmask = jnp.where(keep, dest, n)
        kp = jnp.concatenate([keys, payload], axis=1)
        rkp, rg, rv = _route(kp, gidx, dmask, n, L, L, tag="sort.route")
        rk = rkp[:, :K]
        m_order = _local_lexsort(rk, rg, ~rv, K)
        rp = jnp.take(rkp[:, K:], m_order, axis=0)
        c = jnp.sum(rv.astype(jnp.int32))
        all_c = hall_gather(c, "sort.counts")
        base = jnp.sum(jnp.where(jnp.arange(n) < i, all_c, 0))
        gpos = base + jnp.arange(n * L, dtype=jnp.int32)
        v2 = jnp.arange(n * L) < c
        dest2 = jnp.where(v2, jnp.clip(gpos // L, 0, n - 1), n)
        rp2, rs2, rv2 = _route(rp, gpos % L, dest2, n, n * L, L,
                               tag="sort.route")
        out = jnp.full((L + 1, Pc), jnp.nan, payload.dtype)
        out = out.at[jnp.where(rv2, rs2, L)].set(rp2)
        return out[:L], all_c

    dp = cloud().data_pspec
    return shard_map_compat(
        kern, mesh=mesh,
        in_specs=(dp(None), dp()),
        out_specs=(dp(None), P()), check_vma=False)


def _build_fused_filter(B: int, Pc: int, n: int, spec):
    """A k>=2 filter/na.omit chain as ONE program.  The eager chain
    repacks after every stage, so its final layout is "stage-k
    compaction over stage-(k-1) canonical positions"; this kernel
    reproduces that layout with exactly one balanced exchange: rank the
    stage-(k-1) survivors globally, route them to their canonical
    slots (the k-1 eager repacks collapsed into the one boundary
    route), then compact the final predicate locally.  The per-shard
    survivor counts are the region's only host sync."""
    stages = spec
    L = B // n
    mesh = cloud().mesh

    def kern(payload, valid):
        keep_pre = _keep_mask(payload, valid, stages[:-1])
        keep_all = keep_pre & \
            (_pred_value(payload, stages[-1][1]) > 0)
        idx = jnp.arange(L, dtype=jnp.int32)
        order = jnp.argsort(jnp.where(keep_pre, idx, L + idx))
        c_pre = jnp.sum(keep_pre.astype(jnp.int32))
        pay = jnp.take(payload, order, axis=0)
        flag = jnp.take(keep_all, order).astype(jnp.float32)
        counts_pre = hall_gather(c_pre, "filter.counts")
        i = hshard_index()
        base = jnp.sum(jnp.where(jnp.arange(n) < i, counts_pre, 0))
        gpos = base + jnp.arange(L, dtype=jnp.int32)
        v = jnp.arange(L) < c_pre
        dest = jnp.where(v, jnp.clip(gpos // L, 0, n - 1), n)
        pf = jnp.concatenate([pay, flag[:, None]], axis=1)
        rp, rs, rv = _route(pf, gpos % L, dest, n, L, L,
                            tag="filter.route")
        slot = jnp.where(rv, rs, L)
        buf = jnp.full((L + 1, Pc + 1), jnp.nan, payload.dtype)
        buf = buf.at[slot].set(rp)[:L]
        keep_k = buf[:, Pc] > 0
        idx2 = jnp.arange(L, dtype=jnp.int32)
        order2 = jnp.argsort(jnp.where(keep_k, idx2, L + idx2))
        c = jnp.sum(keep_k.astype(jnp.int32))
        out = jnp.take(buf[:, :Pc], order2, axis=0)
        out = jnp.where((jnp.arange(L) < c)[:, None], out, jnp.nan)
        return out, hall_gather(c, "filter.counts")

    dp = cloud().data_pspec
    return shard_map_compat(
        kern, mesh=mesh,
        in_specs=(dp(None), dp()),
        out_specs=(dp(None), P()), check_vma=False)


def _build_fused_group_count(B: int, Pc: int, n: int, spec):
    """shard_group_count with the pred-stage mask folded into the
    factorize validity: the eager filter's compaction and count sync
    vanish; the keys are computed from the transport matrix inside the
    program (key canonicalization fuses too)."""
    stages, gmeta = spec
    K = len(gmeta)
    L = B // n
    mesh = cloud().mesh
    q = n // cloud().n_slices

    def kern(payload, valid):
        keep = _keep_mask(payload, valid, stages)
        keys = _fused_factor_keys(payload, gmeta)
        inv, order, g = _factorize_block(keys, keep, L, K)
        gs = jnp.take(inv, order)
        bpos = jnp.searchsorted(gs, jnp.arange(L))
        reps = jnp.take(keys,
                        jnp.take(order, jnp.clip(bpos, 0, L - 1)),
                        axis=0)
        slot_ok = jnp.arange(L) < g
        # slice-local rep gather + one DCN scalar psum: exact count on
        # a flat mesh, upper bound on a two-level one (see the munge
        # twin's docstring — the exact count is recovered from the
        # combined counts table after the agg pass)
        ck = hall_gather_inner(
            jnp.where(slot_ok[:, None], reps, jnp.inf),
            "groupby.count").reshape(q * L, K)
        cv = hall_gather_inner(slot_ok, "groupby.count").reshape(q * L)
        _i2, _o2, g2 = _factorize_block(ck, cv, q * L, K)
        return hpsum_slices(g2, "groupby.count")

    dp = cloud().data_pspec
    return shard_map_compat(
        kern, mesh=mesh,
        in_specs=(dp(None), dp()),
        out_specs=P(), check_vma=False)


def _build_fused_group_aggs(B: int, Pc: int, n: int, Gb: int, spec):
    """shard_group_aggs with the pred-stage mask folded in: local
    factorize + fused per-shard partials over the masked rows, then the
    cross-shard combine.  Partial sums accumulate the same values in
    the same per-shard order as the eager group-by over the ragged
    filtered frame (compaction preserves relative order), so the group
    table is bitwise the eager table."""
    stages, gmeta, ameta = spec
    K = len(gmeta)
    A = len(ameta)
    L = B // n
    mesh = cloud().mesh
    # two-level: statically truncate per-shard partials to min(L, Gb)
    # before the hierarchical gather — see _build_shard_group_aggs
    Lg = L if cloud().n_slices == 1 else min(L, Gb)

    def _partials(keys, valid, vals, size):
        inv, order, g = _factorize_block(keys, valid, size, K)
        gs = jnp.take(inv, order)
        bpos = jnp.searchsorted(gs, jnp.arange(size))
        reps = jnp.take(keys,
                        jnp.take(order, jnp.clip(bpos, 0, size - 1)),
                        axis=0)
        slot_ok = jnp.arange(size) < g
        cnt = jax.ops.segment_sum(valid.astype(jnp.float32), inv,
                                  num_segments=size)
        parts = []
        for a in range(A):
            d = vals[:, a]
            ok = valid & ~jnp.isnan(d)
            okf = ok.astype(jnp.float32)
            di = jnp.where(ok, d, 0.0)
            parts.append(jnp.stack([
                jax.ops.segment_sum(okf, inv, num_segments=size),
                jax.ops.segment_sum(di, inv, num_segments=size),
                jax.ops.segment_sum(di * di, inv, num_segments=size),
                jax.ops.segment_min(jnp.where(ok, d, jnp.inf), inv,
                                    num_segments=size),
                jax.ops.segment_max(jnp.where(ok, d, -jnp.inf), inv,
                                    num_segments=size)], axis=1))
        part = jnp.stack(parts, axis=2) if A else \
            jnp.zeros((size, 5, 0), jnp.float32)
        return reps, slot_ok, cnt, part

    def kern(payload, valid):
        keep = _keep_mask(payload, valid, stages)
        keys = _fused_factor_keys(payload, gmeta)
        vals = _fused_agg_vals(payload, ameta, L)
        reps, slot_ok, cnt, part = _partials(keys, keep, vals, L)
        if Lg != L:                       # two-level: drop pure padding
            reps, slot_ok = reps[:Lg], slot_ok[:Lg]
            cnt, part = cnt[:Lg], part[:Lg]
        ck = hall_gather(jnp.where(slot_ok[:, None], reps, jnp.inf),
                         "groupby.partials").reshape(n * Lg, K)
        cv = hall_gather(slot_ok, "groupby.partials").reshape(n * Lg)
        cc = hall_gather(jnp.where(slot_ok, cnt, 0.0),
                         "groupby.partials").reshape(n * Lg)
        cp = hall_gather(jnp.where(slot_ok[:, None, None], part,
                                   jnp.nan),
                         "groupby.partials").reshape(n * Lg, 5, A)
        inv2, order2, _g2 = _factorize_block(ck, cv, n * Lg, K)
        gs2 = jnp.take(inv2, order2)
        bpos2 = jnp.searchsorted(gs2, jnp.arange(Gb))
        keyvals = jnp.take(
            ck, jnp.take(order2, jnp.clip(bpos2, 0, n * Lg - 1)),
            axis=0)[:Gb]
        counts = jax.ops.segment_sum(jnp.where(cv, cc, 0.0), inv2,
                                     num_segments=Gb)
        outs = []
        for a in range(A):
            combine = [
                jax.ops.segment_sum(jnp.where(cv, cp[:, 0, a], 0.0),
                                    inv2, num_segments=Gb),
                jax.ops.segment_sum(jnp.where(cv, cp[:, 1, a], 0.0),
                                    inv2, num_segments=Gb),
                jax.ops.segment_sum(jnp.where(cv, cp[:, 2, a], 0.0),
                                    inv2, num_segments=Gb),
                jax.ops.segment_min(jnp.where(cv, cp[:, 3, a], jnp.inf),
                                    inv2, num_segments=Gb),
                jax.ops.segment_max(jnp.where(cv, cp[:, 4, a],
                                              -jnp.inf),
                                    inv2, num_segments=Gb)]
            outs.append(jnp.stack(combine, axis=1))
        out = jnp.stack(outs, axis=2) if A else \
            jnp.zeros((Gb, 5, 0), jnp.float32)
        return keyvals, counts, out

    dp = cloud().data_pspec
    return shard_map_compat(
        kern, mesh=mesh,
        in_specs=(dp(None), dp()),
        out_specs=(P(), P(), P()), check_vma=False)


# ---------------------------------------------------------------------------
# dispatch + region runners
# ---------------------------------------------------------------------------


def _dispatch(name: str, statics: Tuple, builder, *arrays):
    """Every fused region executes through ``ExecStore.dispatch`` under
    the ``rapids.fuse`` phase (GL310): exec-store cached per (name,
    spec, avals), AOT-persisted, OOM-laddered at the region site."""
    key = (name, statics, tuple(aval_key(a) for a in arrays))
    return exec_store().dispatch(
        PHASE, key, builder, tuple(arrays),
        site="rapids.fuse",
        persist=f"rapids:{name}:{statics!r}",
        content=code_fingerprint(builder))


def run_fused_sort(fr: Frame, stages, sort_spec) -> Frame:
    """Execute a [pred-stage..., sort] region: one collective, one host
    sync (the surviving row count), canonical sorted output."""
    with DispatchStats.phase_scope(PHASE):
        n = cloud().n_nodes
        B = _frame_bucket(fr)
        payload = _payload_matrix(fr, B)
        valid = _pad_rows(fr.row_mask(), B, False)
        S = min(max(sort_oversample() * n, 4), B // n)
        spec = (tuple(stages), tuple(sort_spec))
        out, counts = _dispatch(
            "fused_sort", (B, fr.ncols, n, S, spec),
            lambda: _build_fused_sort(B, fr.ncols, n, S, spec),
            payload, valid)
        n_out = int(np.asarray(counts, np.int64).sum())  # boundary sync
        return Frame(list(fr.names), _payload_to_vecs(out, fr, n_out))


def run_fused_filter(fr: Frame, stages) -> Frame:
    """Execute a k>=2 filter-only region: one collective with the one
    boundary exchange, one host sync, ragged output bitwise matching
    the eager chain's layout."""
    with DispatchStats.phase_scope(PHASE):
        n = cloud().n_nodes
        B = _frame_bucket(fr)
        payload = _payload_matrix(fr, B)
        valid = _pad_rows(fr.row_mask(), B, False)
        spec = tuple(stages)
        out, counts = _dispatch(
            "fused_filter", (B, fr.ncols, n, spec),
            lambda: _build_fused_filter(B, fr.ncols, n, spec),
            payload, valid)
        sc = np.asarray(counts, np.int64)               # boundary sync
        n_out = int(sc.sum())
        return Frame(list(fr.names),
                     _payload_to_vecs(out, fr, n_out, shard_counts=sc))


def run_fused_groupby(fr: Frame, stages, gcols: Sequence[int],
                      aggs) -> Frame:
    """Execute a [pred-stage, group-by] region: the predicate folds
    into both group kernels, eliding the filter dispatch and its count
    sync — the group count is the region's only host sync."""
    with DispatchStats.phase_scope(PHASE):
        n = cloud().n_nodes
        B = _frame_bucket(fr)
        payload = _payload_matrix(fr, B)
        valid = _pad_rows(fr.row_mask(), B, False)
        gmeta = tuple((int(j), bool(fr.vecs[j].is_categorical))
                      for j in gcols)
        ameta = tuple((int(c), bool(fr.vecs[c].is_categorical))
                      for _a, c, _na in aggs)
        cspec = (tuple(stages), gmeta)
        g_dev = _dispatch(
            "fused_group_count", (B, fr.ncols, n, cspec),
            lambda: _build_fused_group_count(B, fr.ncols, n, cspec),
            payload, valid)
        # flat mesh: exact group count; two-level: an upper bound big
        # enough to size the table bucket (munge twin's docstring)
        G = int(g_dev)                                  # boundary sync
        Gb = _bucket_rows(max(_row_pad(G), 1))
        aspec = (tuple(stages), gmeta, ameta)
        keyvals, counts, parts = _dispatch(
            "fused_group_aggs", (B, fr.ncols, n, Gb, aspec),
            lambda: _build_fused_group_aggs(B, fr.ncols, n, Gb, aspec),
            payload, valid)
        if cloud().n_slices > 1:
            # exact count recovered from the combined counts column:
            # real groups are a dense prefix with counts >= 1
            G = int(jnp.sum((counts > 0).astype(jnp.int32)))
        outs = []
        for a, (op, _c, _na) in enumerate(aggs):
            cnt_ok = parts[:, 0, a]
            s = parts[:, 1, a]
            ss = parts[:, 2, a]
            if op in ("nrow", "count"):
                out = counts
            elif op == "sum":
                out = s
            elif op == "mean":
                out = s / jnp.maximum(cnt_ok, 1)
            elif op in ("sd", "var"):
                m = s / jnp.maximum(cnt_ok, 1)
                var = ss / jnp.maximum(cnt_ok, 1) - m * m
                var = jnp.maximum(
                    var * cnt_ok / jnp.maximum(cnt_ok - 1, 1), 0.0)
                out = jnp.sqrt(var) if op == "sd" else var
            else:                                # min / max
                out = parts[:, 3 if op == "min" else 4, a]
                out = jnp.where(jnp.isfinite(out), out, jnp.nan)
            outs.append(out)
        return _group_table(fr, list(gcols), list(aggs), keyvals,
                            counts, outs, G)


# ---------------------------------------------------------------------------
# the rapids.fuse autotuner lever: fused vs per-verb, per (row bucket,
# chain kind), bitwise parity gate against the per-verb reference.
# H2O_TPU_RAPIDS_FUSE forces it outright (the test/audit
# convention, like H2O_TPU_BINS_PACK); in auto mode CPU backends keep
# the per-verb reference and TPU backends measure.
# ---------------------------------------------------------------------------

_PROBE_STAGES = (("filter", ("bin", ">", ("col", 0, False),
                             ("const", 0.0))),)
_PROBE_SORT = ((1, True, False),)


def _fuse_workload(bucket: Tuple) -> dict:
    rows = min(int(bucket[0]), 1 << 15)
    rng = np.random.default_rng(7)
    X = rng.standard_normal((rows, 4)).astype(np.float32)
    fr = Frame.from_numpy(X, names=["a", "b", "c", "d"])
    return {"fr": fr}


def _fuse_run(v: str, w: dict):
    from h2o_tpu.core import munge
    fr = w["fr"]
    if v == "fused":
        out = run_fused_sort(fr, _PROBE_STAGES, _PROBE_SORT)
    else:
        mask = (fr.vecs[0].data > 0).astype(jnp.float32)
        out = munge.sort_frame(munge.filter_rows(fr, mask), [1], [True])
    return out.as_matrix()[: out.nrows]


def _fuse_fp() -> str:
    from h2o_tpu.core import munge
    return ",".join(code_fingerprint(f) for f in (
        _build_fused_sort, _build_fused_filter, _build_fused_group_aggs,
        munge._build_shard_sort, munge._build_shard_filter, _route))


def _register_fuse_lever() -> None:
    from h2o_tpu.core.autotune import Lever, register_lever
    register_lever(Lever(
        site="rapids.fuse",
        env_var="H2O_TPU_RAPIDS_FUSE",
        variants=("per_verb", "fused"),
        true_variants=frozenset({"fused"}),
        default_bucket=(1 << 15, "filter_sort"),
        make_workload=_fuse_workload,
        run_variant=_fuse_run,
        fingerprint=_fuse_fp,
        # the fusion contract promises row-for-row identical frames, so
        # the parity gate is bitwise, not approximate
        tol=(0.0, 0.0),
    ))


_register_fuse_lever()
