"""Device-resident data munging — sort / merge / group-by / filter kernels.

Reference design (water/rapids/Merge.java, RadixOrder.java,
ast/prims/mungers/AstGroup.java, SURVEY §3.6): H2O-3 runs its munging
verbs as first-class distributed map/reduce tasks — a parallel MSD radix
sort over chunks (RadixOrder), a binary-search sorted join
(BinaryMerge), and per-chunk group maps merged in the reduce tree
(AstGroup.GBTask).  Data never leaves the cluster heap, and every chunk
stays home-noded through the whole verb.

This module holds TWO device generations of those verbs:

**Shard-resident collectives (default, ``H2O_TPU_SHARD_MUNGE=1``)** —
every verb is a ``shard_map`` program over the mesh's ``nodes`` axis
(core/cloud.py DATA_AXIS), the direct analog of the reference's
chunk-homed MRTask verbs.  Rows stay on their home shard; only
splitters, per-group partials and per-shard counts cross the
interconnect:

- **sort** — a sample sort: per-shard local ``lexsort``, oversampled
  splitter quantiles gathered from every shard (``all_gather``), a
  bucket exchange over ``all_to_all``, local merge, then a second
  balanced ``all_to_all`` that lands each row at its global sorted
  position.  Stability ties break on the original global row index, so
  the output row order is BITWISE the host ``np.lexsort`` order.
- **group-by** — local factorize + ONE fused local
  ``segment_sum/min/max`` partials pass per shard, then a cross-shard
  combine over the (small) per-group partial tables — only the final
  group table replicates, never the rows.
- **merge/join** — the fold-the-small-frame join: the LEFT side stays
  row-sharded (its rows never leave their shard — pair emission gathers
  left payload locally), the right side's key table broadcasts once;
  per-shard sorted joins emit pairs in global left-row order and
  ``all_y`` right-only rows append after the last shard's pairs —
  bitwise the host oracle's row order.  Put the smaller frame on the
  right (H2O-3's fold-the-small-frame discipline).
- **filter / na.omit** — per-shard compaction: surviving rows compact to
  a LOCAL prefix and the per-shard valid-row counts (one int per shard)
  are the only host sync.  The result Frame is RAGGED
  (``Vec.shard_counts``): downstream verbs and reductions mask the
  padding via ``valid_mask()`` instead of re-gathering; ``repack_frame``
  (one balanced ``all_to_all``) restores the canonical prefix when a
  non-munge consumer needs it.

**Global kernels (``H2O_TPU_SHARD_MUNGE=0``, the PR 4 generation)** —
single logical ``jnp`` programs over the whole row-sharded array.  XLA
partitions them, but is free to gather rows cross-shard; they remain as
the shard path's reference implementation and as the executor for
verbs without a collective form yet (median group-by's order-statistic
pass).

Compile bounding: row counts pad to power-of-two shape buckets, and
every kernel routes through the unified executable store
(core/exec_store.py) under the ``munge`` phase — the shard collectives
dispatch via ``ExecStore.dispatch`` and therefore run under the OOM
degradation ladder (sweep -> non-donating twin -> the interp layer's
host-oracle fallback) and inherit AOT persistence for free.  One
compile per (verb, schema, shape-bucket, mesh shape); hit/miss/disk-hit
/host-pull counters and the distinct kernel entries surface at
GET /3/Dispatch.

Fallback contract: ``H2O_TPU_DEVICE_MUNGE=0`` (or any frame holding
T_TIME/T_STR/T_UUID columns, or a group-by whose ``mode`` aggregates
target numeric / high-cardinality columns — mode_device_eligible)
takes the host-NumPy path in rapids/interp.py — which doubles as the
parity oracle for tests/test_munge_device.py and
tests/test_shard_munge.py.  Categorical ``mode`` itself runs on device
via the segment-bincount + argmax kernel (core/quantile.segment_mode).

NA/tie semantics (all paths agree):
- sort: NAs group FIRST in both sort directions (RadixOrder's
  consistent NA placement); ties keep input order (stable).
- group-by / merge keys: numeric NaN canonicalizes to one NA group
  (sentinel -inf, so the NA group sorts first); categorical NA is the
  -1 code, its own group, also first.  NA keys match each other in
  joins (the host path's string-join semantics).
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from h2o_tpu.core import landing
from h2o_tpu.core.cloud import (cloud, hall_gather, hall_gather_inner,
                                hall_to_all, hpsum, hpsum_slices,
                                hshard_index, shard_map_compat)
from h2o_tpu.core.diag import DispatchStats
from h2o_tpu.core.frame import (Frame, T_CAT, Vec, _row_pad,
                                frame_device_ok)
from h2o_tpu.core.exec_store import (cached_kernel, code_fingerprint,
                                     exec_store)

PHASE = "munge"

# group-by aggregates with a device form.  min..count combine from
# per-shard partials in the shard collective; median and mode need a
# per-group order statistic / bincount and run via the global
# factorize + fused segment kernels (device-resident, not yet pure
# collectives).  mode is device-eligible for every categorical column:
# the chunked segment-bincount (quantile.segment_mode) folds the count
# table in 1024-wide value passes, so domain cardinality is unbounded.
# Numeric mode stays a documented host fallback (rapids/interp.py
# _groupby_host) — a float column has no dense code space to bincount.
DEVICE_AGGS = ("min", "max", "mean", "sum", "sd", "var", "nrow", "count",
               "median", "mode")
COMBINABLE_AGGS = ("min", "max", "mean", "sum", "sd", "var", "nrow",
                   "count")


def mode_device_eligible(fr, aggs) -> bool:
    """True when every ``mode`` agg in the bundle targets a categorical
    column (any cardinality — the chunked segment-bincount kernel's
    count table is bounded per pass).  Numeric mode columns keep the
    documented host fallback."""
    for a, c, _na in aggs:
        if a != "mode":
            continue
        v = fr.vecs[c]
        if not v.is_categorical or not v.domain:
            return False
    return True


def device_munge_enabled() -> bool:
    """H2O_TPU_DEVICE_MUNGE=0|false|off forces the host-NumPy munge
    paths (the parity oracle); default is device-resident."""
    return os.environ.get("H2O_TPU_DEVICE_MUNGE", "1").lower() not in (
        "0", "false", "off")


def shard_munge_enabled() -> bool:
    """H2O_TPU_SHARD_MUNGE=0|false|off drops back to the PR 4 global
    jnp kernels; default runs the verbs as shard_map collectives on
    every mesh shape (a 1x1 mesh runs the same program with no-op
    collectives, so the code path is identical in CI and at scale)."""
    return os.environ.get("H2O_TPU_SHARD_MUNGE", "1").lower() not in (
        "0", "false", "off")


def sort_oversample() -> int:
    """H2O_TPU_SORT_OVERSAMPLE (default 4): splitter samples per shard
    are ``oversample * n_nodes`` — more samples = tighter bucket balance
    in the sample sort's exchange, at the cost of a wider replicated
    splitter sort."""
    return max(int(os.environ.get("H2O_TPU_SORT_OVERSAMPLE", "4")), 1)


def _bucket_rows(p: int) -> int:
    """Smallest power-of-two >= p, rounded up to the row quantum — the
    shape bucket every munge kernel compiles at, so recompiles stay
    logarithmic in frame size (serve/engine.py's ``_bucket`` applied to
    the data plane)."""
    q = cloud().row_multiple()
    b = 1 << max(int(p - 1).bit_length(), 0) if p > 1 else 1
    b = max(b, q)
    return ((b + q - 1) // q) * q


def _pad_rows(arr: jax.Array, n: int, fill) -> jax.Array:
    """Eager device pad of rows to length ``n`` (never touches host).

    Spelled as ``jnp.pad``, NOT ``jnp.concatenate([arr, filler])``:
    concatenating a row-sharded operand with a fresh filler miscompiled
    on meshes with a model axis (XLA:CPU GSPMD emitted a strided/summed
    mess) — the pad op lowers correctly."""
    if arr.shape[0] >= n:
        return arr
    pad_width = [(0, n - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pad_width, constant_values=fill)


def _mk_vec(arr: jax.Array, like: Vec, nrows: int,
            shard_counts=None) -> Vec:
    """Wrap a munge-kernel output column as a row-sharded Vec."""
    arr = landing.reshard_rows(arr)
    return Vec(arr, like.type, nrows=nrows,
               domain=list(like.domain) if like.domain else None,
               shard_counts=shard_counts)


def _dispatch_kernel(name: str, statics: Tuple, builder, *arrays,
                     site: Optional[str] = None):
    """Run one munge kernel through ``ExecStore.dispatch`` — fetched-or-
    compiled once per (name, statics, avals), executed under the OOM
    ladder, AOT-persisted under a stable ``munge:name:statics`` disk
    name.  ``builder()`` must return the RAW kernel (the store jits);
    the shard collectives route here so every sharded variant is a
    DISTINCT, observable exec-store entry."""
    key = (name, statics, tuple(_aval(a) for a in arrays))
    return exec_store().dispatch(
        PHASE, key, builder, tuple(arrays),
        site=site or f"munge.{name}",
        persist=f"munge:{name}:{statics!r}",
        content=code_fingerprint(builder))


def _aval(x):
    from h2o_tpu.core.exec_store import aval_key
    return aval_key(x)


# ---------------------------------------------------------------------------
# traced helpers shared by the global kernels and the shard collectives
# ---------------------------------------------------------------------------


def _factorize_block(keys, valid, size: int, K: int):
    """Rows -> dense codes over one block: sort-based unique (the H2O
    radix factorization).  Returns (inv codes, sort order, n_groups);
    invalid rows sort last and take codes past ``n_groups``."""
    sv = jnp.where(valid, 0, 1)
    cols = [keys[:, k] for k in range(K)]
    order = jnp.lexsort(tuple(cols[::-1]) + (sv,))
    ks = jnp.take(keys, order, axis=0)
    vs = jnp.take(valid, order)
    if size > 1:
        diff = jnp.any(ks[1:] != ks[:-1], axis=1) | (vs[1:] != vs[:-1])
        # pad (not concatenate) — see _pad_rows' sharded-concat caveat
        new_group = jnp.pad(diff, (1, 0), constant_values=True)
    else:
        new_group = jnp.ones((1,), bool)
    gid_sorted = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    inv = jnp.zeros(size, jnp.int32).at[order].set(gid_sorted)
    nvalid = jnp.sum(valid.astype(jnp.int32))
    last = jnp.take(gid_sorted, jnp.maximum(nvalid - 1, 0))
    n_groups = jnp.where(nvalid > 0, last + 1, 0)
    return inv, order, n_groups


def _local_lexsort(keys, gidx, inval, K: int):
    """Stable order by (validity, key columns, original row id)."""
    cols = [gidx.astype(jnp.int32)] + \
        [keys[:, k] for k in range(K - 1, -1, -1)] + \
        [inval.astype(jnp.int32)]
    return jnp.lexsort(tuple(cols))


def _lex_ge(ka, ga, kb, gb, K: int):
    """Vectorized lexicographic (keys..., rowid) >= comparison."""
    ge = ga >= gb
    for k in range(K - 1, -1, -1):
        a, b = ka[..., k], kb[..., k]
        ge = (a > b) | ((a == b) & ge)
    return ge


def _route(payload, slots, dest, n: int, L: int, cap: int,
           tag: str = "route"):
    """One all_to_all bucket exchange: rows sorted stably by ``dest``
    (invalid rows carry dest >= n) are packed into an (n, cap) send
    buffer — slot [d] holds this shard's rows for shard d — exchanged,
    and returned flattened with per-row validity.  ``slots`` rides
    along as an int32 side channel (target position / row id).  On a
    two-level mesh the exchange routes per-slice blocks across DCN
    first (only off-slice buckets cross), then scatters within each
    ICI island — rows are the one payload that MUST move in a sort, so
    route bytes are reported separately from the O(table) combines."""
    o = jnp.argsort(dest, stable=True)
    ds = jnp.take(dest, o)
    starts = jnp.searchsorted(ds, jnp.arange(n)).astype(jnp.int32)
    ends = jnp.searchsorted(ds, jnp.arange(n),
                            side="right").astype(jnp.int32)
    l_idx = jnp.arange(cap, dtype=jnp.int32)[None, :]
    src_pos = starts[:, None] + l_idx                       # (n, cap)
    sendv = src_pos < ends[:, None]
    src = jnp.take(o, jnp.clip(src_pos, 0, dest.shape[0] - 1))
    send_p = jnp.where(sendv[..., None],
                       jnp.take(payload, src, axis=0), jnp.nan)
    send_s = jnp.where(sendv, jnp.take(slots, src), jnp.int32(1 << 30))
    recv_p = hall_to_all(send_p, tag=tag)
    recv_s = hall_to_all(send_s, tag=tag)
    recv_v = hall_to_all(sendv, tag=tag)
    m = n * cap
    return (recv_p.reshape(m, payload.shape[1]), recv_s.reshape(m),
            recv_v.reshape(m))


# ---------------------------------------------------------------------------
# shard_map collective builders (phase "munge"; dispatched through the
# exec store so each is one compiled, persisted, OOM-laddered program)
# ---------------------------------------------------------------------------


def _build_shard_sort(B: int, K: int, Pc: int, n: int, S: int):
    """Sample-sort collective: keys (B,K) canonicalized/NaN-free,
    payload (B,Pc) f32, valid (B,) -> payload at the global stable
    lexsort order, canonical prefix layout.  Row order is bitwise the
    host ``np.lexsort`` order: routing, local merges and the final
    placement all break ties on the original global row index."""
    L = B // n
    mesh = cloud().mesh

    def kern(keys, payload, valid):
        i = hshard_index()
        gidx = i * L + jnp.arange(L, dtype=jnp.int32)
        inval = ~valid
        order = _local_lexsort(keys, gidx, inval, K)
        ks = jnp.take(keys, order, axis=0)
        gs = jnp.take(gidx, order)
        cnt = jnp.sum(valid.astype(jnp.int32))
        # oversampled splitters from every shard's sorted valid prefix
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
        # destination bucket = #splitters <= (row keys, row id)
        ge = _lex_ge(keys[:, None, :], gidx[:, None],
                     split_k[None, :, :], split_g[None, :], K)
        dest = jnp.sum((ge & split_ok[None, :]).astype(jnp.int32),
                       axis=1)
        dmask = jnp.where(valid, dest, n)
        kp = jnp.concatenate([keys, payload], axis=1)
        rkp, rg, rv = _route(kp, gidx, dmask, n, L, L, tag="sort.route")
        rk = rkp[:, :K]
        m_order = _local_lexsort(rk, rg, ~rv, K)
        rp = jnp.take(rkp[:, K:], m_order, axis=0)
        c = jnp.sum(rv.astype(jnp.int32))
        all_c = hall_gather(c, "sort.counts")
        base = jnp.sum(jnp.where(jnp.arange(n) < i, all_c, 0))
        # balanced re-exchange: row j of the merged run lands at global
        # position base + j -> shard (pos // L), slot (pos % L)
        gpos = base + jnp.arange(n * L, dtype=jnp.int32)
        v2 = jnp.arange(n * L) < c
        dest2 = jnp.where(v2, jnp.clip(gpos // L, 0, n - 1), n)
        rp2, rs2, rv2 = _route(rp, gpos % L, dest2, n, n * L, L,
                               tag="sort.route")
        out = jnp.full((L + 1, Pc), jnp.nan, payload.dtype)
        out = out.at[jnp.where(rv2, rs2, L)].set(rp2)
        return out[:L]

    dp = cloud().data_pspec
    in_specs = (dp(None), dp(None), dp())
    return shard_map_compat(kern, mesh=mesh, in_specs=in_specs,
                            out_specs=dp(None),
                            check_vma=False)


def _build_shard_filter(B: int, Pc: int, n: int):
    """Per-shard compaction: surviving rows pack to a LOCAL prefix in
    input order; the (n,) per-shard survivor counts are the only values
    that leave the device — the result stays ragged-sharded."""
    L = B // n
    mesh = cloud().mesh

    def kern(mask, valid, payload):
        keep = (mask > 0) & valid
        idx = jnp.arange(L, dtype=jnp.int32)
        order = jnp.argsort(jnp.where(keep, idx, L + idx))
        c = jnp.sum(keep.astype(jnp.int32))
        out = jnp.take(payload, order, axis=0)
        out = jnp.where((jnp.arange(L) < c)[:, None], out, jnp.nan)
        return out, hall_gather(c, "filter.counts")

    dp = cloud().data_pspec
    return shard_map_compat(
        kern, mesh=mesh,
        in_specs=(dp(), dp(), dp(None)),
        out_specs=(dp(None), P()), check_vma=False)


def _build_shard_repack(B: int, Pc: int, n: int):
    """Ragged -> canonical prefix: one balanced all_to_all routes each
    shard's local valid prefix to its global position (the round-2
    exchange of the sample sort, standalone)."""
    L = B // n
    mesh = cloud().mesh

    def kern(payload, counts):
        i = hshard_index()
        c = jnp.take(counts, i)
        base = jnp.sum(jnp.where(jnp.arange(n) < i, counts, 0))
        gpos = base + jnp.arange(L, dtype=jnp.int32)
        v = jnp.arange(L) < c
        dest = jnp.where(v, jnp.clip(gpos // L, 0, n - 1), n)
        rp, rs, rv = _route(payload, gpos % L, dest, n, L, L,
                            tag="repack.route")
        out = jnp.full((L + 1, Pc), jnp.nan, payload.dtype)
        out = out.at[jnp.where(rv, rs, L)].set(rp)
        return out[:L]

    dp = cloud().data_pspec
    return shard_map_compat(
        kern, mesh=mesh, in_specs=(dp(None), P()),
        out_specs=dp(None), check_vma=False)


def _build_shard_group_count(B: int, K: int, n: int):
    """Distinct-key count.  Flat mesh: local factorize, gather the
    (small) local group-rep tables, factorize the candidates — the
    EXACT global group count (the one scalar the host syncs to size the
    agg pass).  Two-level mesh: the rep gather stays SLICE-LOCAL and
    one scalar psum of the per-slice distinct counts crosses DCN — an
    UPPER BOUND on the global count (groups spanning slices count once
    per slice), which is all the agg pass needs for its table bucket;
    the exact count falls out of the combined counts table afterwards.
    This is what keeps the group-by's cross-slice bytes O(1) instead of
    O(local table)."""
    L = B // n
    mesh = cloud().mesh
    q = n // cloud().n_slices

    def kern(keys, valid):
        inv, order, g = _factorize_block(keys, valid, L, K)
        gs = jnp.take(inv, order)
        bpos = jnp.searchsorted(gs, jnp.arange(L))
        reps = jnp.take(keys,
                        jnp.take(order, jnp.clip(bpos, 0, L - 1)), axis=0)
        slot_ok = jnp.arange(L) < g
        ck = hall_gather_inner(
            jnp.where(slot_ok[:, None], reps, jnp.inf),
            "groupby.count").reshape(q * L, K)
        cv = hall_gather_inner(slot_ok, "groupby.count").reshape(q * L)
        _i2, _o2, g2 = _factorize_block(ck, cv, q * L, K)
        return hpsum_slices(g2, "groupby.count")

    dp = cloud().data_pspec
    return shard_map_compat(kern, mesh=mesh,
                            in_specs=(dp(None), dp()),
                            out_specs=P(), check_vma=False)


def _build_shard_group_aggs(B: int, K: int, A: int, n: int, Gb: int):
    """Local factorize + fused per-shard partials (cnt_ok/sum/sumsq/min/
    max per agg column), then a cross-shard combine over the per-group
    partial tables.  Only the (Gb,*) group table replicates — rows never
    leave their shard.

    Two-level mesh: each shard's partial table is statically truncated
    to ``min(L, Gb)`` rows before the gather — valid local groups are a
    prefix and number at most min(L, G) <= min(L, Gb), so truncation
    drops only padding.  The gather itself is hierarchical (ICI-local,
    one per-slice block across DCN), which makes the group-by combine's
    cross-slice bytes O(Gb) — row-count independent — while the final
    segment combine still sees every shard's partials in flat order,
    so results stay bitwise-equal to the flat mesh (dropped padding
    contributes exact +0.0 / +-inf identity elements)."""
    L = B // n
    mesh = cloud().mesh
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

    def kern(keys, valid, vals):
        reps, slot_ok, cnt, part = _partials(keys, valid, vals, L)
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
        in_specs=(dp(None), dp(), dp(None)),
        out_specs=(P(), P(), P()), check_vma=False)


def _build_shard_merge_match(BL: int, BR: int, K: int, n: int,
                             all_x: bool, all_y: bool):
    """Fold-the-small-frame match: local left rows join the broadcast
    right key table per shard (factorize local-left + full-right into a
    shard-local code space — codes differ per shard but the match SETS
    and right-stable order do not).  psum combines the per-shard
    matched-right masks for ``all_y``."""
    Ll = BL // n
    mesh = cloud().mesh
    BIG = jnp.int32(1 << 30)

    def kern(lkeys, lvalid, rkeys, rvalid):
        keys = jnp.concatenate([lkeys, rkeys], axis=0)
        valid = jnp.concatenate([lvalid, rvalid])
        inv, _o, _g = _factorize_block(keys, valid, Ll + BR, K)
        lc = jnp.where(lvalid, inv[:Ll], BIG)
        rc = jnp.where(rvalid, inv[Ll:], BIG)
        r_order = jnp.argsort(rc, stable=True)
        r_sorted = jnp.take(rc, r_order)
        lo = jnp.searchsorted(r_sorted, lc, side="left")
        hi = jnp.searchsorted(r_sorted, lc, side="right")
        counts = jnp.where(lvalid, hi - lo, 0)
        counts_adj = jnp.where(lvalid & (counts == 0), 1, counts) \
            if all_x else counts
        offsets = jnp.cumsum(counts_adj)
        p = offsets[Ll - 1]
        l_sorted = jnp.sort(lc)
        plo = jnp.searchsorted(l_sorted, rc, side="left")
        phi = jnp.searchsorted(l_sorted, rc, side="right")
        matched = hpsum((rvalid & (phi > plo)).astype(jnp.int32),
                        "merge.match") > 0
        unmatched = rvalid & ~matched
        u_cnt = jnp.sum(unmatched.astype(jnp.int32)) if all_y else \
            jnp.int32(0)
        uord = jnp.argsort(jnp.where(unmatched,
                                     jnp.arange(BR, dtype=jnp.int32),
                                     BIG), stable=True)
        return (counts.astype(jnp.int32), offsets.astype(jnp.int32),
                lo.astype(jnp.int32), r_order.astype(jnp.int32),
                uord.astype(jnp.int32), hall_gather(p, "merge.counts"),
                u_cnt)

    dp = cloud().data_pspec
    return shard_map_compat(
        kern, mesh=mesh,
        in_specs=(dp(None), dp(), P(), P()),
        out_specs=(dp(), dp(), dp(), P(), P(),
                   P(), P()),
        check_vma=False)


def _build_shard_merge_emit(BL: int, BR: int, PL: int, PR: int, n: int,
                            NBl: int):
    """Emit the join rows per shard: pairs in local (= global) left-row
    order, left payload gathered SHARD-LOCALLY (left rows never leave
    home), right payload from the broadcast copy; ``all_y`` right-only
    rows append after the LAST shard's pairs so the concatenated ragged
    result is bitwise the host oracle's row order."""
    Ll = BL // n
    mesh = cloud().mesh

    def kern(counts, offsets, lo, r_order, uord, all_p, u_cnt,
             lpay, rpay):
        i = hshard_index()
        p = jnp.take(all_p, i)
        j = jnp.arange(NBl)
        row = jnp.searchsorted(offsets, j, side="right")
        ic = jnp.clip(row, 0, Ll - 1)
        base = jnp.where(ic > 0,
                         jnp.take(offsets, jnp.maximum(ic - 1, 0)), 0)
        k = j - base
        has = jnp.take(counts, ic) > 0
        rpos = jnp.clip(jnp.take(lo, ic) + k, 0, BR - 1)
        ri_m = jnp.where(has, jnp.take(r_order, rpos), -1)
        in_pairs = j < p
        is_last = i == (n - 1)
        u = jnp.clip(j - p, 0, BR - 1)
        ri_u = jnp.where(is_last & (j >= p) & (j < p + u_cnt),
                         jnp.take(uord, u), -1)
        li = jnp.where(in_pairs, i * Ll + ic, -1).astype(jnp.int32)
        ri = jnp.where(in_pairs, ri_m, ri_u).astype(jnp.int32)
        lg = jnp.take(lpay, jnp.clip(li - i * Ll, 0, Ll - 1), axis=0)
        lcols = jnp.where((li >= 0)[:, None], lg, jnp.nan)
        rg = jnp.take(rpay, jnp.clip(ri, 0, BR - 1), axis=0)
        rcols = jnp.where((ri >= 0)[:, None], rg, jnp.nan)
        cnt_out = p + jnp.where(is_last, u_cnt, 0)
        return li, ri, lcols, rcols, hall_gather(cnt_out, "merge.counts")

    dp = cloud().data_pspec
    return shard_map_compat(
        kern, mesh=mesh,
        in_specs=(dp(), dp(), dp(), P(), P(),
                  P(), P(), dp(None), P()),
        out_specs=(dp(), dp(), dp(None),
                   dp(None), P()),
        check_vma=False)


# ---------------------------------------------------------------------------
# global (PR 4) kernels — the H2O_TPU_SHARD_MUNGE=0 device path and the
# executor for median group-by's order-statistic pass
# ---------------------------------------------------------------------------


def _build_sort(B: int, K: int):
    def kern(keys, nrows):
        idx = jnp.arange(B)
        valid = idx < nrows
        # invalid/pad rows get +inf on every key -> stable-sort last
        cols = [jnp.where(valid, keys[:, k], jnp.inf) for k in range(K)]
        # lexsort: LAST key is primary; keys stack primary-first
        return jnp.lexsort(cols[::-1])
    return kern


def _build_factorize(B: int, K: int):
    """Rows -> dense group codes, sort-based.  Validity is an explicit
    mask so callers with non-prefix layouts (merge's concatenated
    left+right, ragged filtered frames) work too."""
    def kern(keys, valid):
        return _factorize_block(keys, valid, B, K)
    return kern


def _build_group_aggs(B: int, K: int, Gb: int, ops: Tuple[str, ...],
                      cards: Tuple[int, ...] = ()):
    """One fused pass: group key values + counts + every aggregate of
    the bundle.  ``vals`` is the (B, A) agg-column matrix (NA = NaN);
    ``cards`` carries the static per-agg categorical cardinality the
    segment-bincount mode kernel sizes its count table with (0 for
    non-mode aggs)."""
    def kern(keys, valid, inv, order, vals):
        gid_sorted = jnp.take(inv, order)           # nondecreasing
        bpos = jnp.searchsorted(gid_sorted, jnp.arange(Gb))
        start_rows = jnp.take(order, jnp.clip(bpos, 0, B - 1))
        keyvals = jnp.take(keys, start_rows, axis=0)
        vf = valid.astype(jnp.float32)
        counts = jax.ops.segment_sum(vf, inv, num_segments=Gb)
        outs = []
        for a, op in enumerate(ops):
            d = vals[:, a]
            ok = valid & ~jnp.isnan(d)
            okf = ok.astype(jnp.float32)
            di = jnp.where(ok, d, 0.0)
            cnt_ok = jax.ops.segment_sum(okf, inv, num_segments=Gb)
            ssum = jax.ops.segment_sum(di, inv, num_segments=Gb)
            if op in ("nrow", "count"):
                out = counts
            elif op == "sum":
                out = ssum
            elif op == "mean":
                out = ssum / jnp.maximum(cnt_ok, 1)
            elif op in ("sd", "var"):
                m = ssum / jnp.maximum(cnt_ok, 1)
                ss = jax.ops.segment_sum(di * di, inv, num_segments=Gb)
                var = ss / jnp.maximum(cnt_ok, 1) - m * m
                var = jnp.maximum(var * cnt_ok / jnp.maximum(cnt_ok - 1, 1),
                                  0.0)
                out = jnp.sqrt(var) if op == "sd" else var
            elif op in ("min", "max"):
                big = jnp.inf if op == "min" else -jnp.inf
                dm = jnp.where(ok, d, big)
                seg = jax.ops.segment_min if op == "min" else \
                    jax.ops.segment_max
                out = seg(dm, inv, num_segments=Gb)
                out = jnp.where(jnp.isfinite(out), out, jnp.nan)
            elif op == "median":
                from h2o_tpu.core.quantile import segment_median
                out = segment_median(d, ok, inv, B, Gb)
            elif op == "mode":
                from h2o_tpu.core.quantile import segment_mode
                out = segment_mode(d, ok, inv, Gb, cards[a])
            else:  # pragma: no cover — guarded by DEVICE_AGGS
                raise NotImplementedError(op)
            outs.append(out)
        return keyvals, counts, tuple(outs)
    return kern


def _build_filter(B: int):
    def kern(mask, nrows):
        idx = jnp.arange(B)
        keep = (mask > 0) & (idx < nrows)
        n_out = jnp.sum(keep.astype(jnp.int32))
        # kept rows first (in order), dropped rows after: a
        # cumsum-of-mask compaction expressed as a single stable rank
        order = jnp.argsort(jnp.where(keep, idx, B + idx))
        return n_out, order
    return kern


def _build_take(B: int, Pc: int, Bo: int):
    """Index-list row slicing as a device gather: out[j] = rows[idx[j]]
    for j < nidx, NaN-padded.  The gather runs on the row-sharded
    payload (GSPMD lowers it to on-device collectives — no host
    round-trip of any column)."""
    def kern(payload, idx, nidx):
        j = jnp.arange(Bo)
        src = jnp.clip(jnp.take(idx, jnp.clip(j, 0, Bo - 1)), 0, B - 1)
        out = jnp.take(payload, src, axis=0)
        return jnp.where((j < nidx)[:, None], out, jnp.nan)
    return kern


def _build_merge_match(PL: int, PR: int, all_x: bool, all_y: bool):
    BIG = jnp.int32(1 << 30)

    def kern(lcode, rcode, lvalid, rvalid):
        lc = jnp.where(lvalid, lcode, BIG)
        rc = jnp.where(rvalid, rcode, BIG)
        r_order = jnp.argsort(rc, stable=True)
        r_sorted = jnp.take(rc, r_order)
        lo = jnp.searchsorted(r_sorted, lc, side="left")
        hi = jnp.searchsorted(r_sorted, lc, side="right")
        counts = jnp.where(lvalid, hi - lo, 0)
        if all_x:                        # left outer: unmatched keep a slot
            counts_adj = jnp.where(lvalid & (counts == 0), 1, counts)
        else:
            counts_adj = counts
        offsets = jnp.cumsum(counts_adj)
        n_pairs = offsets[PL - 1]
        l_sorted = jnp.sort(lc)
        plo = jnp.searchsorted(l_sorted, rc, side="left")
        phi = jnp.searchsorted(l_sorted, rc, side="right")
        matched_r = rvalid & (phi > plo)
        unmatched = rvalid & ~matched_r
        u_cnt = jnp.sum(unmatched.astype(jnp.int32)) if all_y else \
            jnp.int32(0)
        uord = jnp.argsort(jnp.where(unmatched, jnp.arange(PR), BIG))
        n_out = n_pairs + u_cnt
        return n_out, n_pairs, counts, offsets, lo, r_order, uord
    return kern


def _build_merge_emit(PL: int, PR: int, NB: int):
    def kern(counts, offsets, lo, r_order, uord, n_pairs):
        j = jnp.arange(NB)
        i = jnp.searchsorted(offsets, j, side="right")
        ic = jnp.clip(i, 0, PL - 1)
        base = jnp.where(ic > 0, jnp.take(offsets, jnp.maximum(ic - 1, 0)),
                         0)
        k = j - base
        has = jnp.take(counts, ic) > 0
        rpos = jnp.clip(jnp.take(lo, ic) + k, 0, PR - 1)
        ri_m = jnp.where(has, jnp.take(r_order, rpos), -1)
        in_pairs = j < n_pairs
        u = jnp.clip(j - n_pairs, 0, PR - 1)
        ri_u = jnp.take(uord, u)
        li = jnp.where(in_pairs, ic, -1)
        ri = jnp.where(in_pairs, ri_m, ri_u)
        return li.astype(jnp.int32), ri.astype(jnp.int32)
    return kern


# ---------------------------------------------------------------------------
# key canonicalization + payload transport (eager, fused by XLA)
# ---------------------------------------------------------------------------


def _sort_key_matrix(fr: Frame, idxs: Sequence[int],
                     ascending: Sequence[bool]) -> jax.Array:
    """(P, K) transformed sort keys: descending negates, NAs (NaN and
    the categorical -1 code) become -inf so they group FIRST in both
    directions — np.lexsort/_sort_keys parity."""
    ks = []
    for j, asc in zip(idxs, ascending):
        v = fr.vecs[j]
        d = v.data.astype(jnp.float32)
        na = jnp.isnan(d)
        if v.is_categorical:
            na = na | (d < 0)
        k = d if asc else -d
        ks.append(jnp.where(na, -jnp.inf, k))
    return jnp.stack(ks, axis=1)


def _factor_key_matrix(fr: Frame, cols: Sequence[int]) -> jax.Array:
    """(P, K) group/join keys: cat codes as-is (NA=-1 is its own group,
    first), numeric NaN -> -inf sentinel (ONE NA group, first)."""
    ks = []
    for j in cols:
        v = fr.vecs[j]
        d = v.data.astype(jnp.float32)
        if not v.is_categorical:
            d = jnp.where(jnp.isnan(d), -jnp.inf, d)
        ks.append(d)
    return jnp.stack(ks, axis=1)


def _payload_matrix(fr: Frame, B: int) -> jax.Array:
    """(B, ncols) f32 transport matrix of every column (cat codes ride
    as exact small floats) for the row-moving collectives."""
    cols = []
    for v in fr.vecs:
        d = v.data.astype(jnp.float32)
        cols.append(_pad_rows(d, B, jnp.nan))
    return jnp.stack(cols, axis=1)


def _payload_to_vecs(out: jax.Array, fr: Frame, nrows: int,
                     shard_counts=None) -> List[Vec]:
    """Rebuild typed Vecs from a transport matrix (NaN padding becomes
    the per-type NA sentinel for categoricals)."""
    vecs = []
    for j, v in enumerate(fr.vecs):
        col = out[:, j]
        if v.is_categorical:
            col = jnp.where(jnp.isnan(col), -1.0, col).astype(jnp.int32)
        vecs.append(_mk_vec(col, v, nrows, shard_counts=shard_counts))
    return vecs


def _frame_bucket(fr: Frame) -> int:
    """Device row count a verb should run this frame at.  Canonical
    frames pad up to the pow2 shape bucket (padding appends masked rows
    at the global tail — re-homing them is free).  RAGGED frames must
    keep their exact kernel-shaped device length: their per-shard block
    boundaries (shard_counts geometry) would shift under any re-pad."""
    v0 = fr.vecs[0]
    if v0.is_ragged:
        return v0._device_rows()
    return _bucket_rows(fr.padded_rows)


# ---------------------------------------------------------------------------
# public verbs
# ---------------------------------------------------------------------------


def sort_frame(fr: Frame, idxs: Sequence[int],
               ascending: Sequence[bool]) -> Frame:
    """Device radix-sort analog.  Shard mode: ONE sample-sort collective
    moves each row over the interconnect at most twice and lands the
    frame in canonical sorted order — zero host pulls, bitwise host
    row-order parity.  Global mode: cached lexsort ranking + gather."""
    with DispatchStats.phase_scope(PHASE):
        if shard_munge_enabled():
            n = cloud().n_nodes
            B = _frame_bucket(fr)
            K = len(idxs)
            keys = _pad_rows(_sort_key_matrix(fr, idxs, ascending), B,
                             jnp.inf)
            payload = _payload_matrix(fr, B)
            valid = _pad_rows(fr.row_mask(), B, False)
            S = min(max(sort_oversample() * n, 4), B // n)
            out = _dispatch_kernel(
                "shard_sort", (B, K, fr.ncols, n, S),
                lambda: _build_shard_sort(B, K, fr.ncols, n, S),
                keys, payload, valid, site="munge.sort")
            return Frame(list(fr.names),
                         _payload_to_vecs(out, fr, fr.nrows))
        Pd = fr.vecs[0]._device_rows() or _row_pad(fr.nrows)
        B = _bucket_rows(Pd)
        keys = _pad_rows(_sort_key_matrix(fr, idxs, ascending), B, jnp.inf)
        nr = jnp.int32(fr.nrows)
        kern = cached_kernel(PHASE, "sort", (B, len(idxs)),
                             lambda: _build_sort(B, len(idxs)), keys, nr)
        order = kern(keys, nr)[:Pd]
        vecs = [_mk_vec(jnp.take(v.data, order, axis=0), v, fr.nrows)
                for v in fr.vecs]
        return Frame(list(fr.names), vecs)


def filter_rows(fr: Frame, mask: jax.Array) -> Frame:
    """Boolean-mask row compaction.  Shard mode: rows compact to a
    per-shard prefix and STAY on their home shard; the result is a
    ragged frame whose ``shard_counts`` (n small ints — the one host
    sync) drive downstream masking.  Global mode: rank-of-mask gather
    with the canonical prefix result."""
    with DispatchStats.phase_scope(PHASE):
        if shard_munge_enabled():
            n = cloud().n_nodes
            B = _frame_bucket(fr)
            m = _pad_rows(mask.astype(jnp.float32), B, 0.0)
            payload = _payload_matrix(fr, B)
            valid = _pad_rows(fr.row_mask(), B, False)
            out, counts = _dispatch_kernel(
                "shard_filter", (B, fr.ncols, n),
                lambda: _build_shard_filter(B, fr.ncols, n),
                m, valid, payload, site="munge.filter")
            sc = np.asarray(counts, np.int64)       # the one host sync
            n_out = int(sc.sum())
            return Frame(list(fr.names),
                         _payload_to_vecs(out, fr, n_out,
                                          shard_counts=sc))
        Pd = fr.vecs[0]._device_rows() or _row_pad(fr.nrows)
        B = _bucket_rows(Pd)
        m = _pad_rows(mask.astype(jnp.float32), B, 0.0)
        nr = jnp.int32(fr.nrows)
        kern = cached_kernel(PHASE, "filter", (B,),
                             lambda: _build_filter(B), m, nr)
        n_dev, order = kern(m, nr)
        n_out = int(n_dev)                       # the one host sync
        take = order[: _row_pad(n_out)]
        vecs = [_mk_vec(jnp.take(v.data, take, axis=0), v, n_out)
                for v in fr.vecs]
        return Frame(list(fr.names), vecs)


def repack_frame(fr: Frame) -> Frame:
    """Ragged -> canonical prefix IN PLACE via one balanced all_to_all
    (no host gather, no replication).  Called by Frame.repack()."""
    v0 = fr.vecs[0]
    if v0.shard_counts is None:
        return fr
    with DispatchStats.phase_scope(PHASE):
        n = len(v0.shard_counts)
        B = v0._device_rows()
        payload = _payload_matrix(fr, B)
        counts = jnp.asarray(v0.shard_counts, jnp.int32)
        out = _dispatch_kernel(
            "shard_repack", (B, fr.ncols, n),
            lambda: _build_shard_repack(B, fr.ncols, n),
            payload, counts, site="munge.repack")
        for j, v in enumerate(fr.vecs):
            col = out[:, j]
            if v.is_categorical:
                col = jnp.where(jnp.isnan(col), -1.0,
                                col).astype(jnp.int32)
            # clear raggedness BEFORE assigning (the data setter
            # re-accounts with the memory manager, and stale
            # shard_counts would record the old ragged valid bytes
            # for the now-canonical payload)
            v.shard_counts = None
            v.data = landing.reshard_rows(col)
            v.invalidate()
        return fr


def take_rows(fr: Frame, idx: np.ndarray) -> Frame:
    """Index-list row slicing as a device gather (AstRowSlice with an
    explicit numlist): the index list uploads once, every column
    gathers on device — no column round-trips host."""
    with DispatchStats.phase_scope(PHASE):
        fr.repack()                      # gather needs global positions
        B = _bucket_rows(fr.padded_rows)
        n_out = int(idx.shape[0])
        Bo = _bucket_rows(max(_row_pad(n_out), 1))
        payload = _payload_matrix(fr, B)
        idx_dev = jnp.asarray(
            np.pad(np.asarray(idx, np.int64), (0, Bo - n_out)),
            jnp.int32)
        out = _dispatch_kernel(
            "take", (B, fr.ncols, Bo),
            lambda: _build_take(B, fr.ncols, Bo),
            payload, idx_dev, jnp.int32(n_out), site="munge.take")
        Opad = _row_pad(n_out)
        return Frame(list(fr.names),
                     _payload_to_vecs(out[:Opad], fr, n_out))


def groupby_frame(fr: Frame, gcols: Sequence[int],
                  aggs: Sequence[Tuple[str, int, str]]) -> Frame:
    """AstGroup on device.  Shard mode (combinable aggs): per-shard
    factorize + fused partials, cross-shard combine of the partial
    tables — only the group table replicates.  Median/mode bundles
    (and ``H2O_TPU_SHARD_MUNGE=0``) run the global factorize + fused
    segment pass, with median as a device order-statistic kernel and
    mode as a segment-bincount + argmax kernel."""
    ops = tuple(a for a, _c, _na in aggs)
    if shard_munge_enabled() and all(a in COMBINABLE_AGGS for a in ops):
        return _shard_groupby(fr, gcols, aggs)
    return _global_groupby(fr, gcols, aggs)


def _shard_groupby(fr: Frame, gcols: Sequence[int],
                   aggs: Sequence[Tuple[str, int, str]]) -> Frame:
    with DispatchStats.phase_scope(PHASE):
        n = cloud().n_nodes
        B = _frame_bucket(fr)
        K = len(gcols)
        keys = _pad_rows(_factor_key_matrix(fr, gcols), B, jnp.inf)
        valid = _pad_rows(fr.row_mask(), B, False)
        g_dev = _dispatch_kernel(
            "shard_group_count", (B, K, n),
            lambda: _build_shard_group_count(B, K, n),
            keys, valid, site="munge.groupby")
        # flat mesh: the exact group count (the one host sync).
        # two-level: an upper bound (per-slice distinct counts summed
        # over DCN) — big enough to size the table bucket; the exact
        # count is recovered below from the combined counts column.
        G = int(g_dev)
        Gb = _bucket_rows(max(_row_pad(G), 1))
        acols = [fr.vecs[c].as_float() for _a, c, _na in aggs]
        A = len(acols)
        vals = _pad_rows(jnp.stack(acols, axis=1), B, jnp.nan) if acols \
            else jnp.zeros((B, 0), jnp.float32)
        keyvals, counts, parts = _dispatch_kernel(
            "shard_group_aggs", (B, K, A, n, Gb),
            lambda: _build_shard_group_aggs(B, K, A, n, Gb),
            keys, valid, vals, site="munge.groupby")
        if cloud().n_slices > 1:
            # real groups occupy a dense prefix of the combined table
            # with per-group row counts >= 1 (exact small integers in
            # f32); everything past them is zero-count padding
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
        return _group_table(fr, gcols, aggs, keyvals, counts, outs, G)


def _global_groupby(fr: Frame, gcols: Sequence[int],
                    aggs: Sequence[Tuple[str, int, str]]) -> Frame:
    with DispatchStats.phase_scope(PHASE):
        B = _frame_bucket(fr)
        K = len(gcols)
        keys = _pad_rows(_factor_key_matrix(fr, gcols), B, jnp.inf)
        valid = _pad_rows(fr.row_mask(), B, False)
        fact = cached_kernel(PHASE, "factorize", (B, K),
                             lambda: _build_factorize(B, K), keys, valid)
        inv, order, g_dev = fact(keys, valid)
        G = int(g_dev)                           # the one host sync
        Gb = _bucket_rows(max(_row_pad(G), 1))
        ops = tuple(a for a, _c, _na in aggs)
        cards = tuple(
            (len(fr.vecs[c].domain or ()) if a == "mode" else 0)
            for a, c, _na in aggs)
        acols = [fr.vecs[c].as_float() for _a, c, _na in aggs]
        vals = _pad_rows(jnp.stack(acols, axis=1), B, jnp.nan) if acols \
            else jnp.zeros((B, 0), jnp.float32)
        agg = cached_kernel(PHASE, "group_aggs", (B, K, Gb, ops, cards),
                            lambda: _build_group_aggs(B, K, Gb, ops,
                                                      cards),
                            keys, valid, inv, order, vals)
        keyvals, counts, outs = agg(keys, valid, inv, order, vals)
        return _group_table(fr, gcols, aggs, keyvals, counts, list(outs),
                            G)


def _group_table(fr: Frame, gcols, aggs, keyvals, counts, outs,
                 G: int) -> Frame:
    """Assemble the (small, replicated) group table as a Frame."""
    Gpad = _row_pad(G)
    names: List[str] = []
    vecs: List[Vec] = []
    for k, j in enumerate(gcols):
        v = fr.vecs[j]
        col = keyvals[:, k][:Gpad]
        if v.is_categorical:
            vecs.append(_mk_vec(col.astype(jnp.int32), v, G))
        else:
            # NA sentinel back to NaN in the output key column
            col = jnp.where(jnp.isneginf(col), jnp.nan, col)
            vecs.append(_mk_vec(col, v, G))
        names.append(fr.names[j])
    for (a, col_i, _na), out in zip(aggs, outs):
        names.append(f"{a}_{fr.names[col_i]}")
        vecs.append(Vec(landing.reshard_rows(out[:Gpad]), nrows=G))
    return Frame(names, vecs)


def _merge_key_cols(L: Frame, R: Frame, by_x: Sequence[int],
                    by_y: Sequence[int]):
    """Per-by-col union domains + device-remapped right key columns.
    Categorical keys match by LABEL through a host-built LUT over the
    (small) domain metadata — never per-row."""
    unions = {}
    r_keymap = {}
    lk_cols, rk_cols = [], []
    for jx, jy in zip(by_x, by_y):
        vl, vr = L.vecs[jx], R.vecs[jy]
        if vl.is_categorical:
            have = set(vl.domain)
            dom = list(vl.domain) + [d for d in vr.domain
                                     if d not in have]
            unions[jx] = dom
            pos = {d: i for i, d in enumerate(dom)}
            lut = np.asarray([pos[d] for d in vr.domain], np.int32) \
                if vr.domain else np.zeros(1, np.int32)
            lut_dev = jnp.asarray(lut)
            rc = vr.data
            remapped = jnp.where(
                rc < 0, jnp.int32(-1),
                jnp.take(lut_dev, jnp.clip(rc, 0, len(lut) - 1)))
            r_keymap[jy] = remapped
            lk_cols.append(vl.data.astype(jnp.float32))
            rk_cols.append(remapped.astype(jnp.float32))
        else:
            dl = vl.data.astype(jnp.float32)
            dr = vr.data.astype(jnp.float32)
            r_keymap[jy] = vr.data
            lk_cols.append(jnp.where(jnp.isnan(dl), -jnp.inf, dl))
            rk_cols.append(jnp.where(jnp.isnan(dr), -jnp.inf, dr))
    return unions, r_keymap, lk_cols, rk_cols


def merge_frames(L: Frame, R: Frame, all_x: bool, all_y: bool,
                 by_x: Sequence[int], by_y: Sequence[int]) -> Frame:
    """Sorted join on device (BinaryMerge analog).  Shard mode: the
    fold-the-small-frame join — left rows stay home-sharded, the right
    key table broadcasts, per-shard emissions concatenate to the host
    oracle's exact row order and the result stays ragged-sharded.
    Global mode: the PR 4 shared-code-space join."""
    if shard_munge_enabled():
        return _shard_merge(L, R, all_x, all_y, by_x, by_y)
    return _global_merge(L, R, all_x, all_y, by_x, by_y)


def _shard_merge(L: Frame, R: Frame, all_x: bool, all_y: bool,
                 by_x: Sequence[int], by_y: Sequence[int]) -> Frame:
    with DispatchStats.phase_scope(PHASE):
        n = cloud().n_nodes
        BL = _frame_bucket(L)
        BR = _frame_bucket(R)
        unions, r_keymap, lk_cols, rk_cols = _merge_key_cols(
            L, R, by_x, by_y)
        K = len(by_x)
        lkeys = _pad_rows(jnp.stack(lk_cols, axis=1), BL, jnp.inf)
        rkeys = _pad_rows(jnp.stack(rk_cols, axis=1), BR, jnp.inf)
        lvalid = _pad_rows(L.row_mask(), BL, False)
        rvalid = _pad_rows(R.row_mask(), BR, False)
        counts, offsets, lo, r_order, uord, all_p, u_dev = \
            _dispatch_kernel(
                "shard_merge_match", (BL, BR, K, n, all_x, all_y),
                lambda: _build_shard_merge_match(BL, BR, K, n, all_x,
                                                 all_y),
                lkeys, lvalid, rkeys, rvalid, site="munge.merge")
        p_shard = np.asarray(all_p, np.int64)   # the one host sync
        u_cnt = int(u_dev)
        n_out = int(p_shard.sum()) + u_cnt
        cap = int(max(p_shard.max(initial=0), p_shard[-1] + u_cnt, 1))
        NBl = max(_bucket_rows(cap * n) // n, 1)
        r_idx = [j for j in range(R.ncols) if j not in set(by_y)]
        lpay = _payload_matrix(L, BL)
        rpay = jnp.stack([_pad_rows(R.vecs[j].data.astype(jnp.float32),
                                    BR, jnp.nan) for j in r_idx],
                         axis=1) if r_idx else \
            jnp.zeros((BR, 0), jnp.float32)
        li, ri, lcols, rcols, cnt_out = _dispatch_kernel(
            "shard_merge_emit",
            (BL, BR, L.ncols, len(r_idx), n, NBl),
            lambda: _build_shard_merge_emit(BL, BR, L.ncols,
                                            len(r_idx), n, NBl),
            counts, offsets, lo, r_order, uord, all_p, u_dev,
            lpay, rpay, site="munge.merge")
        sc = np.asarray(cnt_out, np.int64)
        rc = jnp.clip(ri, 0, max(BR - 1, 0))

        names: List[str] = []
        vecs: List[Vec] = []
        for j, nm in enumerate(L.names):
            v = L.vecs[j]
            out = lcols[:, j]
            if j in by_x and u_cnt > 0:
                # right-only rows: key value from the right frame (cat
                # codes already remapped into the union domain)
                jy = by_y[by_x.index(j)]
                rg = jnp.take(r_keymap[jy].astype(jnp.float32), rc,
                              axis=0)
                out = jnp.where(li >= 0, out,
                                jnp.where(ri >= 0, rg, jnp.nan))
            if v.is_categorical:
                cat = jnp.where(jnp.isnan(out), -1.0,
                                out).astype(jnp.int32)
                dom = unions[j] if j in by_x and u_cnt > 0 \
                    else list(v.domain)
                arr = landing.reshard_rows(cat)
                vecs.append(Vec(arr, T_CAT, nrows=n_out, domain=dom,
                                shard_counts=sc))
            else:
                vecs.append(_mk_vec(out, v, n_out, shard_counts=sc))
            names.append(nm)
        for c_i, j in enumerate(r_idx):
            v = R.vecs[j]
            nm = R.names[j]
            out = rcols[:, c_i]
            if v.is_categorical:
                cat = jnp.where(jnp.isnan(out), -1.0,
                                out).astype(jnp.int32)
                arr = landing.reshard_rows(cat)
                vecs.append(Vec(arr, T_CAT, nrows=n_out,
                                domain=list(v.domain), shard_counts=sc))
            else:
                vecs.append(_mk_vec(out, v, n_out, shard_counts=sc))
            names.append(nm if nm not in names else f"{nm}_y")
        return Frame(names, vecs)


def _global_merge(L: Frame, R: Frame, all_x: bool, all_y: bool,
                  by_x: Sequence[int], by_y: Sequence[int]) -> Frame:
    with DispatchStats.phase_scope(PHASE):
        PL = L.vecs[0].data.shape[0]
        PR = R.vecs[0].data.shape[0]
        unions, r_keymap, lk_cols, rk_cols = _merge_key_cols(
            L, R, by_x, by_y)
        K = len(by_x)
        lvalid = _pad_rows(L.row_mask(), PL, False)
        rvalid = _pad_rows(R.row_mask(), PR, False)
        B = _bucket_rows(PL + PR)
        # stitch left+right via scatter-into-fresh (sharded-operand
        # concatenate miscompiles on multi-axis meshes — _pad_rows note)
        K_ = len(lk_cols)
        ck = jnp.full((B, K_), jnp.inf, jnp.float32)
        ck = ck.at[:PL].set(jnp.stack(lk_cols, axis=1))
        ck = ck.at[PL: PL + PR].set(jnp.stack(rk_cols, axis=1))
        cv = jnp.zeros((B,), bool)
        cv = cv.at[:PL].set(lvalid)
        cv = cv.at[PL: PL + PR].set(rvalid)
        fact = cached_kernel(PHASE, "factorize", (B, K),
                             lambda: _build_factorize(B, K), ck, cv)
        inv, _order, _g = fact(ck, cv)
        lcode, rcode = inv[:PL], inv[PL: PL + PR]
        match = cached_kernel(PHASE, "merge_match",
                              (PL, PR, all_x, all_y),
                              lambda: _build_merge_match(PL, PR, all_x,
                                                         all_y),
                              lcode, rcode, lvalid, rvalid)
        n_dev, np_dev, counts, offsets, lo, r_order, uord = \
            match(lcode, rcode, lvalid, rvalid)
        n_out = int(n_dev)                       # the one host sync
        n_pairs = int(np_dev)
        u_cnt = n_out - n_pairs
        NB = _bucket_rows(max(_row_pad(n_out), 1))
        npdev = jnp.int32(n_pairs)
        emit = cached_kernel(PHASE, "merge_emit", (PL, PR, NB),
                             lambda: _build_merge_emit(PL, PR, NB),
                             counts, offsets, lo, r_order, uord, npdev)
        li, ri = emit(counts, offsets, lo, r_order, uord, npdev)
        Ppad = _row_pad(n_out)
        li, ri = li[:Ppad], ri[:Ppad]
        lc = jnp.clip(li, 0, max(PL - 1, 0))
        rc = jnp.clip(ri, 0, max(PR - 1, 0))

        names: List[str] = []
        vecs: List[Vec] = []
        r_by = set(by_y)
        for j, n in enumerate(L.names):
            v = L.vecs[j]
            lg = jnp.take(v.data, lc, axis=0)
            if v.is_categorical:
                out = jnp.where(li >= 0, lg, -1).astype(jnp.int32)
                dom = list(v.domain)
                if j in by_x and u_cnt > 0:
                    jy = by_y[by_x.index(j)]
                    dom = unions[j]
                    rg = jnp.take(r_keymap[jy], rc, axis=0)
                    out = jnp.where(li >= 0, out,
                                    jnp.where(ri >= 0, rg, -1)
                                    ).astype(jnp.int32)
                arr = landing.reshard_rows(out)
                vecs.append(Vec(arr, T_CAT, nrows=n_out, domain=dom))
            else:
                out = jnp.where(li >= 0, lg, jnp.nan)
                if j in by_x and u_cnt > 0:
                    jy = by_y[by_x.index(j)]
                    rg = jnp.take(r_keymap[jy].astype(jnp.float32), rc,
                                  axis=0)
                    out = jnp.where(li >= 0, out,
                                    jnp.where(ri >= 0, rg, jnp.nan))
                vecs.append(Vec(landing.reshard_rows(out),
                                v.type, nrows=n_out))
            names.append(n)
        for j, n in enumerate(R.names):
            if j in r_by:
                continue
            v = R.vecs[j]
            rg = jnp.take(v.data, rc, axis=0)
            if v.is_categorical:
                out = jnp.where(ri >= 0, rg, -1).astype(jnp.int32)
                arr = landing.reshard_rows(out)
                vecs.append(Vec(arr, T_CAT, nrows=n_out,
                                domain=list(v.domain)))
            else:
                out = jnp.where(ri >= 0, rg, jnp.nan)
                vecs.append(Vec(landing.reshard_rows(out),
                                v.type, nrows=n_out))
            names.append(n if n not in names else f"{n}_y")
        return Frame(names, vecs)


def merge_device_ok(L: Frame, R: Frame, by_x: Sequence[int],
                    by_y: Sequence[int]) -> bool:
    """Device join requires device-resident frames and type-consistent
    key pairs (cat<->cat matches by label via domain LUT; num<->num by
    value; mixed pairs fall back to the host string-join path)."""
    if not (frame_device_ok(L) and frame_device_ok(R)):
        return False
    return all(L.vecs[jx].is_categorical == R.vecs[jy].is_categorical
               for jx, jy in zip(by_x, by_y))
