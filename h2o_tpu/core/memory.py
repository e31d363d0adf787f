"""Tiered column store — the user-mode swap of the reference, in three tiers.

Reference: water/Cleaner.java:10-12 ("user-mode swap-to-disk": tracks the
heap budget and swaps cold Values to ice_root under pressure) +
water/MemoryManager.java (malloc with OOM callbacks).

TPU-native, the managed heap spans THREE tiers:

- **HBM** — a Vec's live device payload.  Every frame column registers
  its device bytes here; when an allocation would exceed the budget
  (``H2O_TPU_HBM_BUDGET`` bytes, or ``OptArgs.hbm_budget``; 0 =
  unlimited), the least-recently-used
  resident columns are spilled: the device array is dropped (XLA frees
  the HBM) after a host copy is parked on the Vec.
- **Host** — the parked copy, held as :class:`HostBlocks`: the column
  chunked into SHARD-ALIGNED row blocks of ``H2O_TPU_TIER_BLOCK_ROWS``
  per-shard rows, so the tree driver can stream one block window at a
  time back through training without rehydrating the column (and the
  landing layer puts each block's shard straight on its home device).
  T_TIME/T_STR host-only residues (:class:`HostResidue`) live in this
  tier too — they page host ⇄ persist but never touch HBM.
- **Persist** — cold host blocks written to ``ice_root/tier`` (the
  reference's ice) under ``H2O_TPU_HOST_BUDGET`` pressure, demand-paged
  back block-at-a-time on access.

The next access reloads transparently through the same accounting — the
Value.isPersisted / reload-on-touch cycle of the reference.  Transient
compute buffers (binned matrices, histograms, model state) are XLA's to
manage; the data plane — the part that scales with row count — is what
lives here, exactly as the reference's Cleaner only swaps DKV Values.

This is the ACCOUNTING half of the memory story; the RECOVERY half is
core/oom.py: on a device RESOURCE_EXHAUSTED, the OOM ladder's first
rung calls :meth:`MemoryManager.sweep` (spill everything cold) and
retries the dispatch; the tiered streaming paths add a shrink rung that
halves the resident block window.  ALL spill/persist I/O runs OUTSIDE
the manager lock (candidates are collected under it, GL401/GL403
two-phase discipline), so a Vec whose spill/reload path re-enters the
manager can never deadlock against a concurrent sweep.

Prefetch telemetry (hits/misses/stalls, noted by the block streamer in
core/mrtask.py) and per-tier resident bytes surface in :meth:`stats`,
``GET /3/Resilience``, and the conftest session summary line.
"""

from __future__ import annotations

import os
import pickle
import threading
import weakref
from typing import List, Optional

import numpy as np

from h2o_tpu.core.lockwitness import make_lock, make_rlock
from h2o_tpu.core.log import get_logger

log = get_logger("memory")


# -- tier knobs (defaults + docs live in h2o_tpu/config.py) ----------------
from h2o_tpu.config import (prefetch_depth, tenant_highwater,  # noqa: F401
                            tier_block_rows)


def _tenant_share(name: Optional[str]) -> float:
    """The tenant's reserved HBM fraction (0 when unknown/unreserved).
    Read OUTSIDE the manager lock — the tenant registry lives in the
    DKV, and the manager lock must never nest inside a DKV read."""
    if not name:
        return 0.0
    try:
        from h2o_tpu.core.tenant import get_tenant
        t = get_tenant(name)
        return float(t.hbm_share) if t is not None else 0.0
    except Exception:  # noqa: BLE001 — quota lookup must never fail an
        # allocation; an unresolvable tenant just has no reservation
        return 0.0


def _tier_dir() -> str:
    from h2o_tpu.core.cloud import Cloud
    inst = Cloud._instance
    root = (inst.args.ice_root if inst is not None
            else os.environ.get("H2O_TPU_ICE_ROOT", "/tmp/h2o_tpu"))
    d = os.path.join(root, "tier")
    os.makedirs(d, exist_ok=True)
    return d


_seq_lock = threading.Lock()
_seq = 0


def _next_seq() -> int:
    global _seq
    with _seq_lock:
        _seq += 1
        return _seq


def _rm_files(paths: List[Optional[str]]) -> None:
    for p in paths:
        if p:
            try:
                os.remove(p)
            except OSError:
                pass


class HostBlocks:
    """A parked host column, chunked into shard-aligned row blocks.

    The device payload's host copy (capacity rows, already padded to the
    mesh row quantum) is viewed as ``(n_shards, L, ...)`` and split
    along the per-shard axis into blocks of :func:`tier_block_rows`
    rows.  Block ``b`` therefore holds per-shard rows ``[b*q, (b+1)*q)``
    of EVERY shard — exactly one streaming window — so demand paging,
    prefetch, and the blocked training loop all move the same unit.

    Individual blocks persist to ``ice_root/tier`` under host-budget
    pressure and page back on access; :meth:`to_ndarray` rehydrates the
    original capacity-rows array bit-for-bit.
    """

    def __init__(self, arr: np.ndarray, n_shards: int = 0):
        arr = np.asarray(arr)
        if n_shards <= 0 or arr.shape[0] % max(n_shards, 1):
            n_shards = 1
        self.shape = arr.shape
        self.dtype = arr.dtype
        self.nbytes = int(arr.nbytes)
        self._n = n_shards
        self._L = arr.shape[0] // n_shards
        self._q = max(1, min(tier_block_rows(), self._L))
        view = arr.reshape((n_shards, self._L) + arr.shape[1:])
        self._blocks: List[Optional[np.ndarray]] = [
            np.ascontiguousarray(view[:, i:i + self._q])
            for i in range(0, self._L, self._q)]
        self._paths: List[Optional[str]] = [None] * len(self._blocks)
        self._pbytes: List[int] = [0] * len(self._blocks)
        self._io = threading.Lock()   # serializes persist/page I/O
        self._tag = _next_seq()
        # file cleanup must not resurrect self: finalize on the list obj
        self._fin = weakref.finalize(self, _rm_files, self._paths)

    # -- geometry ----------------------------------------------------------

    @property
    def block_count(self) -> int:
        return len(self._blocks)

    @property
    def block_rows(self) -> int:
        """Per-shard rows per block (the residency quantum)."""
        return self._q

    @property
    def n_shards(self) -> int:
        return self._n

    @property
    def resident_nbytes(self) -> int:
        return sum(int(b.nbytes) for b in self._blocks if b is not None)

    @property
    def persisted_nbytes(self) -> int:
        return sum(self._pbytes)

    # -- paging ------------------------------------------------------------

    def block(self, i: int) -> np.ndarray:
        """Block ``i`` as ``(n_shards, q_i, ...)`` — demand-paged in."""
        b = self._blocks[i]
        if b is not None:
            return b
        with self._io:
            b = self._blocks[i]
            if b is None:
                b = np.load(self._paths[i])
                self._blocks[i] = b
                nb = self._pbytes[i]
                self._pbytes[i] = 0
                manager()._note_page_in(int(b.nbytes), freed_persist=nb)
        return b

    def slice_shard_rows(self, w0: int, w1: int) -> np.ndarray:
        """Per-shard row window ``[w0, w1)`` across all shards, shape
        ``(n_shards, w1-w0, ...)`` — pages in exactly the covering
        blocks (the demand half of demand+prefetch)."""
        parts = []
        b0, b1 = w0 // self._q, (w1 - 1) // self._q
        for b in range(b0, b1 + 1):
            lo, hi = b * self._q, min((b + 1) * self._q, self._L)
            blk = self.block(b)
            s0, s1 = max(w0, lo) - lo, min(w1, hi) - lo
            parts.append(blk[:, s0:s1])
        out = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=1)
        return np.ascontiguousarray(out)

    def to_ndarray(self) -> np.ndarray:
        """Rehydrate the full capacity-rows array (bitwise)."""
        blocks = [self.block(i) for i in range(len(self._blocks))]
        full = blocks[0] if len(blocks) == 1 else np.concatenate(
            blocks, axis=1)
        return np.ascontiguousarray(full.reshape(self.shape))

    def _persist(self) -> int:
        """Write every resident block to the persist tier, freeing host
        RAM.  Called OUTSIDE the manager lock (two-phase LRU)."""
        freed = 0
        wrote = 0
        with self._io:
            for i, b in enumerate(self._blocks):
                if b is None:
                    continue
                if self._paths[i] is None:
                    self._paths[i] = os.path.join(
                        _tier_dir(), "hb%d_%d.npy" % (self._tag, i))
                np.save(self._paths[i], b)
                self._pbytes[i] = int(b.nbytes)
                self._blocks[i] = None
                freed += self._pbytes[i]
                wrote += 1
        if freed:
            manager()._note_pages_out(wrote, freed)
        return freed


class HostResidue:
    """A host-ONLY column payload in the tier model (never HBM).

    T_TIME keeps an exact float64 copy (device f32 loses ms precision,
    PR 9) and T_STR/T_UUID keep a Python list; both now tier
    host ⇄ persist like any cold column: under ``H2O_TPU_HOST_BUDGET``
    pressure the payload pickles/saves to ``ice_root/tier`` and pages
    back on the next access.  List byte size is an estimate (64 B/item)
    — accounting, not a malloc."""

    def __init__(self, payload):
        self._payload = payload
        self._path: Optional[str] = None
        self._pbytes = 0
        self._io = threading.Lock()
        self._tag = _next_seq()
        self._is_np = isinstance(payload, np.ndarray)
        self._paths: List[Optional[str]] = [None]
        self._fin = weakref.finalize(self, _rm_files, self._paths)
        self.nbytes = (int(payload.nbytes) if self._is_np
                       else 64 * len(payload))

    @property
    def resident_nbytes(self) -> int:
        return self.nbytes if self._payload is not None else 0

    @property
    def persisted_nbytes(self) -> int:
        return self._pbytes

    def get(self):
        p = self._payload
        if p is not None:
            manager().touch_host(self)
            return p
        with self._io:
            if self._payload is None:
                if self._is_np:
                    self._payload = np.load(self._paths[0])
                else:
                    with open(self._paths[0], "rb") as f:
                        self._payload = pickle.load(f)
                nb = self._pbytes
                self._pbytes = 0
                manager()._note_page_in(self.nbytes, freed_persist=nb)
            return self._payload

    def _persist(self) -> int:
        with self._io:
            if self._payload is None:
                return 0
            if self._paths[0] is None:
                ext = "npy" if self._is_np else "pkl"
                self._paths[0] = os.path.join(
                    _tier_dir(), "hr%d.%s" % (self._tag, ext))
            if self._is_np:
                np.save(self._paths[0], self._payload)
            else:
                with open(self._paths[0], "wb") as f:
                    pickle.dump(self._payload, f,
                                protocol=pickle.HIGHEST_PROTOCOL)
            self._pbytes = self.nbytes
            self._payload = None
        manager()._note_pages_out(1, self._pbytes)
        return self._pbytes


class MemoryManager:
    """Budgeted tier accounting + LRU movement for Vec payloads."""

    def __init__(self, budget_bytes: int = 0,
                 host_budget_bytes: Optional[int] = None):
        self.budget = int(budget_bytes)
        if host_budget_bytes is None:
            from h2o_tpu.config import host_budget
            host_budget_bytes = host_budget()
        self.host_budget = int(host_budget_bytes)
        self._lock = make_rlock("memory.MemoryManager._lock")
        # insertion-ordered dicts of weakref -> nbytes; order = LRU
        self._resident: "dict[weakref.ref, int]" = {}
        # device-CAPACITY vs VALID bytes: ragged columns (per-shard
        # valid prefixes) occupy their full padded buffer in HBM but
        # only shard_counts rows are real — _resident holds capacity
        # (what eviction frees), _valid holds real-row bytes (what
        # pressure() drives off)
        self._valid: "dict[weakref.ref, int]" = {}
        self._host: "dict[weakref.ref, int]" = {}
        # tenant ISOLATION: each registration is tagged with the tenant
        # context of the allocating thread (None = unowned/system).
        # Eviction pressure from tenant A selects A's own (or unowned)
        # cold blocks first; another tenant's blocks become eligible
        # only past the global high-water mark, and every such spill is
        # counted — cross_tenant_below_highwater is the soak's
        # must-be-zero invariant.
        self._tenant_of: "dict[weakref.ref, Optional[str]]" = {}
        self._tenant_spills: "dict[str, int]" = {}
        self.cross_tenant_evictions = 0
        self.cross_tenant_below_highwater = 0
        self.spill_count = 0
        self.reload_count = 0
        self.pages_in = 0
        self.pages_out = 0
        self.persist_count = 0
        self.persist_reloads = 0
        self.prefetch_hit_count = 0
        self.prefetch_miss_count = 0
        self.demand_stall_count = 0
        self.peak_resident = 0

    # -- HBM tier ----------------------------------------------------------

    def _prune(self) -> None:
        dead = [r for r in self._resident if r() is None]
        for r in dead:
            self._resident.pop(r, None)
            self._valid.pop(r, None)
            self._tenant_of.pop(r, None)

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            self._prune()
            return sum(self._resident.values())

    def register(self, vec, nbytes: int,
                 valid_nbytes: Optional[int] = None) -> None:
        """A Vec's device payload came alive; evict LRU columns if the
        budget is exceeded (Cleaner sweep).  The spill itself runs
        OUTSIDE the manager lock (see _spill_lru).  ``valid_nbytes``
        is the real-row subset of ``nbytes`` (ragged columns pad to
        device capacity); defaults to ``nbytes`` for dense payloads.

        The payload is tagged with the allocating thread's TENANT; a
        tenant with a reserved ``hbm_share`` that exceeds it spills its
        OWN cold blocks first (strict), then the global budget is
        enforced with the two-pass isolation policy (own/unowned
        first; cross-tenant only past high-water)."""
        from h2o_tpu.core.tenant import current_tenant
        tenant = current_tenant()
        share = _tenant_share(tenant)
        with self._lock:
            self._prune()
            r = weakref.ref(vec)
            vec._mm_ref = r              # O(1) touch/unregister handle
            self._resident[r] = int(nbytes)
            self._valid[r] = int(nbytes if valid_nbytes is None
                                 else min(valid_nbytes, nbytes))
            self._tenant_of[r] = tenant
            total = sum(self._resident.values())
            if total > self.peak_resident:
                self.peak_resident = total
            need = (total - self.budget) if self.budget > 0 else 0
            own_need = 0
            if share > 0 and self.budget > 0:
                mine = sum(nb for rr, nb in self._resident.items()
                           if self._tenant_of.get(rr) == tenant)
                own_need = mine - int(share * self.budget)
        if own_need > 0:
            self._spill_lru(own_need, exclude=vec, tenant=tenant,
                            own_only=True)
        if need > 0:
            self._spill_lru(need, exclude=vec, tenant=tenant)

    def touch(self, vec) -> None:
        """Mark recently used (moves to the MRU end)."""
        r = getattr(vec, "_mm_ref", None)
        if r is None:
            return
        with self._lock:
            if r in self._resident:
                self._resident[r] = self._resident.pop(r)

    def unregister(self, vec) -> None:
        r = getattr(vec, "_mm_ref", None)
        if r is None:
            return
        with self._lock:
            self._resident.pop(r, None)
            self._valid.pop(r, None)
            self._tenant_of.pop(r, None)

    def _spill_lru(self, need_bytes: int, exclude=None,
                   tenant: Optional[str] = None, own_only: bool = False,
                   ignore_tenants: bool = False) -> int:
        """Spill the coldest columns until ``need_bytes`` are freed.

        Two-phase: candidates are COLLECTED under the manager lock, but
        each ``v._spill()`` (the device-array drop, which takes the
        Vec's own spill lock and may re-enter manager accounting) runs
        OUTSIDE it — a Vec whose spill/reload path touches the manager
        can never deadlock against a concurrent sweep.

        Tenant isolation (two-pass victim selection, LRU within each):

        1. blocks owned by the requesting ``tenant`` or by nobody
           (``own_only`` restricts to the tenant's own — the
           share-reservation path, where unowned spills wouldn't lower
           the tenant's usage anyway);
        2. ONLY when global residency is past
           ``H2O_TPU_TENANT_HIGHWATER × budget`` (survival beats
           isolation): other tenants' blocks, each successful spill
           counted as a ``cross_tenant_eviction``.

        ``ignore_tenants`` (the OOM-ladder emergency sweep) restores
        flat LRU: a RESOURCE_EXHAUSTED dispatch outranks isolation and
        its spills are not cross-tenant accounting events.
        """
        with self._lock:
            total = sum(self._resident.values())
            tagged = any(t is not None for t in self._tenant_of.values())
            flat = ignore_tenants or not tagged
            allow_cross = (not flat and not own_only and self.budget > 0
                           and total > tenant_highwater() * self.budget)
            cands = []
            planned = 0
            seen = set()

            def _collect(pred, cross: bool) -> None:
                nonlocal planned
                for r in list(self._resident):  # LRU order
                    if planned >= need_bytes:
                        return
                    if r in seen:
                        continue
                    v = r()
                    if v is None or v is exclude:
                        continue
                    tag = self._tenant_of.get(r)
                    if not pred(tag):
                        continue
                    seen.add(r)
                    cands.append((r, v, self._resident[r], tag, cross))
                    planned += self._resident[r]

            if flat:
                _collect(lambda tag: True, cross=False)
            else:
                if own_only:
                    _collect(lambda tag: tag == tenant, cross=False)
                else:
                    _collect(lambda tag: tag == tenant or tag is None,
                             cross=False)
                if allow_cross and planned < need_bytes:
                    _collect(lambda tag: True, cross=True)
        freed = 0
        for r, v, nb, tag, cross in cands:
            if v._spill():                      # drops the device array
                with self._lock:
                    if self._resident.pop(r, None) is not None:
                        self.spill_count += 1
                        freed += nb
                        if tag is not None:
                            self._tenant_spills[tag] = \
                                self._tenant_spills.get(tag, 0) + 1
                        if cross:
                            self.cross_tenant_evictions += 1
                            if not allow_cross:  # defensive: impossible
                                self.cross_tenant_below_highwater += 1
                    self._valid.pop(r, None)
                    self._tenant_of.pop(r, None)
        if freed:
            log.info("spilled %d bytes of cold columns to host "
                     "(budget %d)", freed, self.budget)
        return freed

    def demote(self, vec) -> int:
        """Proactively spill ONE column HBM → host (the blocked training
        paths park their sources before streaming windows back)."""
        r = getattr(vec, "_mm_ref", None)
        with self._lock:
            nb = self._resident.get(r, 0) if r is not None else 0
        if not vec._spill():
            return 0
        with self._lock:
            if r is not None and self._resident.pop(r, None) is not None:
                self.spill_count += 1
            if r is not None:
                self._valid.pop(r, None)
                self._tenant_of.pop(r, None)
        return nb

    def sweep(self) -> int:
        """Emergency Cleaner sweep (OOM-ladder rung (a), core/oom.py):
        spill EVERY resident column, returning the bytes freed — the
        user-mode-swap answer to a RESOURCE_EXHAUSTED dispatch.
        Bypasses tenant isolation: survival outranks fairness, and an
        emergency sweep is not a cross-tenant accounting event."""
        return self._spill_lru(1 << 62, ignore_tenants=True)

    def note_reload(self) -> None:
        self.reload_count += 1

    # -- host tier ---------------------------------------------------------

    def _prune_host(self) -> None:
        dead = [r for r in self._host if r() is None]
        for r in dead:
            self._host.pop(r, None)

    def register_host(self, obj, nbytes: int) -> None:
        """A host-tier payload (HostBlocks park or HostResidue) came
        alive; persist LRU payloads if the host budget is exceeded."""
        with self._lock:
            self._prune_host()
            r = weakref.ref(obj)
            obj._mmh_ref = r
            self._host[r] = int(nbytes)
            need = 0
            if self.host_budget > 0:
                live = sum(o.resident_nbytes for o in
                           (w() for w in self._host) if o is not None)
                need = live - self.host_budget
        if need > 0:
            self._persist_lru(need, exclude=obj)

    def touch_host(self, obj) -> None:
        r = getattr(obj, "_mmh_ref", None)
        if r is None:
            return
        with self._lock:
            if r in self._host:
                self._host[r] = self._host.pop(r)

    def unregister_host(self, obj) -> None:
        r = getattr(obj, "_mmh_ref", None)
        if r is None:
            return
        with self._lock:
            self._host.pop(r, None)

    def _persist_lru(self, need_bytes: int, exclude=None) -> int:
        """Persist the coldest host payloads until ``need_bytes`` are
        freed — same two-phase discipline as :meth:`_spill_lru`: the
        disk writes run OUTSIDE the manager lock."""
        with self._lock:
            cands = []
            planned = 0
            for r in list(self._host):          # LRU order
                if planned >= need_bytes:
                    break
                o = r()
                if o is None or o is exclude:
                    continue
                nb = o.resident_nbytes
                if nb <= 0:
                    continue
                cands.append((r, o))
                planned += nb
        freed = 0
        for r, o in cands:
            got = o._persist()                  # disk I/O, no locks held
            if got:
                freed += got
                with self._lock:
                    self.persist_count += 1
        if freed:
            log.info("persisted %d bytes of cold host payloads to ice "
                     "(host budget %d)", freed, self.host_budget)
        return freed

    def persist_sweep(self) -> int:
        """Persist EVERY host payload (tests + emergency host pressure)."""
        return self._persist_lru(1 << 62)

    # -- streaming telemetry (noted by the mrtask block streamer) ----------

    def _note_page_in(self, nbytes: int, freed_persist: int = 0) -> None:
        with self._lock:
            self.pages_in += 1
            if freed_persist:
                self.persist_reloads += 1

    def _note_pages_out(self, nblocks: int, nbytes: int) -> None:
        with self._lock:
            self.pages_out += int(nblocks)

    def note_prefetch(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.prefetch_hit_count += 1
            else:
                self.prefetch_miss_count += 1

    def note_demand_stall(self) -> None:
        with self._lock:
            self.demand_stall_count += 1

    # -- telemetry ---------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            self._prune()
            self._prune_host()
            sizes = sorted(self._resident.values(), reverse=True)
            hbm = sum(sizes)
            valid = sum(self._valid.values())
            live = [o for o in (w() for w in self._host) if o is not None]
            host = sum(o.resident_nbytes for o in live)
            persist = sum(o.persisted_nbytes for o in live)
            if hbm > self.peak_resident:
                self.peak_resident = hbm
            return {"budget": self.budget,
                    "host_budget": self.host_budget,
                    # capacity vs valid: resident_bytes is what the
                    # padded device buffers occupy (what a spill would
                    # free); valid_bytes counts only real rows — on a
                    # ragged frame the gap is the padding overhead
                    "resident_bytes": hbm,
                    "valid_bytes": valid,
                    "resident_vecs": len(sizes),
                    "spills": self.spill_count,
                    "reloads": self.reload_count,
                    # per-tier residency: the HBM ⇄ host ⇄ persist split
                    "tiers": {"hbm": hbm, "host": host, "persist": persist},
                    "peak_hbm_bytes": self.peak_resident,
                    "pages_in": self.pages_in,
                    "pages_out": self.pages_out,
                    "persists": self.persist_count,
                    "persist_reloads": self.persist_reloads,
                    "prefetch_hits": self.prefetch_hit_count,
                    "prefetch_misses": self.prefetch_miss_count,
                    "demand_page_stalls": self.demand_stall_count,
                    # tenant isolation surface: per-tenant residency +
                    # spill attribution, and the cross-tenant counters
                    # the soak asserts (below-highwater must stay 0)
                    "cross_tenant_evictions": self.cross_tenant_evictions,
                    "cross_tenant_below_highwater":
                        self.cross_tenant_below_highwater,
                    "highwater_frac": tenant_highwater(),
                    "tenants": self._tenant_stats_locked(),
                    # who is holding HBM (top allocations) — the OOM
                    # terminal diagnostic names these
                    "largest_holders": sizes[:5]}

    def _tenant_stats_locked(self) -> dict:
        """Per-tenant residency/spill block (caller holds the lock).
        Shares are NOT read here — that would nest a DKV get inside the
        manager lock; the REST layer joins shares from the registry."""
        per: dict = {}
        for r, nb in self._resident.items():
            tag = self._tenant_of.get(r)
            if tag is None:
                continue
            d = per.setdefault(tag, {"resident_bytes": 0,
                                     "resident_vecs": 0, "spills": 0})
            d["resident_bytes"] += nb
            d["resident_vecs"] += 1
        for tag, n in self._tenant_spills.items():
            per.setdefault(tag, {"resident_bytes": 0,
                                 "resident_vecs": 0,
                                 "spills": 0})["spills"] = n
        return per

    def pressure(self) -> dict:
        """One memory-pressure sample for the serving circuit breaker
        (serve/breaker.py): ``hbm_frac`` is VALID/budget (0.0 when
        unbounded — nothing to protect against) — valid bytes, not
        padded capacity, because a heavily-filtered ragged frame's
        padding is reclaimable by one balanced repack and must not
        trip load-shedding.  Both figures are reported.  Plus the
        CUMULATIVE paging counters the breaker differentiates between
        samples (demand-page stalls and pages in/out rising between
        two reads mean the tier store is actively thrashing — the
        leading indicator that the next big dispatch walks the OOM
        ladder).  Cheap by design: sums the residency table under the
        lock, no device work, no I/O — safe from the admission path."""
        with self._lock:
            self._prune()
            hbm = sum(self._resident.values())
            valid = sum(self._valid.values())
            return {
                "hbm_frac": (valid / self.budget) if self.budget > 0
                else 0.0,
                "resident_bytes": hbm,
                "valid_bytes": valid,
                "demand_page_stalls": self.demand_stall_count,
                "pages_in": self.pages_in,
                "pages_out": self.pages_out,
                "spills": self.spill_count,
            }


_manager: Optional[MemoryManager] = None
_manager_lock = make_lock("memory._manager_lock")

_COUNTERS = ("spill_count", "reload_count", "pages_in", "pages_out",
             "persist_count", "persist_reloads", "prefetch_hit_count",
             "prefetch_miss_count", "demand_stall_count", "peak_resident",
             "cross_tenant_evictions", "cross_tenant_below_highwater")


def manager() -> MemoryManager:
    global _manager
    if _manager is None:
        with _manager_lock:
            if _manager is None:
                from h2o_tpu.config import hbm_budget
                _manager = MemoryManager(hbm_budget())
    return _manager


def set_budget(budget_bytes: int,
               host_budget_bytes: Optional[int] = None) -> MemoryManager:
    """(Re)configure the budgets — tests and boot flags use this.

    Existing registrations in BOTH tiers carry over (their _mm_ref /
    _mmh_ref handles stay valid) and the new budgets are enforced
    immediately with LRU sweeps, so already-resident columns remain
    accounted, spillable, and persistable."""
    global _manager
    with _manager_lock:
        new = MemoryManager(int(budget_bytes), host_budget_bytes)
        if _manager is not None:
            new._resident = dict(_manager._resident)
            new._valid = dict(_manager._valid)
            new._host = dict(_manager._host)
            new._tenant_of = dict(_manager._tenant_of)
            new._tenant_spills = dict(_manager._tenant_spills)
            if host_budget_bytes is None:
                new.host_budget = _manager.host_budget
            for k in _COUNTERS:
                setattr(new, k, getattr(_manager, k))
        _manager = new
    if new.budget > 0:
        over = new.resident_bytes - new.budget
        if over > 0:
            new._spill_lru(over)
    if new.host_budget > 0:
        with new._lock:
            live = sum(o.resident_nbytes for o in
                       (w() for w in new._host) if o is not None)
        if live > new.host_budget:
            new._persist_lru(live - new.host_budget)
    return new
