"""Frame / Vec — the distributed columnar data plane, TPU-native edition.

Reference design (water/fvec/*, SURVEY §2.1): a Frame is a list of Vecs; each
Vec is one column split into ~4 MiB compressed Chunks homed across nodes, with
a VectorGroup keeping all columns of a frame chunk-aligned so a row's cells
are co-located (Vec.java:120-135).  Types are T_NUM/T_CAT/T_TIME/T_STR/T_UUID
/T_BAD (Vec.java:207-212); categorical domains are String[] on the Vec; lazy
``RollupStats`` (min/max/mean/sigma/nacnt/histogram) are computed by an MRTask
and cached (RollupStats.java).

TPU-native redesign:
- a Vec's numeric payload is ONE ``jax.Array`` row-sharded over the mesh's
  ``nodes`` axis — the shard is the "chunk", HBM is the heap, and
  ``NamedSharding`` is the VectorGroup (all Vecs of a Frame share the same
  row partitioning by construction, so cells of a row are on the same chip);
- rows are padded to a fixed per-device quantum (lane-aligned static shapes —
  XLA's analog of the chunk size constant, FileVec.java:33-38) and masked with
  a row-validity predicate derived from ``iota < nrows``;
- NAs are NaN in the float payload (numeric/time) and -1 in int payloads
  (categorical), mirroring the reference's per-type NA sentinels
  (water/fvec/C8Chunk.java NAs / DHistogram NA bucket);
- chunk compression codecs (C1Chunk..C16Chunk, SURVEY §2.1) are replaced by
  dtype selection: float32 payloads by default, bfloat16 matrices for MXU
  consumption; XLA fuses any decompression-like widening into consumers;
- strings/UUIDs stay host-side (SURVEY §7 "strings stay host-side");
- rollups are one fused jit reduction, cached on the Vec, invalidated on
  mutation — same contract as RollupStats' lazy compute-once.

SHARD-RESIDENCY CONTRACT (the scale-out data plane, core/munge.py):
``is_row_sharded`` Vecs/Frames carry their payload row-sharded over the
mesh's ``nodes`` axis.  Canonical frames keep valid rows as one global
prefix (``iota < nrows``); frames produced by the sharded filter/merge
collectives are instead RAGGED — each shard holds a local prefix of
valid rows tracked by ``shard_counts`` (one int per shard, the analog of
per-node chunk row counts).  ``valid_mask()`` is the one predicate both
layouts share; downstream munge verbs consume ragged frames directly by
masking, and anything that needs the canonical layout (``as_matrix`` for
training, appends) first calls ``Frame.repack()`` — a balanced
``all_to_all`` exchange on device, never a host gather.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from h2o_tpu.core.cloud import cloud, pad_rows
from h2o_tpu.core.store import Key

# Vec types (reference: water/fvec/Vec.java:207-212)
T_BAD = "bad"      # all-NA
T_NUM = "real"     # numeric (int or float — device f32)
T_CAT = "enum"     # categorical: int32 codes + host domain
T_TIME = "time"    # ms since epoch (device f32; precision caveat documented)
T_STR = "string"   # host-side list of str
T_UUID = "uuid"    # host-side


def _row_pad(n: int) -> int:
    q = cloud().row_multiple()
    return ((n + q - 1) // q) * q


def _append_capacity(n: int) -> int:
    """Device-buffer capacity for ``n`` logical rows on the append path:
    the power-of-two shape bucket (exec_store.bucket_pow2) padded to the
    shard quantum, so a stream of appends revisits at most ~log2(N)
    distinct buffer shapes — and therefore at most ~log2(N) compiled
    kernels per verb (zero steady-state recompiles per chunk)."""
    from h2o_tpu.core.exec_store import bucket_pow2
    return _row_pad(bucket_pow2(max(int(n), 1)))


def _merge_domains(base: Optional[List[str]], new: Optional[List[str]]):
    """Union categorical domain (base levels keep their codes, new levels
    append in first-seen order — the streaming analog of the multi-file
    domain merge, ParseDataset.java:356-535) plus the remap array taking
    ``new``-local codes into the union space (-1 stays -1)."""
    union = list(base or [])
    seen = {d: i for i, d in enumerate(union)}
    remap = np.empty(len(new or []) + 1, np.int32)
    remap[-1] = -1
    for j, d in enumerate(new or []):
        if d not in seen:
            seen[d] = len(union)
            union.append(d)
        remap[j] = seen[d]
    return union, remap


# -- append kernels (phase "append", cached through the exec store: one
#    compile per (capacity, chunk-bucket, dtype) — the pow2 buckets bound
#    the program count logarithmically) ----------------------------------

def _build_grow(cap_old: int, cap_new: int, fill_kind: str):
    # fill_kind is a STRING marker ("nan" | "neg1"), not the value: a NaN
    # inside a cache key never compares equal to itself, so it would
    # defeat the kernel cache entirely
    fill = float("nan") if fill_kind == "nan" else -1

    def kern(buf):
        # jnp.pad, not concatenate-with-filler: the latter miscompiles
        # for sharded operands on meshes with a model axis (see
        # core/munge._pad_rows)
        return jnp.pad(buf, (0, cap_new - cap_old),
                       constant_values=fill)
    return kern


def _build_append_write(cap: int, ch: int):
    def kern(buf, chunk, start, nvalid):
        idx = jnp.arange(cap)
        src = jnp.clip(idx - start, 0, ch - 1)
        vals = jnp.take(chunk, src)
        write = (idx >= start) & (idx < start + nvalid)
        return jnp.where(write, vals, buf)
    return kern


def _rollups_matrix_kernel(matrix: jax.Array, rowvalid: jax.Array):
    """Fused single-pass rollup stats over ALL columns of a padded, sharded
    (rows, cols) matrix at once.

    Equivalent of the RollupStats MRTask (water/fvec/RollupStats.java), but
    batched column-wise: the reference computes rollups one Vec at a time
    (one MRTask each); here one XLA program covers the whole frame: each
    shard reduces its own rows and the (cols,) partials meet in one
    ``hpsum`` / ``hpmin`` / ``hpmax`` each.  ``rowvalid``
    is the row-validity predicate — a plain ``iota < nrows`` prefix for
    canonical frames, the per-shard-count mask for ragged ones — so the
    kernel consumes sharded inputs as-is, no reshard or repack first
    (rows are padded, not valid, to a multiple of the shard count where
    they are not one).
    """
    return _rollups_program(pad_rows(matrix), pad_rows(rowvalid, False),
                            mesh=cloud().mesh)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _rollups_program(matrix, rowvalid, mesh):
    from h2o_tpu.core.cloud import hpmax, hpmin, hpsum, shard_map_compat
    dp = cloud().data_pspec

    def run(matrix, rowvalid):
        valid = rowvalid[:, None]
        isna = jnp.isnan(matrix) & valid
        ok = valid & ~isna
        x = jnp.where(ok, matrix, 0.0)
        cnt, nacnt, zeros = hpsum(jnp.stack([
            jnp.sum(ok, axis=0), jnp.sum(isna, axis=0),
            jnp.sum(ok & (matrix == 0), axis=0)]).astype(jnp.int32),
            "rollups.counts")
        mean = hpsum(jnp.sum(x, axis=0), "rollups.sum") / \
            jnp.maximum(cnt, 1)
        var = hpsum(jnp.sum(jnp.where(ok, (matrix - mean[None, :]) ** 2,
                                      0.0), axis=0),
                    "rollups.sum") / jnp.maximum(cnt - 1, 1)
        big = jnp.asarray(jnp.inf, matrix.dtype)
        vmin = hpmin(jnp.min(jnp.where(ok, matrix, big), axis=0),
                     "rollups.range")
        vmax = hpmax(jnp.max(jnp.where(ok, matrix, -big), axis=0),
                     "rollups.range")
        isint = hpmin(jnp.all(jnp.where(ok, matrix == jnp.round(matrix),
                                        True), axis=0).astype(jnp.int32),
                      "rollups.range") > 0
        return dict(cnt=cnt, nacnt=nacnt, mean=mean, sigma=jnp.sqrt(var),
                    min=vmin, max=vmax, zeros=zeros, isint=isint)

    return shard_map_compat(run, mesh=mesh, in_specs=(dp(None), dp()),
                            out_specs=P(), check_vma=False)(
        matrix, rowvalid)


@functools.partial(jax.jit, static_argnames=("nbins",))
def _hist_kernel(data: jax.Array, rowvalid: jax.Array, vmin, vmax,
                 nbins: int = 64):
    """Lazy fixed-width histogram for one column (REST frame summaries)."""
    ok = rowvalid & ~jnp.isnan(data)
    span = jnp.maximum(vmax - vmin, 1e-30)
    b = jnp.clip(((data - vmin) / span * nbins).astype(jnp.int32), 0,
                 nbins - 1)
    return jnp.zeros((nbins,), jnp.int32).at[b].add(ok.astype(jnp.int32))


class RollupStats:
    """Materialized rollups for one Vec (histogram computed lazily)."""

    __slots__ = ("cnt", "nacnt", "mean", "sigma", "min", "max", "zeros",
                 "isint", "_vec")

    def __init__(self, d: dict, vec: "Vec" = None):
        for k in self.__slots__:
            if k == "_vec":
                continue
            setattr(self, k, np.asarray(d[k]).item())
        self._vec = vec

    @property
    def hist(self) -> np.ndarray:
        return self._vec.histogram()


# -- a frame's enum codes in another frame's domain --------------------------

_LOOKUP_QUANTUM = 64    # a lookup table's length is padded to it: one program a size


def domain_table(frame_domain, train_domain) -> Optional[np.ndarray]:
    """H2O-3's ``adaptTestForTrain`` for one enum column, as a lookup
    table: entry i is the TRAINING code of the frame's level i, matched
    by the level's string; NaN for a level training never saw.  The tail
    (at least one entry) is NaN padding.  None when the two domains are
    equal: there is nothing to do."""
    fd, td = list(frame_domain or ()), list(train_domain or ())
    if fd == td:
        return None
    code = {s: i for i, s in enumerate(td)}
    table = np.full(-(-(len(fd) + 1) // _LOOKUP_QUANTUM) * _LOOKUP_QUANTUM,
                    np.nan, np.float32)
    table[: len(fd)] = [code.get(s, np.nan) for s in fd]
    return table


def table_unseen_levels(table: np.ndarray, frame_domain) -> int:
    """Levels of the frame's domain that the table maps to nothing."""
    return int(np.isnan(table[: len(frame_domain or ())]).sum())


@jax.jit
@jax.named_scope("h2o.score.adapt")
def _codes_in_domain(codes, table, nrows):
    """Row-sharded int codes (NA below 0) -> float32 codes of the
    table's domain (NA and unseen levels NaN), and how many of the first
    ``nrows`` rows hold a level the table lacks."""
    last = table.shape[0] - 1                   # always NaN
    held = (codes >= 0) & (codes < last)
    out = table[jnp.where(held, codes, last)]
    miss = held & jnp.isnan(out) & (jnp.arange(codes.shape[0]) < nrows)
    return out, jnp.sum(miss, dtype=jnp.int32)


class Vec:
    """One column.  Numeric/categorical/time payloads live on-device."""

    def __init__(self, data, vtype: str = T_NUM, nrows: Optional[int] = None,
                 domain: Optional[List[str]] = None,
                 shard_counts: Optional[np.ndarray] = None):
        self.type = vtype
        self.domain = domain
        self._rollups: Optional[RollupStats] = None
        self._hist: Optional[np.ndarray] = None
        self._host_f64 = None     # residue-backed property (tier model)
        self._spill_np = None     # parked host copy (memory.HostBlocks)
        # ragged shard layout (sharded filter/merge outputs): valid rows
        # are a PER-SHARD prefix; shard_counts[s] rows of shard s are
        # real, the rest is masked padding.  None = canonical global
        # prefix (iota < nrows).
        self.shard_counts = (np.asarray(shard_counts, np.int64)
                             if shard_counts is not None else None)
        if self.shard_counts is not None and nrows is None:
            nrows = int(self.shard_counts.sum())
        import threading as _th
        self._spill_lock = _th.Lock()   # guards _data <-> _spill_np swaps
        if vtype in (T_STR, T_UUID):
            self.host_data: List = list(data)
            self.nrows = len(self.host_data)
            self._data = None
            return
        self.host_data = None
        if isinstance(data, jax.Array):
            assert nrows is not None, "device data requires explicit nrows"
            self._data = data
            self.nrows = nrows
            self._account()
        else:
            arr = np.asarray(data)
            self.nrows = nrows if nrows is not None else arr.shape[0]
            if vtype == T_CAT:
                arr = arr.astype(np.int32)
                # NA code -1 → represent as float NaN? no: keep int + sentinel
                self._data = cloud().device_put_rows(arr)
            else:
                if vtype == T_TIME:
                    # ms-since-epoch exceeds f32 precision (~131 s ulp at
                    # current epochs); keep an exact host copy for
                    # time-part extraction while the device payload stays
                    # f32 for arithmetic/binning
                    self._host_f64 = arr.astype(np.float64, copy=True)
                self._data = cloud().device_put_rows(
                    arr.astype(np.float32, copy=False))
            self._account()

    # -- HBM budget integration (core/memory.py, the Cleaner analog) -------

    def _device_nbytes(self) -> int:
        d = self._data
        return int(d.size * d.dtype.itemsize) if d is not None else 0

    def _valid_nbytes(self) -> int:
        """Bytes of the device payload holding REAL rows: a ragged
        column (per-shard valid prefixes) counts only its shard_counts
        rows, a canonical column counts min(nrows, buffer rows).  The
        capacity/valid split is what MemoryManager.stats() reports and
        what pressure() drives off — a heavily-filtered ragged frame
        must not inflate HBM pressure by its padding."""
        d = self._data
        if d is None or not d.ndim:
            return 0
        if self.shard_counts is not None:
            valid = int(self.shard_counts.sum())
        else:
            valid = min(int(self.nrows), int(d.shape[0]))
        per_row = int(d.dtype.itemsize)
        for s in d.shape[1:]:
            per_row *= int(s)
        return max(valid, 0) * per_row

    def _account(self) -> None:
        if self._data is not None:
            from h2o_tpu.core.memory import manager
            manager().register(self, self._device_nbytes(),
                               self._valid_nbytes())

    def _spill(self) -> bool:
        """Drop the device payload after parking a host copy (called by
        the MemoryManager under budget pressure).  The park is a
        block-chunked :class:`~h2o_tpu.core.memory.HostBlocks` — the
        host tier of the column store: individually persistable blocks
        that the blocked training paths stream back window-at-a-time.
        Returns False when there is nothing to spill."""
        from h2o_tpu.core.cloud import Cloud
        from h2o_tpu.core.memory import HostBlocks, manager
        with self._spill_lock:
            if self._data is None:
                return False
            inst = Cloud._instance
            park = HostBlocks(np.asarray(self._data),
                              inst.n_nodes if inst is not None else 1)
            self._spill_np = park
            self._data = None
        # host-tier registration outside the vec lock (it may trigger a
        # persist sweep of OTHER parks, which take their own I/O locks)
        manager().register_host(park, park.nbytes)
        return True

    @property
    def data(self) -> Optional[jax.Array]:
        """The device payload; spilled columns reload transparently.
        The lock makes reload/spill atomic: a concurrent Cleaner sweep
        can never hand a reader None mid-swap."""
        from h2o_tpu.core.memory import manager
        park = None
        with self._spill_lock:
            if self._data is None and self._spill_np is not None:
                park = self._spill_np
                # rehydrate (paging persisted blocks back in) and land
                # shard-direct — each shard straight to its home device
                self._data = cloud().device_put_rows(park.to_ndarray())
                self._spill_np = None
                manager().note_reload()
                reloaded = True
            else:
                reloaded = False
            out = self._data
        if park is not None:
            manager().unregister_host(park)
        # manager calls outside the vec lock (it takes its own lock; a
        # register may spill OTHER vecs, which grab their own locks)
        if reloaded:
            self._account()
        elif out is not None:
            manager().touch(self)
        return out

    @data.setter
    def data(self, value) -> None:
        from h2o_tpu.core.memory import manager
        manager().unregister(self)
        with self._spill_lock:
            self._data = value
            old_park = self._spill_np
            self._spill_np = None
        if old_park is not None:
            manager().unregister_host(old_park)
        if value is not None:
            self._account()

    # -- host-tier residues (T_TIME exact f64, T_STR/T_UUID lists) ---------
    # These payloads never touch HBM by design; in the tier model they
    # page host ⇄ persist through the MemoryManager's host tier
    # (core/memory.HostResidue) and reload transparently on access —
    # the properties keep every existing reader/writer site unchanged.

    @property
    def _host_f64(self) -> Optional[np.ndarray]:
        res = self.__dict__.get("_time_res")
        return res.get() if res is not None else None

    @_host_f64.setter
    def _host_f64(self, value) -> None:
        from h2o_tpu.core.memory import HostResidue, manager
        old = self.__dict__.get("_time_res")
        if old is not None:
            manager().unregister_host(old)
        if value is None:
            self.__dict__["_time_res"] = None
            return
        res = HostResidue(np.asarray(value, np.float64))
        self.__dict__["_time_res"] = res
        manager().register_host(res, res.nbytes)

    @property
    def host_data(self) -> Optional[List]:
        res = self.__dict__.get("_str_res")
        return res.get() if res is not None else None

    @host_data.setter
    def host_data(self, value) -> None:
        from h2o_tpu.core.memory import HostResidue, manager
        old = self.__dict__.get("_str_res")
        if old is not None:
            manager().unregister_host(old)
        if value is None:
            self.__dict__["_str_res"] = None
            return
        res = HostResidue(value if isinstance(value, list) else list(value))
        self.__dict__["_str_res"] = res
        manager().register_host(res, res.nbytes)

    # -- basics ------------------------------------------------------------

    def __len__(self) -> int:
        return self.nrows

    @property
    def is_categorical(self) -> bool:
        return self.type == T_CAT

    @property
    def is_numeric(self) -> bool:
        return self.type in (T_NUM, T_TIME)

    @property
    def is_ragged(self) -> bool:
        """True when valid rows are a per-shard prefix (shard_counts)
        rather than one global prefix."""
        return self.shard_counts is not None

    @property
    def is_row_sharded(self) -> bool:
        """Cheap shard-residency invariant: the device payload exists and
        is sharded over the mesh's ``nodes`` axis (the chunk-homing
        contract the scale-out munge verbs rely on).  Checked against
        the CURRENT cloud — a payload left over from a pre-``reform``
        mesh reads False."""
        with self._spill_lock:
            d = self._data
        if d is None:
            return False
        try:
            from h2o_tpu.core.cloud import DATA_AXIS, SLICE_AXIS, cloud
            spec = d.sharding.spec
            # flat mesh rows shard over "nodes"; two-level over the
            # ("slices", "nodes") product — both are row-sharded
            if not spec or spec[0] not in (DATA_AXIS,
                                           (SLICE_AXIS, DATA_AXIS)):
                return False
            return d.sharding.mesh.devices.ravel()[0] in set(
                cloud().mesh.devices.ravel())
        except Exception:  # noqa: BLE001 — single-device/host arrays
            return False

    def valid_mask(self) -> jax.Array:
        """Row-validity predicate over the device payload: a global
        prefix for canonical Vecs, the per-shard prefix for ragged ones.
        This is the ONE mask every munge collective and reduction
        kernel consumes — padding is masked, never re-gathered."""
        B = self._device_rows() or _row_pad(self.nrows)
        idx = jnp.arange(B)
        if self.shard_counts is None:
            return idx < self.nrows
        n = len(self.shard_counts)
        L = B // n
        counts = jnp.asarray(self.shard_counts, jnp.int32)
        return idx % L < jnp.take(counts, idx // L)

    @property
    def cardinality(self) -> int:
        return len(self.domain) if self.domain is not None else -1

    def as_float(self) -> jax.Array:
        """Device payload as float32 with NaN NAs (cat codes -1 → NaN)."""
        if self.type == T_CAT:
            f = self.data.astype(jnp.float32)
            return jnp.where(self.data < 0, jnp.nan, f)
        return self.data

    def to_numpy(self) -> np.ndarray:
        """Unpadded host copy (NA = NaN for numeric, -1 for categorical).
        T_TIME returns the exact float64 epoch-ms copy when available.

        Every call that actually reads the DEVICE payload is counted
        (count + bytes) against the calling thread's DispatchStats
        phase — the HBM->host traffic the device-munge layer exists to
        eliminate shows up per phase at GET /3/Dispatch."""
        if self.host_data is not None:
            return np.asarray(self.host_data, dtype=object)
        if self._host_f64 is not None:
            return self._host_f64[: self.nrows]
        with self._spill_lock:
            if self._data is None and self._spill_np is not None:
                # host reads of spilled columns never touch the device
                return self._compact_host(self._spill_np.to_ndarray())
        from h2o_tpu.core.diag import DispatchStats
        arr = np.asarray(self.data)
        DispatchStats.note_host_pull(arr.nbytes)
        return self._compact_host(arr)

    def _compact_host(self, arr: np.ndarray) -> np.ndarray:
        """Unpadded host view: global prefix for canonical Vecs; ragged
        Vecs concatenate each shard's valid prefix (host-side — the
        ragged->canonical device path is Frame.repack)."""
        if self.shard_counts is None:
            return arr[: self.nrows]
        n = len(self.shard_counts)
        L = arr.shape[0] // n
        blocks = arr.reshape((n, L) + arr.shape[1:])
        return np.concatenate([blocks[s][: int(c)]
                               for s, c in enumerate(self.shard_counts)])

    # -- rollups -----------------------------------------------------------

    @property
    def rollups(self) -> RollupStats:
        if self._rollups is None:
            from h2o_tpu.core.diag import DispatchStats
            DispatchStats.note_dispatch("rollups")
            d = _rollups_matrix_kernel(self.as_float()[:, None],
                                       self.valid_mask())
            self._rollups = RollupStats(
                {k: np.asarray(v)[0] for k, v in d.items()}, vec=self)
        return self._rollups

    def histogram(self, nbins: int = 64) -> np.ndarray:
        r = self.rollups
        if self._hist is None or len(self._hist) != nbins:
            self._hist = np.asarray(_hist_kernel(
                self.as_float(), self.valid_mask(),
                jnp.float32(r.min), jnp.float32(r.max), nbins))
        return self._hist

    def mean(self) -> float:
        return self.rollups.mean

    def sigma(self) -> float:
        return self.rollups.sigma

    def min(self) -> float:
        return self.rollups.min

    def max(self) -> float:
        return self.rollups.max

    def nacnt(self) -> int:
        if self.type == T_CAT:
            # categorical NA is the -1 code, invisible to the NaN-based
            # kernel; counted as a device reduction (one scalar syncs)
            # instead of pulling the whole code column to host
            d = self.data
            return int(jnp.sum((d < 0) & self.valid_mask()))
        return int(self.rollups.nacnt)

    def invalidate(self) -> None:
        self._rollups = None
        self._hist = None

    # -- streaming append (h2o_tpu/stream: append-able Vecs) ----------------

    def _device_rows(self) -> int:
        """Length of the device payload (or its parked host copy) — the
        Vec's buffer CAPACITY, which exceeds ``_row_pad(nrows)`` once the
        append path has grown it to a pow2 bucket.  0 for host-side
        columns (T_STR/T_UUID, unmaterialized sparse)."""
        with self._spill_lock:
            if self._data is not None:
                return int(self._data.shape[0])
            if self._spill_np is not None:
                return int(self._spill_np.shape[0])
        return 0

    def append(self, values, domain: Optional[List[str]] = None) -> None:
        """Grow this Vec by ``values`` rows IN PLACE, landing the new rows
        as one device block write — the existing payload is never pulled
        to host (zero-host-pull, lint-enforced like the munge verbs).

        The device buffer is sized in power-of-two capacity buckets
        (``_append_capacity``) and new rows land via a cached
        ``dynamic-update`` kernel keyed on (capacity, chunk-bucket), so a
        steady stream of same-sized chunks costs ZERO recompiles after
        the first; capacity growth re-allocates at the next bucket
        (~log2(N) growths over a stream's lifetime).

        ``values``: host array of new rows (float payload for T_NUM /
        T_TIME epoch-ms; int codes for T_CAT).  ``domain`` gives the
        chunk-LOCAL categorical domain; new levels extend this Vec's
        domain and the chunk codes are remapped into the union space.
        Cached rollups/histograms invalidate; callers holding the vec in
        a Frame must clear that frame's matrix cache (Frame.append_rows
        does)."""
        if self.type in (T_STR, T_UUID):
            lst = self.host_data
            lst.extend(list(values))
            # re-wrap: refreshes the host-tier byte accounting and drops
            # any stale persisted copy of the pre-append payload
            self.host_data = lst
            self.nrows = len(lst)
            return
        if self.shard_counts is not None:
            raise ValueError(
                "cannot append to a ragged (shard-prefix) Vec — call "
                "Frame.repack() first to restore the canonical prefix "
                "layout the append block-writes assume")
        arr = np.asarray(values)
        n_new = int(arr.shape[0])
        if n_new == 0:
            return
        from h2o_tpu.core.diag import DispatchStats
        from h2o_tpu.core.exec_store import cached_kernel
        if self.type == T_CAT:
            codes = arr.astype(np.int32)
            if domain is not None and list(domain) != list(self.domain
                                                          or []):
                self.domain, remap = _merge_domains(self.domain, domain)
                ok = (codes >= 0) & (codes < len(domain))
                codes = np.where(ok, remap[np.clip(codes, 0,
                                                   len(domain) - 1)],
                                 -1).astype(np.int32)
            chunk = codes
            fill_kind = "neg1"
        else:
            if self.type == T_TIME:
                if self._host_f64 is None:
                    raise ValueError(
                        "appending to a T_TIME vec that lost its exact "
                        "float64 host copy would silently degrade "
                        "time-part extraction to f32 precision")
                self._host_f64 = np.concatenate(
                    [self._host_f64[: self.nrows],
                     arr.astype(np.float64)])
            chunk = arr.astype(np.float32)
            fill_kind = "nan"
        old_n, new_n = self.nrows, self.nrows + n_new
        cap = max(_append_capacity(new_n), self._device_rows() or 0)
        ch = _append_capacity(n_new)
        fill = np.nan if fill_kind == "nan" else -1
        if ch > n_new:
            chunk = np.concatenate(
                [chunk, np.full(ch - n_new, fill, chunk.dtype)])
        with DispatchStats.phase_scope("append"):
            chunk_dev = cloud().device_put_rows(chunk)
            buf = self.data            # spilled payloads reload here
            assert buf is not None, "append needs a device payload"
            cap_old = int(buf.shape[0])
            if cap_old < cap:
                grow = cached_kernel(
                    "append", "grow", (cap_old, cap, fill_kind),
                    lambda: _build_grow(cap_old, cap, fill_kind), buf)
                buf = grow(buf)
            write = cached_kernel(
                "append", "write", (cap, ch, str(buf.dtype)),
                lambda: _build_append_write(cap, ch), buf, chunk_dev,
                jnp.int32(old_n), jnp.int32(n_new))
            new = write(buf, chunk_dev, jnp.int32(old_n),
                        jnp.int32(n_new))
        self.nrows = new_n
        self.data = new                # setter re-registers with the MM
        self.invalidate()

    # -- mesh resize (Cloud.reform) ----------------------------------------

    def _rehome(self) -> None:
        """Re-land the payload on the CURRENT cloud's mesh — the mesh-
        resize event (Cloud.reform).  The payload bounces through host
        once (the resize is a topology change, not a hot-path verb):
        padding quantum and sharding both depend on the mesh shape, so
        the old device buffer cannot be reused.  Ragged Vecs compact to
        the canonical prefix layout as part of the move."""
        if self.host_data is not None or self._data is None and \
                self._spill_np is None:
            return
        from h2o_tpu.core.memory import manager
        with self._spill_lock:
            src = self._spill_np.to_ndarray() if self._data is None else \
                np.asarray(self._data)
        arr = self._compact_host(src)
        manager().unregister(self)
        with self._spill_lock:
            old_park = self._spill_np
            self._spill_np = None
        if old_park is not None:
            manager().unregister_host(old_park)
        with self._spill_lock:
            if self.type == T_CAT:
                self._data = cloud().device_put_rows(
                    arr.astype(np.int32, copy=False))
            else:
                self._data = cloud().device_put_rows(
                    arr.astype(np.float32, copy=False))
        self.shard_counts = None
        self._account()
        self.invalidate()

    # -- in-place mutation (donating) --------------------------------------

    def map_inplace(self, fn, *extras) -> None:
        """Elementwise in-place transform of the device payload:
        ``payload = fn(payload, *extras)`` through the dispatch cache,
        DONATING the old buffer when the backend supports it
        (H2O_TPU_DONATE) — the mutating-frame-op analog of the forest
        carry donation: no fresh HBM allocation per mutation.  ``fn``
        must be a module-level function (a per-call closure would defeat
        the cache).  Rollups/histograms invalidate; callers that hold
        the vec in a Frame must clear that frame's matrix cache."""
        assert self._data is not None or self._spill_np is not None, \
            "map_inplace needs a device payload"
        assert self._host_f64 is None, \
            "map_inplace would desync the exact host copy (T_TIME)"
        from h2o_tpu.core.mrtask import mutate_array
        # route through the data property so spilled payloads reload
        new = mutate_array(fn, self.data, *extras)
        self.data = new                # setter re-registers with the MM
        self.invalidate()


class SparseVec(Vec):
    """Sparse numeric column codec — the CXIChunk/CXFChunk analog
    (reference water/fvec/CXIChunk.java: store only non-default values).

    TPU-native role: sparse is the AT-REST codec, dense the COMPUTE form.
    The MXU wants dense tiles, so decompression happens once at the HBM
    boundary (first device access materializes the dense payload) instead
    of per-op; under memory pressure the Cleaner drops the dense copy and
    the column collapses back to its (indices, values) pairs — spilling
    is free because the sparse source is authoritative.
    """

    def __init__(self, idx, vals, nrows: int, default: float = 0.0,
                 vtype: str = T_NUM):
        import threading as _th
        idx = np.asarray(idx, np.int64)
        vals = np.asarray(vals, np.float32)
        assert idx.shape == vals.shape
        assert vtype in (T_NUM, T_TIME)
        self.type = vtype
        self.domain = None
        self.nrows = int(nrows)
        self.host_data = None
        self._rollups = None
        self._hist = None
        self._host_f64 = None
        self._spill_np = None
        self.shard_counts = None             # sparse vecs are canonical
        self._spill_lock = _th.Lock()
        self._sparse = (idx, vals, np.float32(default))
        self._data = None                    # dense device form, lazy

    @property
    def nnz(self) -> int:
        return len(self._sparse[0])

    def _densify_host(self) -> np.ndarray:
        idx, vals, default = self._sparse
        dense = np.full(self.nrows, default, np.float32)
        dense[idx] = vals
        return dense

    @property
    def data(self):
        if self._sparse is None:             # graduated to dense (mutated)
            return Vec.data.fget(self)
        from h2o_tpu.core.memory import manager
        with self._spill_lock:
            if self._data is None:
                self._data = cloud().device_put_rows(self._densify_host())
                out = self._data
                materialized = True
            else:
                out = self._data
                materialized = False
        if materialized:
            self._account()
        else:
            manager().touch(self)
        return out

    @data.setter
    def data(self, value) -> None:
        # dense mutation graduates the column out of the sparse codec
        # (the reference likewise re-compresses to a different chunk type
        # on NewChunk close); from here on base-class spill semantics
        # (park a dense host copy) apply
        self._sparse = None
        Vec.data.fset(self, value)

    def _spill(self) -> bool:
        if self._sparse is None:
            return Vec._spill(self)
        # drop the dense device payload; the sparse pairs stay
        with self._spill_lock:
            if self._data is None:
                return False
            self._data = None
            return True

    def _rehome(self) -> None:
        if self._sparse is None:
            Vec._rehome(self)
            return
        # sparse source is authoritative: drop the dense copy and let
        # the next access re-densify onto the new mesh
        from h2o_tpu.core.memory import manager
        manager().unregister(self)
        with self._spill_lock:
            self._data = None

    def to_numpy(self) -> np.ndarray:
        if self._sparse is None:
            return Vec.to_numpy(self)
        with self._spill_lock:
            if self._data is not None:
                return np.asarray(self._data)[: self.nrows]
        return self._densify_host()


def _chunk_cols_from_frame(target: "Frame", chunk: "Frame") -> Dict:
    """Host column payloads of a CHUNK frame, shaped for ``Vec.append``.
    Deliberately outside the zero-host-pull append verbs: it reads only
    the (small, freshly-staged) chunk — never the accumulated frame."""
    if list(chunk.names) != list(target.names):
        raise ValueError(
            f"append_rows schema mismatch: frame has {target.names}, "
            f"chunk has {chunk.names}")
    cols: Dict = {}
    for name, v in zip(chunk.names, chunk.vecs):
        tv = target.vec(name)
        if v.type != tv.type:
            raise ValueError(
                f"append_rows type mismatch on {name!r}: frame is "
                f"{tv.type}, chunk is {v.type}")
        if v.host_data is not None:
            cols[name] = list(v.host_data)
        elif v.type == T_CAT:
            cols[name] = (v.to_numpy(), list(v.domain or []))
        else:
            cols[name] = v.to_numpy()
    return cols


def frame_device_ok(fr: "Frame") -> bool:
    """True when every column lives (or can live) on device with exact
    semantics: numeric/categorical payloads only.  T_TIME is excluded
    (its exact f64 epoch-ms copy is host-side by design), as are
    strings/UUIDs — frames holding those take the host munge path."""
    return bool(fr.vecs) and all(
        v.type in (T_NUM, T_CAT) and v.host_data is None
        for v in fr.vecs)


class Frame:
    """An ordered collection of equally-long, identically-sharded Vecs."""

    def __init__(self, names: Sequence[str] = (), vecs: Sequence[Vec] = (),
                 key: Optional[str] = None):
        assert len(names) == len(vecs)
        self.names: List[str] = list(names)
        self.vecs: List[Vec] = list(vecs)
        for v in self.vecs[1:]:
            assert v.nrows == self.vecs[0].nrows, "ragged frame"
        self.key = Key(key) if key else Key.make("frame")
        self._matrix_cache: Dict = {}

    # -- construction ------------------------------------------------------

    @classmethod
    def from_numpy(cls, array: np.ndarray, names: Optional[Sequence[str]] = None,
                   key: Optional[str] = None) -> "Frame":
        array = np.asarray(array, dtype=np.float32)
        if array.ndim == 1:
            array = array[:, None]
        names = list(names) if names else [f"C{i+1}" for i in
                                           range(array.shape[1])]
        vecs = [Vec(array[:, j]) for j in range(array.shape[1])]
        return cls(names, vecs, key=key)

    @classmethod
    def from_dict(cls, cols: Dict[str, Union[np.ndarray, list]],
                  key: Optional[str] = None) -> "Frame":
        names, vecs = [], []
        for name, col in cols.items():
            names.append(name)
            arr = np.asarray(col)
            if arr.dtype.kind in "OUS":  # strings → categorical
                domain, codes = np.unique(arr.astype(str), return_inverse=True)
                vecs.append(Vec(codes.astype(np.int32), T_CAT,
                                domain=[str(d) for d in domain]))
            else:
                vecs.append(Vec(arr.astype(np.float32)))
        return cls(names, vecs, key=key)

    # -- shape / access ----------------------------------------------------

    @property
    def nrows(self) -> int:
        return self.vecs[0].nrows if self.vecs else 0

    @property
    def ncols(self) -> int:
        return len(self.vecs)

    @property
    def padded_rows(self) -> int:
        """Device row count of this frame's matrices.  Equals
        ``_row_pad(nrows)`` for parse-built frames; once the append path
        has grown a column into a pow2 capacity bucket, the bucket IS the
        padded shape (rows beyond ``nrows`` are masked everywhere by the
        row-validity predicate)."""
        n = _row_pad(self.nrows)
        for v in self.vecs:
            n = max(n, v._device_rows())
        return n

    @property
    def is_ragged(self) -> bool:
        return any(v.is_ragged for v in self.vecs)

    @property
    def is_row_sharded(self) -> bool:
        """Shard-residency invariant for the whole frame: every column's
        payload lives row-sharded on the current mesh."""
        return bool(self.vecs) and all(v.is_row_sharded
                                       for v in self.vecs)

    def repack(self) -> "Frame":
        """Restore the canonical global-prefix layout IN PLACE after a
        ragged-producing collective (sharded filter/merge): one balanced
        ``all_to_all`` exchange on device — rows move shard-to-shard
        over the interconnect, never through host, and never replicate.
        No-op for canonical frames."""
        if not self.is_ragged:
            return self
        from h2o_tpu.core.munge import repack_frame
        repack_frame(self)
        self._matrix_cache.clear()
        return self

    def vec(self, name: str) -> Vec:
        return self.vecs[self.names.index(name)]

    def __getitem__(self, name):
        if isinstance(name, str):
            return self.vec(name)
        if isinstance(name, (list, tuple)):
            return self.subframe(name)
        raise TypeError(name)

    def __contains__(self, name: str) -> bool:
        return name in self.names

    def subframe(self, names: Sequence[str]) -> "Frame":
        return Frame(list(names), [self.vec(n) for n in names])

    def drop(self, names: Sequence[str]) -> "Frame":
        if isinstance(names, str):
            names = [names]
        keep = [n for n in self.names if n not in names]
        return self.subframe(keep)

    def add(self, name: str, vec: Vec) -> "Frame":
        assert vec.nrows == self.nrows or not self.vecs
        self.names.append(name)
        self.vecs.append(vec)
        self._matrix_cache.clear()
        return self

    def cbind(self, other: "Frame") -> "Frame":
        return Frame(self.names + other.names, self.vecs + other.vecs)

    # -- streaming append ---------------------------------------------------

    def append_rows(self, chunk) -> "Frame":
        """Append a chunk of rows IN PLACE — the streaming-ingest landing
        verb (h2o_tpu/stream).  ``chunk`` is either a dict of host column
        payloads (``name -> ndarray`` for numeric/time, ``(codes,
        domain)`` for categorical, ``list`` for strings — the zero-copy
        form the chunk tokenizer emits) or another Frame with the same
        schema.  Every column grows by the same row count via
        ``Vec.append`` (pow2-bucketed device block writes, no host pull
        of the existing payload); categorical domains merge; cached
        rollups and the frame matrix cache invalidate."""
        cols = chunk if isinstance(chunk, dict) else \
            _chunk_cols_from_frame(self, chunk)
        missing = [n for n in self.names if n not in cols]
        if missing:
            raise ValueError(f"append_rows chunk is missing columns "
                             f"{missing}")
        n_new = None
        for name in self.names:
            payload = cols[name]
            vals, dom = (payload if isinstance(payload, tuple)
                         else (payload, None))
            n = len(vals)
            if n_new is None:
                n_new = n
            elif n != n_new:
                raise ValueError(
                    f"ragged append chunk: column {name!r} has {n} rows, "
                    f"expected {n_new}")
        for name in self.names:
            payload = cols[name]
            vals, dom = (payload if isinstance(payload, tuple)
                         else (payload, None))
            self.vec(name).append(vals, domain=dom)
        self._matrix_cache.clear()
        return self

    def slice_rows(self, mask_or_idx) -> "Frame":
        """New Frame of the selected rows (the deep-slice/row-filter
        path, reference rapids AstRowSlice).

        A ``jax.Array`` boolean mask routes through the device-munge
        compaction kernel (core/munge.py): the mask never materializes
        on host, rows are selected by a cumsum-of-mask gather on device,
        and only the surviving row COUNT syncs back.  Integer index
        arrays (the rapids numlist path) route through the device
        ``take`` kernel — a sharded gather, no column round-trips host.
        Host boolean masks keep the host gather + re-upload path."""
        if isinstance(mask_or_idx, jax.Array):
            from h2o_tpu.core.munge import device_munge_enabled, filter_rows
            if device_munge_enabled() and frame_device_ok(self):
                return filter_rows(self, mask_or_idx)
            mask_or_idx = np.asarray(mask_or_idx)[: self.nrows]
        sel = np.asarray(mask_or_idx)
        idx = np.flatnonzero(sel) if sel.dtype == bool else sel
        if sel.dtype != bool and np.issubdtype(sel.dtype, np.integer):
            from h2o_tpu.core.munge import device_munge_enabled, take_rows
            if device_munge_enabled() and frame_device_ok(self):
                return take_rows(self, np.asarray(idx, np.int64))
        vecs = []
        for v in self.vecs:
            if v.host_data is not None:
                vecs.append(Vec([v.host_data[i] for i in idx], v.type))
            else:
                arr = v.to_numpy()[idx]
                vecs.append(Vec(arr, v.type,
                                domain=list(v.domain) if v.domain else None))
        return Frame(list(self.names), vecs)

    # -- device views ------------------------------------------------------

    def as_matrix(self, names: Optional[Sequence[str]] = None,
                  dtype=jnp.float32) -> jax.Array:
        """(padded_rows, ncols) row-sharded matrix of the named columns.

        Categoricals appear as their float codes (NA → NaN).  Cached — the
        fused "decompress chunks into a dense row block" analog of
        DataInfo row extraction (hex/DataInfo.java), but done once.
        """
        if self.is_ragged:
            # training/metrics kernels assume the canonical prefix; the
            # repack is one balanced device exchange, not a host gather
            self.repack()
        names = tuple(names) if names is not None else tuple(self.names)
        ck = (names, jnp.dtype(dtype).name)
        m = self._matrix_cache.get(ck)
        if m is None:
            m = self._stack_columns(
                [self.vec(n).as_float() for n in names], dtype)
            self._matrix_cache[ck] = m
        return m

    def _stack_columns(self, cols, dtype=jnp.float32) -> jax.Array:
        """Float columns -> the (padded_rows, ncols) row-sharded matrix."""
        R = self.padded_rows
        # appendable columns carry pow2 capacity; a column added
        # AFTER appends (or a lazy sparse one) may be shorter — pad
        # it to the frame's capacity so the stack stays rectangular
        cols = [c if c.shape[0] == R else
                jnp.pad(c, (0, R - c.shape[0]),
                        constant_values=jnp.nan) for c in cols]
        m = jnp.stack(cols, axis=1).astype(dtype)
        from h2o_tpu.core import landing
        return landing.reshard_rows(m, cloud().matrix_sharding())

    def as_matrix_in_domains(self, names: Sequence[str], tables: Dict):
        """``as_matrix(names)`` with the enum columns in ``tables``
        (column -> ``domain_table``) carried into another frame's domain
        on the device, and a column this frame lacks all NaN.  Returns
        ``(matrix, unseen_rows)``: the second a device int32 scalar, the
        rows (a column at a time) whose level the other domain lacks.
        Cached like ``as_matrix``."""
        if self.is_ragged:
            self.repack()
        ck = ("in_domains", tuple(names),
              tuple((c, t.tobytes()) for c, t in sorted(tables.items())))
        hit = self._matrix_cache.get(ck)
        if hit is None:
            cols, unseen = [], jnp.int32(0)
            for n in names:
                if n not in self:
                    cols.append(jnp.full((self.padded_rows,), jnp.nan,
                                         jnp.float32))
                elif n in tables:
                    col, miss = _codes_in_domain(
                        self.vec(n).data, jnp.asarray(tables[n]),
                        jnp.int32(self.nrows))
                    cols.append(col)
                    unseen = unseen + miss
                else:
                    cols.append(self.vec(n).as_float())
            hit = self._matrix_cache[ck] = (self._stack_columns(cols),
                                            unseen)
        return hit

    def row_mask(self) -> jax.Array:
        """Validity predicate over padded rows (ragged-aware: all vecs of
        a munge-built frame share one shard layout)."""
        if self.vecs and self.vecs[0].is_ragged:
            return self.vecs[0].valid_mask()
        return jnp.arange(self.padded_rows) < self.nrows

    def fill_rollups(self, names: Optional[Sequence[str]] = None) -> None:
        """Batch-compute rollups for all (named) device columns in ONE
        kernel call and populate each Vec's cache — the fast path DataInfo
        uses instead of 1 dispatch per column."""
        names = list(names) if names is not None else self.names
        todo = [n for n in names
                if self.vec(n)._rollups is None and
                self.vec(n).data is not None]
        if not todo:
            return
        from h2o_tpu.core.diag import DispatchStats
        DispatchStats.note_dispatch("rollups")
        m = self.as_matrix(todo)
        d = jax.tree.map(np.asarray,
                         _rollups_matrix_kernel(m, self.row_mask()))
        for j, n in enumerate(todo):
            v = self.vec(n)
            v._rollups = RollupStats({k: d[k][j] for k in d}, vec=v)

    # -- misc --------------------------------------------------------------

    def types(self) -> List[str]:
        return [v.type for v in self.vecs]

    def to_pandas(self):
        import pandas as pd
        cols = {}
        for n, v in zip(self.names, self.vecs):
            arr = v.to_numpy()
            if v.is_categorical:
                dom = np.asarray(v.domain + ["NaN"], dtype=object)
                cols[n] = dom[np.where(arr < 0, len(v.domain), arr)]
            else:
                cols[n] = arr
        return pd.DataFrame(cols)

    def __repr__(self) -> str:
        return (f"<Frame {self.key} {self.nrows}x{self.ncols} "
                f"[{', '.join(self.names[:8])}{'...' if self.ncols > 8 else ''}]>")
