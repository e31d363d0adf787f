"""Cloud = fixed TPU device mesh + thin host control plane.

The reference forms a "cloud" of JVMs by gossip consensus over UDP heartbeats
(water/Paxos.java:15-132, water/HeartBeatThread.java:24) and *locks* membership
at the first distributed write (Paxos.java:145-166).  A TPU slice is already a
fixed, hardware-discovered set of chips, so the TPU-native cloud is simply a
``jax.sharding.Mesh`` built once at boot — the same "fixed membership"
semantics the reference converges to, without the consensus machinery.  Multi-
host pods join via ``jax.distributed.initialize`` (the flatfile/multicast
discovery analog, reference water/init/NetworkInit.java:166-186).

Mesh axes:
- ``slices`` — the OUTER data-axis level (H2O_TPU_SLICES, default 1): one
  entry per ICI island of a multi-slice pod, connected to its peers over
  DCN.  At the default of 1 the axis is omitted entirely and the mesh is
  byte-identical to the historical flat layout.
- ``nodes``  — the data axis.  Frame rows shard over it; MRTask reduces psum
  over it.  This is the analog of chunk home-nodes (water/Key.java:91-182).
  With slices > 1 it becomes the INNER level (``nodes/slices`` entries per
  slice) and rows shard over the ``(slices, nodes)`` product, which visits
  devices in exactly the flat order (slice-major), so shard g of the
  two-level mesh holds the same rows as shard g of the flat mesh.
- ``model``  — optional second axis for tensor parallelism inside an algorithm
  (e.g. wide GLM Gram blocks, DL layer sharding).  The reference has no model
  parallelism (SURVEY §2.4); this axis defaults to size 1.

Every collective in the data plane goes through the hierarchical helper
layer at the bottom of this module (hpsum/hall_gather/hall_to_all/
hshard_index + the slice-scoped hall_gather_inner/hpsum_slices): on the
flat mesh each helper lowers to exactly the historical flat-axis
collective; on a two-level mesh the bulk stage stays ICI-local and one
combine crosses the ``slices`` (DCN) level.  graftlint GL305 bans raw
flat-axis collectives outside this module so the hierarchy cannot be
silently bypassed.
"""

from __future__ import annotations

import contextlib
import functools
import os
import re
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from h2o_tpu.core.config import OptArgs
from h2o_tpu.core.log import get_logger

log = get_logger("cloud")

DATA_AXIS = "nodes"
MODEL_AXIS = "model"
SLICE_AXIS = "slices"

_cache_enabled = False

# <checkout>/.jax_cache — resolved from this package's own location, so
# every process started from one checkout agrees on it and the second
# one hits what the first one wrote
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

_OFF = ("0", "off", "false", "none", "no", "disable", "disabled")
_ON = ("1", "on", "true", "yes")


def shard_map_compat(f, *, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map`` — every shard_map in the codebase goes through
    here (graftlint's kernel classifier keys on this name)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def backend_is_tpu() -> bool:
    """Default-backend probe shared by the trace-time TPU-only gates
    (donation, autotune probes, Pallas kernels, compile cache, fused
    Rapids).  A backend that cannot initialize raises here — silently
    answering False would turn every one of those gates off."""
    return jax.default_backend() == "tpu"


def donation_enabled() -> bool:
    """Buffer-donation switch for the hot carries (forest F, scorer F,
    serve micro-batches, in-place frame mutations).  H2O_TPU_DONATE=1
    forces donation on, =0 forces it off; unset defaults to
    donation-on-TPU only — XLA:CPU ignores donation (the buffers are
    simply not aliased) and warns per call, so the CPU test mesh runs
    the non-donating variants unless a test opts in explicitly.
    Resolve OUTSIDE jit traces (it selects between jit wrappers)."""
    v = os.environ.get("H2O_TPU_DONATE", "").lower()
    if v in ("0", "off", "false", "no"):
        return False
    if v in ("1", "on", "true", "yes"):
        return True
    return backend_is_tpu()


def _enable_compile_cache() -> None:
    """Persistent XLA compilation cache (process-wide, once) — THE one
    place the package configures it.

    The whole-forest tree engine compiles large programs; the disk cache
    makes every process after the first pay steady-state cost only — the
    TPU analog of the reference shipping pre-built Java bytecode rather
    than re-JITting per JVM.

    On by default on an accelerator backend; XLA:CPU AOT reloads warn
    about machine-feature mismatches across processes, so CPU needs the
    explicit opt-in H2O_TPU_COMPILE_CACHE=1 (tests/conftest.py).
    H2O_TPU_COMPILE_CACHE=0|off turns it off everywhere.

    Placement: where JAX_COMPILATION_CACHE_DIR is set JAX itself reads
    it and no directory is set in code; otherwise ``<checkout>/.jax_cache``.
    """
    global _cache_enabled
    if _cache_enabled:
        return
    raw = os.environ.get("H2O_TPU_COMPILE_CACHE", "").strip().lower()
    if raw in _OFF:
        return
    if raw and raw not in _ON:
        raise ValueError(
            f"H2O_TPU_COMPILE_CACHE={raw!r}: only an on/off switch; "
            f"place the cache with JAX_COMPILATION_CACHE_DIR")
    if not raw and not backend_is_tpu():
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    # names are part of the key: the cache strips locations by default,
    # so a program whose only change is its ``h2o.*`` scopes would load
    # the older executable and every profile would show its stale names.
    # File names go in relative to the checkout, so that the key does not
    # move with the directory the checkout lies in.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(_CHECKOUT + os.sep))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _cache_enabled = True


class Cloud:
    """Singleton runtime: device mesh + config + store + job registry."""

    _instance: Optional["Cloud"] = None
    _lock = threading.Lock()

    def __init__(self, args: OptArgs, devices=None):
        self.args = args
        _enable_compile_cache()
        devs = list(devices if devices is not None else jax.devices())
        n = args.nodes or (len(devs) // args.model_axis)
        m = args.model_axis
        s = int(args.slices or 1)
        if n * m > len(devs):
            raise ValueError(
                f"requested mesh {n}x{m} exceeds {len(devs)} devices")
        if s < 1 or n % s != 0:
            raise ValueError(
                f"slices={s} must evenly divide the {n} data shards")
        devs = devs[: n * m]
        if s == 1:
            # flat mesh, byte-identical to the historical layout: same
            # axes, same device order, same shardings — so every compiled
            # program, exec-store key and CPU-tier output is unchanged
            self.mesh = Mesh(
                np.asarray(devs).reshape(n, m), (DATA_AXIS, MODEL_AXIS))
        else:
            # two-level mesh: same flat device list reshaped slice-major,
            # so P((SLICE_AXIS, DATA_AXIS)) visits devices in the flat
            # P(DATA_AXIS) order — shard g holds the same rows either way
            self.mesh = Mesh(
                np.asarray(devs).reshape(s, n // s, m),
                (SLICE_AXIS, DATA_AXIS, MODEL_AXIS))
        # n_nodes stays the TOTAL data-shard count (slices x per-slice
        # nodes): shard quanta, row padding and every verb's statics are
        # independent of how the shards are grouped into ICI islands
        self.n_nodes = n
        self.n_slices = s
        # host control plane
        from h2o_tpu.core.store import DKV
        from h2o_tpu.core.job import JobRegistry
        self.dkv = DKV()
        self.jobs = JobRegistry(
            default_deadline_secs=args.job_deadline_secs,
            default_stall_secs=args.job_stall_secs,
            watchdog_interval=args.watchdog_interval_secs,
            jobs_cap=args.jobs_cap)
        self.session_counter = 0
        if args.hbm_budget:
            from h2o_tpu.core.memory import set_budget
            set_budget(args.hbm_budget)
        # collective-execution gate (see device_gate below): only the
        # host-emulated multi-device topology needs it
        self._device_gate = threading.RLock() if (
            devs[0].platform == "cpu" and len(devs) > 1 and
            os.environ.get("H2O_TPU_DEVICE_GATE", "1").lower()
            not in ("0", "off", "false")) else None
        log.info("Cloud '%s' of size %d formed (mesh %s%dx%d, platform=%s)",
                 args.name, n, f"{s}x" if s > 1 else "", n, m,
                 devs[0].platform)

    def device_gate(self):
        """Serialize multi-device collective programs across host threads.

        XLA:CPU's in-process collectives have no gang scheduler: two
        programs dispatched concurrently from different threads can
        enqueue onto the virtual devices in different orders and
        deadlock at the all-reduce rendezvous (program A holds device 0
        waiting for devices 1-7, which are parked in program B waiting
        for device 0).  Real TPU backends gang-schedule per-core streams
        so this cannot happen there — the gate is a no-op lock off the
        forced-host-device test topology (and can be forced off with
        ``H2O_TPU_DEVICE_GATE=0``).  Held around whole model-build
        bodies (ModelBuilder.train_async), where parallel grids /
        AutoML / segment training create exactly this concurrency;
        single-device programs (the online-scoring engine's bucketed
        predicts) need no gate — they cannot form a rendezvous cycle.
        """
        if self._device_gate is None:
            return contextlib.nullcontext()
        return self._device_gate

    # -- singleton management (the reference's H2O.CLOUD / H2O.SELF statics) --

    @classmethod
    def get(cls) -> "Cloud":
        if cls._instance is None:
            with cls._lock:
                if cls._instance is None:
                    cls._instance = Cloud(OptArgs.from_env())
        return cls._instance

    @classmethod
    def boot(cls, **flags) -> "Cloud":
        """(Re)boot the cloud with explicit flags.  Replaces any prior cloud —
        tests use this to get differently-shaped meshes."""
        with cls._lock:
            cls._instance = Cloud(OptArgs.from_env(**flags))
        return cls._instance

    @classmethod
    def reform(cls, **flags) -> "Cloud":
        """Re-form the cloud on a DIFFERENT mesh shape while keeping the
        control plane — the mesh-resize event (a slice shrank, a node
        pool grew).  The reference cannot do this at all (membership
        locks at the first distributed write, Paxos.java:145-166); here
        the DKV, job registry and session counter carry over and every
        device-backed Frame in the store is re-homed onto the new mesh
        (one host bounce per column — a topology change, not a hot-path
        verb; padding quantum and sharding are both mesh-shaped).
        Checkpoint/resume survives the resize: recovery state is
        host-side, and the tree driver re-pads a checkpointed F carry
        to the new quantum on load (models/tree/driver.py)."""
        with cls._lock:
            old = cls._instance
            newc = Cloud(OptArgs.from_env(**flags))
            if old is not None:
                newc.dkv = old.dkv
                newc.jobs = old.jobs
                newc.session_counter = old.session_counter
            cls._instance = newc
        # drop jitted-trace caches: module-level jits that trace-capture
        # the mesh (histogram collective, uplift engine, quantile
        # refine) would otherwise replay jaxprs built for the old
        # device set on shape-compatible inputs
        jax.clear_caches()
        # the exec store and autotune decisions are keyed per
        # platform×ndev ON DISK, but their in-memory sides are not:
        # a cached executable or a measured lever winner from the old
        # mesh must not be served on the new one
        from h2o_tpu.core.exec_store import exec_store
        from h2o_tpu.core import autotune
        exec_store().clear()
        autotune.invalidate_decisions()
        if old is not None:
            from h2o_tpu.core.frame import Frame
            for key in list(newc.dkv.keys()):
                val = newc.dkv.get(key)
                if isinstance(val, Frame):
                    for v in val.vecs:
                        v._rehome()
                    val._matrix_cache.clear()
            log.info("Cloud re-formed to mesh %s%dx%d (%d frames re-homed)",
                     f"{newc.n_slices}x" if newc.n_slices > 1 else "",
                     newc.n_nodes, newc.args.model_axis,
                     sum(1 for k in newc.dkv.keys()
                         if isinstance(newc.dkv.get(k), Frame)))
        return newc

    @classmethod
    def boot_multihost(cls, coordinator: str, num_processes: int,
                       process_id: int, **flags) -> "Cloud":
        """Multi-host boot: the flatfile-discovery analog.  Each host calls
        this with the same coordinator address; jax.distributed performs the
        barriered rendezvous that Paxos gossip performs in the reference."""
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
        return cls.boot(**flags)

    # -- sharding helpers ---------------------------------------------------

    def data_pspec(self, *rest) -> P:
        """The partition spec of the data axis on THIS mesh: ``P("nodes",
        *rest)`` flat, ``P(("slices", "nodes"), *rest)`` two-level.  Every
        row-sharded in_spec/out_spec and NamedSharding in the data plane
        derives from this, so shard g always holds the same rows on either
        topology (slice-major device order makes the specs equivalent)."""
        if self.n_slices == 1:
            return P(DATA_AXIS, *rest)
        return P((SLICE_AXIS, DATA_AXIS), *rest)

    @property
    def row_sharding(self) -> NamedSharding:
        """Rows sharded over the data axis (chunk-homing analog)."""
        return NamedSharding(self.mesh, self.data_pspec())

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def matrix_sharding(self) -> NamedSharding:
        """(rows, cols) matrices: rows over nodes, cols replicated."""
        return NamedSharding(self.mesh, self.data_pspec(None))

    def row_multiple(self) -> int:
        """Row counts are padded to a multiple of this so every device holds
        an identical-shape, lane-aligned shard (the fixed-shape analog of the
        reference's ~4 MiB chunk quantum, water/fvec/FileVec.java:33-38)."""
        return self.n_nodes * self.args.row_align

    def device_put_rows(self, host_array) -> jax.Array:
        """Pad host rows to the shard quantum and scatter over the mesh."""
        if self.args.client:
            # -client mode (water/H2O.java:391-394): the node participates
            # in the control plane (DKV metadata, jobs, REST) but never
            # homes data — exactly the reference's "join without keys"
            raise RuntimeError(
                "client-mode cloud cannot home frame data "
                "(boot with client=False to shard rows here)")
        from h2o_tpu.core.chaos import chaos
        if chaos().enabled:
            chaos().maybe_fail_device_put()
        # Placement lives in the landing layer: each shard's slice goes
        # straight to its home device (no whole-array single-host put).
        from h2o_tpu.core import landing
        return landing.land_rows(host_array)


def cloud() -> Cloud:
    """The current cloud (boots a default local one on first use)."""
    return Cloud.get()


# -- hierarchical collective helper layer -----------------------------------
#
# The one place in the repo allowed to issue raw flat-axis collectives
# (graftlint GL305 exempts this module).  Each helper reads the cloud at
# TRACE time — topology is static per compiled program, and the exec
# store keys entries by input shardings, so flat and two-level programs
# are automatically distinct cache entries.
#
# Bitwise contract (probed on the 8-virtual-device XLA:CPU mesh, and the
# property the parity matrix in tests/test_two_level_mesh.py gates):
# every helper's two-level lowering produces BITWISE-identical results
# to its flat-mesh lowering for the same global operand.
#
# - hpsum/hpmin/hpmax reduce over the axis PRODUCT ("slices","nodes") in
#   slice-major order rather than spelling two nested psums: the product
#   group enumerates devices in exactly the flat order, so the f32
#   reduction association is independent of the slice split (an explicit
#   psum-then-psum is NOT bitwise-stable — measured, not assumed).  XLA
#   decomposes a cross-DCN all-reduce hierarchically on real topologies
#   (intra-slice reduce, one DCN combine of the reduced payload per
#   level), which is what the byte accounting records.
# - hall_gather gathers the inner level first, then the outer; the
#   (s, q, ...) -> (n, ...) reshape restores flat order exactly.
# - hall_to_all stages the route as one cross-slice exchange of whole
#   per-slice blocks (only the (s-1)/s off-slice fraction moves over
#   DCN; the self-addressed block never leaves the island) followed by
#   an ICI-local exchange — same permutation as the flat all_to_all.


def _static_nbytes(x) -> int:
    """Per-participant payload bytes of a collective operand — static
    shape arithmetic at trace time (x is a tracer)."""
    import jax.numpy as jnp
    size = 1
    for d in jnp.shape(x):
        size *= int(d)
    return size * np.dtype(jnp.result_type(x)).itemsize


def _note(kind: str, tag: str, ici: int, dcn: int) -> None:
    from h2o_tpu.core.diag import DispatchStats
    DispatchStats.note_collective(f"{kind}:{tag}" if tag else kind,
                                  ici, dcn)


def _coll_scope(tag: str):
    """``h2o.coll.<tag>``: the name a collective's device operations
    carry in a profile — the site tag ``note_collective`` gets."""
    return jax.named_scope(f"h2o.coll.{tag or 'untagged'}")


def _preduce(op, x, tag: str):
    c = cloud()
    nb = _static_nbytes(x)
    with _coll_scope(tag):
        if c.n_slices == 1:
            _note(op.__name__, tag, ici=nb, dcn=0)
            return op(x, DATA_AXIS)
        _note(op.__name__, tag, ici=nb, dcn=nb)
        return op(x, (SLICE_AXIS, DATA_AXIS))


def hpsum(x, tag: str = ""):
    """Hierarchical psum over all data shards (flat: ``psum(x, "nodes")``).
    One reduced-payload combine crosses DCN per call on a two-level mesh;
    bitwise-equal to the flat reduction (product-axis group order)."""
    return _preduce(jax.lax.psum, x, tag)


def hpmin(x, tag: str = ""):
    """Hierarchical pmin over all data shards (exact — min is associative)."""
    return _preduce(jax.lax.pmin, x, tag)


def hpmax(x, tag: str = ""):
    """Hierarchical pmax over all data shards (exact — max is associative)."""
    return _preduce(jax.lax.pmax, x, tag)


def hall_gather(x, tag: str = ""):
    """Gather one per-shard operand from every data shard ->
    ``(n_nodes, *x.shape)`` in flat shard order.  Two-level lowering:
    ICI-local gather to ``(q, ...)``, then ONE cross-slice gather of the
    slice-local block, then a pure reshape — DCN carries ``q * nbytes``
    per non-local slice, independent of anything but the operand shape."""
    import jax.numpy as jnp
    c = cloud()
    nb = _static_nbytes(x)
    with _coll_scope(tag):
        if c.n_slices == 1:
            _note("all_gather", tag, ici=nb * (c.n_nodes - 1), dcn=0)
            return jax.lax.all_gather(x, DATA_AXIS)
        s = c.n_slices
        q = c.n_nodes // s
        _note("all_gather", tag, ici=nb * (q - 1), dcn=nb * q * (s - 1))
        g = jax.lax.all_gather(x, DATA_AXIS)          # (q, ...)   ICI
        g = jax.lax.all_gather(g, SLICE_AXIS)         # (s, q, ...) DCN
        return g.reshape((c.n_nodes,) + tuple(jnp.shape(x)))


def hall_to_all(x, tag: str = ""):
    """Bucket exchange: shard i's row-block ``x[j]`` lands on shard j
    (flat: ``all_to_all(x, "nodes", 0, 0)``; x has leading dim n_nodes).
    Two-level lowering routes whole per-slice blocks across DCN first
    (only off-slice blocks cross — the self block stays on the island),
    then scatters within each slice over ICI.  Same permutation, bitwise
    payloads; DCN bytes are the off-slice fraction of the buffer."""
    import jax.numpy as jnp
    c = cloud()
    nb = _static_nbytes(x)
    n = c.n_nodes
    with _coll_scope(tag):
        if c.n_slices == 1:
            _note("all_to_all", tag, ici=nb * (n - 1) // n, dcn=0)
            return jax.lax.all_to_all(x, DATA_AXIS, 0, 0)
        s = c.n_slices
        q = n // s
        _note("all_to_all", tag, ici=nb * (q - 1) // q,
              dcn=nb * (s - 1) // s)
        rest = tuple(jnp.shape(x))[1:]
        b = x.reshape((s, q) + rest)
        b = jax.lax.all_to_all(b, SLICE_AXIS, 0, 0)   # DCN: per-slice blocks
        b = jax.lax.all_to_all(b, DATA_AXIS, 1, 1)    # ICI: in-slice scatter
        return b.reshape((n,) + rest)


def hshard_index():
    """Global data-shard index of the calling program instance, in flat
    shard order (0..n_nodes-1) on either topology."""
    c = cloud()
    if c.n_slices == 1:
        return jax.lax.axis_index(DATA_AXIS)
    q = c.n_nodes // c.n_slices
    return (jax.lax.axis_index(SLICE_AXIS) * q
            + jax.lax.axis_index(DATA_AXIS))


def hall_gather_inner(x, tag: str = ""):
    """SLICE-LOCAL gather: ``(q, *x.shape)`` from the shards of the
    calling instance's own ICI island only — never touches DCN.  On the
    flat mesh the island is the whole cloud (``q == n_nodes``).  Used by
    two-level kernels that combine a slice-local partial before the one
    DCN exchange (e.g. the group-by distinct-count upper bound)."""
    nb = _static_nbytes(x)
    c = cloud()
    q = c.n_nodes // c.n_slices
    _note("all_gather", tag, ici=nb * (q - 1), dcn=0)
    with _coll_scope(tag):
        return jax.lax.all_gather(x, DATA_AXIS)


def hpsum_slices(x, tag: str = ""):
    """Reduce a slice-replicated value across slices only — the one DCN
    combine of a hierarchical reduction whose inner stage was computed
    slice-locally.  Identity on the flat mesh (one slice, nothing to
    combine)."""
    c = cloud()
    if c.n_slices == 1:
        return x
    nb = _static_nbytes(x)
    _note("psum", tag, ici=0, dcn=nb)
    with _coll_scope(tag):
        return jax.lax.psum(x, SLICE_AXIS)


def pad_rows(x, fill=0):
    """``x`` padded at the end of its rows with ``fill`` to a non-zero
    multiple of the cloud's shard count, what a ``shard_map`` over the
    data axis takes; unchanged where its rows are one (a frame's always
    are)."""
    import jax.numpy as jnp
    n, rows = cloud().n_nodes, x.shape[0]
    pad = max(n, -(-rows // n) * n) - rows
    if not pad:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1),
                   constant_values=fill)


def hsum_rows(x, tag: str = "rows.sum"):
    """Sum of a row-sharded (R, ...) array over its rows, computed where
    the rows live: each shard sums its own, then one ``hpsum`` (no
    collective the partitioner would put in on its own)."""
    import jax.numpy as jnp
    return _sum_rows_program(pad_rows(jnp.asarray(x)), tag=tag,
                             mesh=cloud().mesh)


@functools.partial(jax.jit, static_argnames=("tag", "mesh"))
def _sum_rows_program(x, tag: str, mesh):
    import jax.numpy as jnp
    return shard_map_compat(
        lambda s: hpsum(jnp.sum(s, axis=0), tag), mesh=mesh,
        in_specs=(cloud().data_pspec(*([None] * (x.ndim - 1))),),
        out_specs=P(), check_vma=False)(x)


def hbroadcast_rows(row, rows: int):
    """``(rows, *row.shape)`` float32 copies of one row, row-sharded over
    the data axis as a frame's columns are: each shard makes its own
    rows, no whole array is laid on one device, and a carry that starts
    here has the sharding of the one a program hands back."""
    c = cloud()
    row = jax.numpy.asarray(row)
    return _broadcast_rows_program(
        row, rows=int(rows),
        sharding=NamedSharding(c.mesh, c.data_pspec(*([None] * row.ndim))))


@functools.partial(jax.jit, static_argnames=("rows", "sharding"))
def _broadcast_rows_program(row, rows: int, sharding):
    import jax.numpy as jnp
    return jax.lax.with_sharding_constraint(
        jnp.broadcast_to(row[None], (rows,) + row.shape).astype(jnp.float32),
        sharding)
