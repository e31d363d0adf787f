"""Cluster/runtime configuration flags.

TPU-native analog of H2O's single ``OptArgs`` POJO parsed from argv with an
``ai.h2o.*`` system-property overlay (reference: water/H2O.java:233-466,
2355-2366).  Here flags come from constructor kwargs with an ``H2O_TPU_*``
environment-variable overlay, and the parsed config seeds the Cloud singleton.

Resilience knobs NOT held on OptArgs (read directly from env by their
owning modules, like the chaos flags, so they work before a cloud boots):

- retry policy (core/resilience.py, applied to every persist byte-store
  op and recovery checkpoint write):
  ``H2O_TPU_RETRY_MAX_ATTEMPTS`` (4), ``H2O_TPU_RETRY_BASE_DELAY``
  (0.05 s), ``H2O_TPU_RETRY_MAX_DELAY`` (2 s),
  ``H2O_TPU_RETRY_TOTAL_DEADLINE`` (60 s across attempts; 0 = none);
- fault injection (core/chaos.py): ``H2O_TPU_CHAOS_JOB``,
  ``H2O_TPU_CHAOS_DEVICE_PUT``, ``H2O_TPU_CHAOS_PERSIST``
  (probabilities), ``H2O_TPU_CHAOS_PERSIST_TRANSIENT`` (fail the first
  N attempts of each persist op, then succeed),
  ``H2O_TPU_CHAOS_STALL`` + ``H2O_TPU_CHAOS_STALL_SECS`` (job-stall
  injector for the watchdog), ``H2O_TPU_CHAOS_SCORE_SLOW[_MS]`` (slow
  online-scoring batches), ``H2O_TPU_CHAOS_TRANSFER_SLOW[_MS]`` (slow
  device->host block pulls), ``H2O_TPU_CHAOS_OOM`` (probability) /
  ``H2O_TPU_CHAOS_OOM_TRANSIENT`` (fail the first N attempts at each
  dispatch site with a synthetic RESOURCE_EXHAUSTED),
  ``H2O_TPU_CHAOS_SEED``;
- OOM degradation ladder (core/oom.py, wrapped around every device
  dispatch choke point): ``H2O_TPU_OOM_SWEEP_RETRIES`` (default 2 —
  how many spill-the-LRU-and-retry attempts before the ladder descends
  to quantum shrinking / host fallback / terminal job failure);
- unified executable store (core/exec_store.py — the one compiled-
  program cache under the MRTask verbs, the serve predict path, the
  munge kernels and the tree-engine executable pair):
  ``H2O_TPU_EXEC_STORE`` (LRU capacity in entries, default 256),
  ``H2O_TPU_EXEC_STORE_DIR`` (directory for persistent AOT-serialized
  executables; unset = disk layer off.  A fresh process warms its
  kernel set from here — disk entries are schema-versioned and
  invalidate cleanly on any key mismatch: schema bump, h2o_tpu or jax
  version, backend topology, content fingerprint [function body /
  model parameter digest — a retrained model under a reused model_id
  or an upgraded kernel body rebuilds instead of loading stale], or
  header corruption.  SECURITY: entries are unpickled on load, which
  is code execution — point this only at a directory writable solely
  by principals trusted to run code in every process that warms from
  it; the store writes 0o600 files in a 0o700 dir and warns if the
  dir is group/other-writable), and
  ``H2O_TPU_COMPILE_CACHE`` (XLA persistent compile cache on-off
  switch, core/cloud.py — the fallback warm-start layer for entries
  executable serialization cannot cover, e.g. jit-level
  shape-polymorphic programs and closure map fns; the directory is
  ``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``);
- buffer donation: ``H2O_TPU_DONATE`` (the store's donation policy;
  default on-TPU-only — donating and non-donating variants are
  distinct store entries and OOM retries auto-route to the
  non-donating twin);
- scale-out data plane (core/munge.py shard_map collectives — the
  chunk-homed MRTask munge verbs):
  ``H2O_TPU_DEVICE_MUNGE`` (0 = host-NumPy parity-oracle paths),
  ``H2O_TPU_SHARD_MUNGE`` (default 1: sort/merge/group-by/filter run
  as shard_map collectives over the mesh ``nodes`` axis — rows stay
  home-sharded, only splitters/partials/per-shard counts cross the
  interconnect; 0 = the PR 4 global-jnp device kernels, where XLA may
  gather rows cross-shard), and
  ``H2O_TPU_SORT_OVERSAMPLE`` (default 4: sample-sort splitter samples
  per shard are oversample x n_nodes — more samples tighten bucket
  balance in the exchange at the cost of a wider replicated splitter
  sort);
- kernel autotuner (core/autotune.py — measured per-backend selection
  of the tunable kernel levers, decisions persisted next to
  ``H2O_TPU_EXEC_STORE_DIR`` executables):
  ``H2O_TPU_AUTOTUNE`` (``auto`` default: probe on TPU backends only,
  off-TPU the reference variants win with zero probe runs; ``0``/off =
  always reference variants, never probe; ``force`` = probe on any
  backend — what tests/test_autotune.py uses),
  ``H2O_TPU_AUTOTUNE_REPS`` (timed reps per candidate after the
  untimed compile run, default 5 — winner is the median),
  ``H2O_TPU_AUTOTUNE_ROWS`` (probe workload row cap, default 65536,
  rounded up to the mesh row multiple) and
  ``H2O_TPU_AUTOTUNE_MARGIN`` (default 0.03 — a non-reference variant
  must beat the reference by this fractional margin to win, so noise
  never flips a lever).  The per-lever knobs are TRI-STATE —
  ``H2O_TPU_HIST_PALLAS`` (hist.kernel: fused Pallas histogram vs the
  one-hot-matmul XLA reference), ``H2O_TPU_MATMUL_ROUTE``
  (tree.matmul_route: one-hot-matmul row routing vs gather),
  ``H2O_TPU_SIBLING_SUBTRACT`` (tree.sibling_subtract: left-child
  histogram + parent-minus-left vs full rebuild) and
  ``H2O_TPU_BINS_PACK`` (tree.bins_dtype: the binned feature matrix
  carried at the narrowest dtype its fine bin count permits — uint8
  iff the NA sentinel F <= 255, int16 iff F <= 32767 — vs the int32
  reference; ops/binpack.py owns the decode contract, kernels widen
  in-register per tile, and the parity gate is BITWISE, tol (0, 0),
  since packing must not change a single forest bit) and
  ``H2O_TPU_STATS_DTYPE`` (tree.stats_dtype: gradient/hessian stats
  quantized per tree to an integer carrier with stochastic rounding
  keyed off the per-tree fold_in key, histogram tables accumulated in
  exact int32 and dequantized once per level at the table;
  ops/statpack.py owns the decode contract and graftlint GL631 bans
  f32 re-widening of the carrier anywhere else.  Also accepts the
  carrier names ``int16``/``int8``/``f32`` directly; ``1`` means
  int16.  Unlike bins packing the gate is NOT bitwise — each table
  entry moves by < max|f|/qmax per row — so the lever's tolerance band
  is (0.02, 0.05) at the table and tests pin whole-forest
  metrics to statpack.METRIC_TOL.  Unset on CPU resolves to the f32
  reference with zero probes and stays bitwise-identical to the
  pre-quantization engine) each accept ``1``
  (force on, no probe), ``0`` (force off, no probe) or unset/``auto``
  (defer to the autotuner's parity-gated, persisted decision).  A
  candidate that fails the parity gate against its reference output is
  disqualified for that backend — a miscompiling kernel degrades to
  the reference instead of corrupting training;
- streaming ingest + online refresh (h2o_tpu/stream — the
  train-on-fresh-data pipeline: chunked parse -> append-able Frames ->
  warm-start retrain -> serve-alias hot-swap):
  ``H2O_TPU_STREAM_CHUNK_ROWS`` (target rows per ingest chunk, default
  4096 — the byte budget per source read derives from the sampled mean
  record length; chunk landings are pow2-shape-bucketed device block
  writes, so same-sized chunks cost zero steady-state recompiles),
  ``H2O_TPU_STREAM_REFRESH_CHUNKS`` (retrain cadence in chunks, default
  5 — GBM/DRF checkpoint-resume new tree blocks, GLM warm-starts from
  the previous beta), ``H2O_TPU_STREAM_LAG_BOUND`` (0 = unbounded;
  chunks-landed minus chunks-trained above this flags the pipeline
  ``lagging`` at GET /3/Stream and attaches a job warning), and the
  stream chaos injectors ``H2O_TPU_CHAOS_STREAM_TRUNCATE``
  (probability) / ``H2O_TPU_CHAOS_STREAM_TRUNCATE_TRANSIENT`` (fail
  the first N reads of each source, then succeed — proves the retry
  loop heals a truncated/flaky source) and
  ``H2O_TPU_CHAOS_STREAM_SLOW`` + ``H2O_TPU_CHAOS_STREAM_SLOW_MS``
  (stalled source reads);
- graftaudit recorder tiers (lint/audit.py + core/lockwitness.py —
  the IR executable auditor and the runtime lock witness behind
  ``python -m h2o_tpu.lint --tier ir|runtime`` and GET /3/Audit):
  ``H2O_TPU_AUDIT`` (default off: the exec store records a compact
  per-AOT-compile summary — donation aliasing, host custom-call
  targets, input/output shardings, per-site aval churn — for the
  GL701–GL704 rules; recording is compile-time-only, the steady-state
  dispatch path is untouched), ``H2O_TPU_AUDIT_CHURN`` (default 8 —
  distinct argument-aval keys per dispatch site before GL704 calls it
  a shape-bucketing regression) and ``H2O_TPU_LOCK_WITNESS`` (default
  off; tests/conftest.py turns it on for the whole suite: the named
  supervisor/store/memory/exec-store/serving locks are created through
  the witness factory, which records the real acquisition-order graph
  for GL801 cycle detection and flags device dispatch under any
  witnessed lock as GL802.  Decided at lock CREATION time — set it
  before the first h2o_tpu import; off means plain ``threading``
  primitives and zero overhead).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env(name: str, default, cast):
    raw = os.environ.get("H2O_TPU_" + name.upper())
    if raw is None:
        return default
    if cast is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return cast(raw)


@dataclasses.dataclass
class OptArgs:
    """Runtime flags.  Mirrors the semantics (not the transport) of the
    reference's CLI surface: cluster name, ports, log level, recovery dir."""

    # -name: cluster identity (used in REST /3/Cloud responses)
    name: str = "h2o-tpu"
    # -baseport / port for the REST server
    port: int = 54321
    ip: str = "127.0.0.1"
    # data-axis size override: number of mesh "nodes" (None = all local devices)
    nodes: Optional[int] = None
    # outer data-axis level: number of ICI islands ("slices") the data
    # shards are grouped into.  1 (default) = today's flat mesh with
    # byte-identical programs; >1 grows the mesh to
    # (slices, nodes/slices, model) and every collective consumer runs
    # through the core/cloud.py hierarchical helpers (hpsum/hall_gather/
    # hall_to_all): bulk traffic stays inside an ICI island, one
    # table-sized combine crosses DCN per level.  ``nodes`` stays the
    # TOTAL data-shard count, so shard quanta and verb statics are
    # independent of how the shards are grouped.  H2O_TPU_SLICES env.
    slices: int = 1
    # second mesh axis for model/tensor parallelism inside an algorithm
    model_axis: int = 1
    # -log_level
    log_level: str = "INFO"
    # -ice_root equivalent: spill/checkpoint directory
    ice_root: str = "/tmp/h2o_tpu"
    # -auto_recovery_dir equivalent (job-level fault tolerance, SURVEY §5.3)
    auto_recovery_dir: Optional[str] = None
    # default compute dtype for frame matrices fed to the MXU
    compute_dtype: str = "float32"
    # deterministic reductions (reference: _reproducibleHistos)
    reproducible: bool = True
    # row-shard padding multiple per device (TPU lane friendliness)
    row_align: int = 128
    # HBM budget in bytes for the frame data plane (0 = unlimited);
    # the Cleaner-analog spills LRU columns to host above it
    # (core/memory.py; reference water/Cleaner.java:10-12)
    hbm_budget: int = 0
    # TLS for the REST server (reference -jks/-ssl flags, water/webserver):
    # PEM cert + key paths; both set => REST serves https
    ssl_cert: Optional[str] = None
    ssl_key: Optional[str] = None
    # Basic auth (reference -hash_login/JAAS modules): "user:password".
    # One pair — the reference's hash-file multi-user store can layer on.
    basic_auth: Optional[str] = None
    # LDAP auth (reference -ldap_login + JAAS LdapLoginModule): Basic
    # credentials are verified by an LDAPv3 simple bind against
    # ldap_url, with the DN formed from ldap_dn_template ("{}" is the
    # username, e.g. "uid={},ou=people,dc=example,dc=com")
    ldap_url: Optional[str] = None
    ldap_dn_template: Optional[str] = None
    # -client mode: join the control plane without homing data
    # (water/H2O.java:391-394); client nodes never shard frame rows
    client: bool = False
    # job deadlines + watchdog (core/job.py): default wall-clock budget
    # per job (0 = unbounded; jobs may override per-instance) and the
    # stall window — a RUNNING job with no update() heartbeat for this
    # long is expired FAILED(TimeoutError) and its pool slot reclaimed
    job_deadline_secs: float = 0.0
    job_stall_secs: float = 0.0
    # watchdog scan period
    watchdog_interval_secs: float = 0.5
    # registry bound: terminal jobs past this count are LRU-evicted
    jobs_cap: int = 512

    @classmethod
    def from_env(cls, **overrides) -> "OptArgs":
        args = cls()
        for f in dataclasses.fields(cls):
            setattr(args, f.name, _env(f.name, getattr(args, f.name),
                                       _cast_for(f.type)))
        for k, v in overrides.items():
            if not hasattr(args, k):
                raise ValueError(f"unknown flag: {k}")
            setattr(args, k, v)
        return args


def _cast_for(tp) -> type:
    tp = str(tp)
    if "bool" in tp:
        return bool
    if "float" in tp:
        return float
    if "int" in tp:
        return int
    return str
