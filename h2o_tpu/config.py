"""Central registry for the tiered-column-store tuning knobs.

Every knob is an environment variable read at CALL time (never cached at
import), so tests can monkeypatch ``os.environ`` and long-lived sessions
can retune between jobs.  The accessors below are the single source of
truth for defaults; the modules that consume them (``core/memory.py``,
``models/tree/shared_tree.py``) import from here.

Knobs
-----

``H2O_TPU_HBM_BUDGET`` — bytes of device HBM the tier manager may hold
    resident before LRU-spilling cold column blocks to host.  ``0``
    (default) means unbounded: nothing spills and streaming's ``auto``
    gate stays closed.
    ``MemoryManager.set_budget()`` overrides the env at runtime.

``H2O_TPU_HOST_BUDGET`` — bytes of host RAM the middle tier may hold
    before cold blocks sink further to the persist tier (the
    reference's "ice": compressed npz spill files).  ``0`` (default)
    means unbounded host tier; persistence then only happens via an
    explicit ``persist_sweep()``.

``H2O_TPU_TIER_BLOCK_ROWS`` — per-shard row quantum (default 65536) for
    block-granular residency and for the streamed-training window.  It
    is the OOM ladder's shrink unit: under device-OOM the streaming
    ladder halves it (re-aligned to ``row_multiple``) and retries, so
    the value must stay a multiple of the row alignment for bitwise
    window parity.

``H2O_TPU_PREFETCH_DEPTH`` — how many upcoming windows the streamer
    stages host->device ahead of consumption (default 1, i.e. double
    buffering).  Raising it hides more page-in latency at the cost of
    ``depth * window_bytes`` extra transient HBM.

``H2O_TPU_TIER_STREAM`` — streamed GBM bin-preparation mode: ``auto``
    (default) streams only when an HBM budget is set and the binned
    matrix would not fit; ``1``/``on`` forces streaming; ``0``/``off``
    disables it even under pressure.

Lazy Rapids planner knobs (``rapids/plan.py`` / ``core/fuse.py``)
-----------------------------------------------------------------

``H2O_TPU_RAPIDS_FUSE`` — tri-state fusion lever for the lazy Rapids
    planner.  ``1`` forces every fusable verb chain through the fused
    single-program path; ``0`` forces the eager per-verb chain (the
    bitwise parity oracle); unset defers to the ``rapids.fuse``
    autotuner lever (measured fused-vs-per-verb per chain kind x row
    bucket on TPU; the per-verb reference elsewhere).  Tests and the
    audit gate set ``1`` explicitly — the same
    convention as ``H2O_TPU_BINS_PACK``.

``H2O_TPU_RAPIDS_FUSE_MAX_VERBS`` — cap on the number of verbs the
    planner folds into one fused region (default 8).  Longer chains
    split at the cap; each split region still fuses independently.

Serving-fleet knobs (``serve/replica.py``)
------------------------------------------

``H2O_TPU_SERVE_REPLICAS`` — number of serve replicas the fleet layer
    spins up (default 1: the plain single-registry path).  Replicas are
    in-process registries sharing one ScoringEngine, so every replica
    warm-starts kernels + autotune decisions from the shared exec store
    (``H2O_TPU_EXEC_STORE_DIR``) with zero extra compiles.

Breaker knobs (``serve/breaker.py``) — pressure scores are normalized
to [0, 1]:

``H2O_TPU_BREAKER_SOFT`` — score at which the breaker enters SHEDDING
    (shrink batch quanta + refuse a fraction with 429).  Default 0.85.

``H2O_TPU_BREAKER_HARD`` — score at which the breaker trips OPEN
    (refuse everything with 503 until the cooldown).  Default 0.97.

``H2O_TPU_BREAKER_OPEN_SECS`` — OPEN cooldown before HALF_OPEN probes
    are admitted.  Default 5.0.

``H2O_TPU_BREAKER_PROBES`` — live requests admitted in HALF_OPEN; all
    must succeed (with a calm score) to close.  Default 3.

``H2O_TPU_BREAKER_INTERVAL_MS`` — minimum milliseconds between breaker
    telemetry re-evaluations (admissions in between reuse the last
    verdict).  Default 50.

``H2O_TPU_BREAKER_STALL_SOFT`` — demand-page stalls per sample window
    that count as a fully-saturated stall signal.  Default 4.

Adaptive micro-batching knobs (``serve/batcher.py`` tuner) — bounds are
pow2 so adaptation never leaves the engine's compiled bucket set:

``H2O_TPU_SERVE_ADAPTIVE`` — ``1`` enables the adaptive batch tuner by
    default for new deployments (default ``0``: static knobs; the
    REST/``ServingConfig`` field overrides per deployment).

``H2O_TPU_SERVE_MIN_BATCH`` / ``H2O_TPU_SERVE_MAX_BATCH`` — inclusive
    pow2 bounds the tuner may move ``max_batch`` within (defaults 1 and
    128; non-pow2 values are rounded up to the next bucket).

Multi-tenant knobs (``core/tenant.py`` / ``core/memory.py``)
------------------------------------------------------------

``H2O_TPU_TENANT_SLOTS`` — concurrent admissions the fair-share queue
    dispatches onto the user pool (default 0 = the pool's worker
    count).  Set to 1 in tests to force strict stride ordering.

``H2O_TPU_TENANT_QUEUE`` — default per-tenant admission-queue bound
    (default 16); a tenant's own ``max_queue`` overrides it.  A full
    queue refuses with a classified 429 ``AdmissionRejected``.

``H2O_TPU_TENANT_HIGHWATER`` — global HBM residency fraction (default
    0.9) below which eviction pressure from tenant A may ONLY spill
    A's own (or untagged) cold blocks.  Past it, survival beats
    isolation: other tenants' blocks become eligible and each such
    spill is counted as a ``cross_tenant_eviction`` — the soak's
    invariant metric (must be 0 below high-water).

Streaming follow-mode knobs (``stream/ingest.py`` / ``refresh.py``)
-------------------------------------------------------------------

``H2O_TPU_STREAM_POLL_MS`` — milliseconds a ``ChunkReader(follow=True)``
    sleeps between re-polls of a source that returned no new bytes
    (default 50).

``H2O_TPU_STREAM_HOLDOUT`` — default per-chunk row fraction a
    ``StreamPipeline`` holds out of training for the swap gate's
    validation split (default 0.0 = judge on training rows, the
    pre-PR-20 behavior).  The holdout is deterministic per chunk
    (seeded from the pipeline id + chunk index), so replays carve the
    same rows.
"""

import os

__all__ = [
    "hbm_budget", "host_budget", "tier_block_rows", "prefetch_depth",
    "tier_stream_mode",
    "rapids_fuse_mode", "rapids_fuse_max_verbs",
    "serve_replicas", "breaker_soft", "breaker_hard",
    "breaker_open_secs", "breaker_probes", "breaker_interval_ms",
    "breaker_stall_soft", "serve_adaptive_default", "serve_min_batch",
    "serve_max_batch",
    "tenant_slots", "tenant_queue_bound", "tenant_highwater",
    "stream_poll_ms", "stream_holdout",
]


def hbm_budget() -> int:
    """Device-HBM residency budget in bytes; 0 = unbounded."""
    return int(os.environ.get("H2O_TPU_HBM_BUDGET") or 0)


def host_budget() -> int:
    """Host-tier residency budget in bytes; 0 = unbounded."""
    return int(os.environ.get("H2O_TPU_HOST_BUDGET", "0") or 0)


def tier_block_rows() -> int:
    """Per-shard row quantum for tier blocks and streaming windows."""
    return int(os.environ.get("H2O_TPU_TIER_BLOCK_ROWS", "65536") or 65536)


def prefetch_depth() -> int:
    """Windows staged ahead by the streamer (1 = double buffering)."""
    return int(os.environ.get("H2O_TPU_PREFETCH_DEPTH", "1") or 1)


def tier_stream_mode() -> str:
    """``auto`` | ``on``/``1`` | ``off``/``0`` (normalized, lowercase)."""
    return os.environ.get("H2O_TPU_TIER_STREAM", "auto").lower()


def rapids_fuse_mode() -> str:
    """``auto`` (defer to the lever) | ``on``/``1`` | ``off``/``0``."""
    v = os.environ.get("H2O_TPU_RAPIDS_FUSE", "").lower()
    if v in ("1", "on", "true", "yes"):
        return "on"
    if v in ("0", "off", "false", "no"):
        return "off"
    return "auto"


def rapids_fuse_max_verbs() -> int:
    """Max verbs per fused region (longer chains split at the cap)."""
    return max(2, int(os.environ.get("H2O_TPU_RAPIDS_FUSE_MAX_VERBS",
                                     "8") or 8))


def serve_replicas() -> int:
    """Serve-fleet size (default 1 = single-registry path)."""
    return max(1, int(os.environ.get("H2O_TPU_SERVE_REPLICAS", "1") or 1))


def breaker_soft() -> float:
    """Pressure score that enters SHEDDING (shrink + 429s)."""
    return float(os.environ.get("H2O_TPU_BREAKER_SOFT", "0.85") or 0.85)


def breaker_hard() -> float:
    """Pressure score that trips OPEN (503s until cooldown)."""
    return float(os.environ.get("H2O_TPU_BREAKER_HARD", "0.97") or 0.97)


def breaker_open_secs() -> float:
    """OPEN cooldown seconds before HALF_OPEN probes are admitted."""
    return float(os.environ.get("H2O_TPU_BREAKER_OPEN_SECS", "5.0") or 5.0)


def breaker_probes() -> int:
    """Live requests admitted while HALF_OPEN."""
    return max(1, int(os.environ.get("H2O_TPU_BREAKER_PROBES", "3") or 3))


def breaker_interval_ms() -> float:
    """Minimum ms between breaker telemetry re-evaluations."""
    return float(os.environ.get("H2O_TPU_BREAKER_INTERVAL_MS", "50")
                 or 50.0)


def breaker_stall_soft() -> float:
    """Demand-page stalls per sample that saturate the stall signal."""
    return float(os.environ.get("H2O_TPU_BREAKER_STALL_SOFT", "4") or 4.0)


def serve_adaptive_default() -> bool:
    """Whether new deployments default to the adaptive batch tuner."""
    return os.environ.get("H2O_TPU_SERVE_ADAPTIVE", "0").lower() in (
        "1", "on", "true", "yes")


def serve_min_batch() -> int:
    """Lower pow2 bound for the adaptive tuner's ``max_batch``."""
    return max(1, int(os.environ.get("H2O_TPU_SERVE_MIN_BATCH", "1") or 1))


def serve_max_batch() -> int:
    """Upper pow2 bound for the adaptive tuner's ``max_batch``."""
    return max(1, int(os.environ.get("H2O_TPU_SERVE_MAX_BATCH", "128")
                      or 128))


def tenant_slots() -> int:
    """Concurrent fair-share admissions (0 = user-pool worker count)."""
    return max(0, int(os.environ.get("H2O_TPU_TENANT_SLOTS", "0") or 0))


def tenant_queue_bound() -> int:
    """Default per-tenant admission-queue bound (0 = unbounded)."""
    return max(0, int(os.environ.get("H2O_TPU_TENANT_QUEUE", "16") or 16))


def tenant_highwater() -> float:
    """Global HBM fraction above which cross-tenant spills are legal."""
    return float(os.environ.get("H2O_TPU_TENANT_HIGHWATER", "0.9")
                 or 0.9)


def stream_poll_ms() -> float:
    """Follow-mode re-poll interval for a quiet stream source (ms)."""
    return float(os.environ.get("H2O_TPU_STREAM_POLL_MS", "50") or 50.0)


def stream_holdout() -> float:
    """Default per-chunk validation-holdout row fraction (0 = off)."""
    return min(0.9, max(0.0, float(
        os.environ.get("H2O_TPU_STREAM_HOLDOUT", "0") or 0.0)))
