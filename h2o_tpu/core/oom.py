"""Device-OOM recovery — the degradation ladder.

The reference platform's defining robustness trait is that it degrades
instead of dying: water/Cleaner.java swaps cold Values to disk under
heap pressure and water/MemoryManager.java retries allocations after
OOM callbacks, so a job that outgrows the heap slows down rather than
killing the cloud.  core/memory.py is the accounting half of that story
(LRU spill under ``H2O_TPU_HBM_BUDGET``); this module is the RECOVERY
half: an XLA ``RESOURCE_EXHAUSTED`` raised inside a dispatch no longer
propagates straight up and takes the job (or the process) with it.

``oom_ladder(site, attempt, ...)`` wraps every device dispatch choke
point — core/mrtask.py (map_reduce / map_frame / mutate_array), the
Rapids munge verbs, the tree-driver block loop, and the serving
engine's batch predict — and walks a ladder on :func:`is_device_oom`
failures:

(a) **sweep** — spill ALL cold columns via ``MemoryManager.sweep()``
    and retry at the same work quantum (bounded by
    ``H2O_TPU_OOM_SWEEP_RETRIES``, default 2);
(b) **shrink** — reduce the work quantum via the caller's ``shrink()``
    hook (halve the tree block, split the serve micro-batch) and retry,
    recording a degradation — smaller quanta, same math: outputs stay
    bitwise-identical (the tree engine keys each tree's RNG off its
    ABSOLUTE index, so any block partition reproduces the same forest);
(c) **host fallback** — for the munge verbs, run the ``*_host`` parity
    oracle instead (same values by the device/host parity contract);
(d) **terminal** — raise :class:`OOMError` with an actionable
    diagnostic (resident bytes, budget, largest holders).  OOMError is
    an ordinary Exception: it fails the JOB through the normal
    Job.FAILED path, never the process, and leaves the DKV / job
    registry / recovery snapshots consistent so ``Recovery`` resume
    still works.

Every rung is observable: ``stats()`` feeds ``GET /3/Resilience`` and
the pytest session summary; the deterministic chaos injector
(``H2O_TPU_CHAOS_OOM_TRANSIENT=N``, core/chaos.py) exercises the full
ladder on CPU CI without real HBM pressure.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, Optional

from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.log import get_logger

log = get_logger("oom")

# message markers of an XLA / jaxlib allocation failure
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED",
                "Resource exhausted", "Out of memory", "out of memory",
                "failed to allocate")

# exception class names that can carry a device allocation failure
_OOM_CLASSES = ("XlaRuntimeError", "JaxRuntimeError", "RuntimeError",
                "InternalError")


class OOMError(RuntimeError):
    """Terminal rung of the ladder: device memory exhausted at ``site``
    and every recovery rung failed.  Carries the MemoryManager
    diagnostic; fails the job, never the process.

    Single-argument construction re-raises a preformatted message —
    Job.join clones a failed job's exception as ``type(exc)(*exc.args)``
    and must get the same text back."""

    def __init__(self, site: str, diagnostic: Optional[str] = None):
        if diagnostic is None:
            super().__init__(str(site))
            self.site = ""
        else:
            super().__init__(
                f"device out of memory at {site} after exhausting the "
                f"degradation ladder (sweep -> shrink -> fallback); "
                f"{diagnostic}")
            self.site = site


# message markers of a Mosaic/Pallas custom-kernel compile failure — a
# shape Mosaic refuses must degrade to the portable XLA path, not kill
# the training job (chip_smoke.py proves the HIGGS shapes do compile)
_KERNEL_MARKERS = ("Mosaic", "mosaic", "Pallas", "pallas", "VMEM",
                   "custom_call_target", "tpu_custom_call")


def is_kernel_compile_failure(exc: BaseException) -> bool:
    """Classify an exception as a custom-kernel (Mosaic/Pallas) lowering
    or compile failure — recoverable by re-dispatching through the
    portable XLA path.  Device OOMs are NOT kernel failures (they walk
    the memory ladder instead)."""
    if isinstance(exc, OOMError) or is_device_oom(exc):
        return False
    msg = f"{type(exc).__name__}: {exc}"
    return any(m in msg for m in _KERNEL_MARKERS)


def kernel_fallback(site: str, run: Callable[[bool], object], *,
                    pallas: bool):
    """Run ``run(pallas)``; on a Mosaic/Pallas kernel-compile failure
    with the fused kernel enabled, record a ladder event and re-dispatch
    ``run(False)`` — the portable XLA executable (a distinct static-arg
    program, so the broken kernel is never cached).  Everything else
    propagates untouched.  The chaos injector
    (``H2O_TPU_CHAOS_KERNEL_REJECT``) fires here so CPU CI can walk the
    rejection path — including the hist_pallas VMEM gate shape — without
    a real Mosaic failure."""
    from h2o_tpu.core.chaos import chaos
    try:
        if pallas:
            chaos().maybe_kernel_reject(site)
        return run(pallas)
    except Exception as e:  # noqa: BLE001 — reclassified below
        if not (pallas and is_kernel_compile_failure(e)):
            raise
        _note(site, "kernel_fallbacks")
        log.warning("%s: Pallas kernel failed to compile (%s); degrading "
                    "to the portable XLA histogram path", site,
                    str(e)[:200])
        return run(False)


def fused_fallback(site: str, run_fused: Callable[[], object],
                   run_unfused: Callable[[], object]):
    """Run a planner-fused Rapids region; if the region's own OOM
    ladder exhausts (terminal :class:`OOMError`) or the fused program
    hits an unrecovered device OOM, record the ``unfused_fallbacks``
    resilience rung and replay the region as the eager per-verb chain —
    the ``H2O_TPU_RAPIDS_FUSE=0`` parity oracle, so the degraded result
    is still bitwise.  Everything else propagates untouched: a fused
    region must never mask a non-memory failure behind a silent
    replan.  The chaos injector
    (``H2O_TPU_CHAOS_REGION_OOM_TRANSIENT``) fires here so CPU CI can
    walk the degradation path — the region-level OOM that the per-verb
    chain does not share — without a real allocation failure."""
    from h2o_tpu.core.chaos import chaos
    try:
        chaos().maybe_region_oom(site)
        return run_fused()
    except Exception as e:  # noqa: BLE001 — reclassified below
        if not (isinstance(e, OOMError) or is_device_oom(e)):
            raise
        _note(site, "unfused_fallbacks")
        log.warning("%s: fused region OOMed beyond the ladder (%s); "
                    "degrading to the unfused per-verb chain", site,
                    str(e)[:200])
        return run_unfused()


def is_device_oom(exc: BaseException) -> bool:
    """Classify an exception as a recoverable device OOM (XLA
    RESOURCE_EXHAUSTED / jaxlib allocation failure / injected chaos
    OOM).  A terminal :class:`OOMError` is NOT recoverable — the ladder
    already ran.  A device/slice LOSS is not an OOM either: no amount
    of sweeping or shrinking brings a preempted slice back, so it must
    reach the membership layer instead of walking the memory ladder."""
    from h2o_tpu.core.chaos import ChaosOOMError, ChaosSliceLossError
    if isinstance(exc, (OOMError, ChaosSliceLossError)):
        return False
    if isinstance(exc, ChaosOOMError):
        return True
    cls = type(exc)
    if cls.__name__ not in _OOM_CLASSES and \
            not cls.__module__.startswith(("jaxlib", "jax")):
        return False
    msg = str(exc)
    return any(m in msg for m in _OOM_MARKERS)


# message markers of a lost/halted device or a broken inter-chip link —
# the failure class behind a preempted TPU slice.  Deliberately disjoint
# from _OOM_MARKERS and _KERNEL_MARKERS: loss is handled by mesh reform
# (core/membership.py), never by the memory ladder or kernel fallback.
_LOSS_MARKERS = ("device unavailable", "Device unavailable",
                 "DEVICE UNAVAILABLE", "UNAVAILABLE:", "device halted",
                 "Device halted", "core halted", "ICI failure",
                 "interconnect failure", "slice preempted",
                 "device is lost", "Device lost")


def is_device_loss(exc: BaseException) -> bool:
    """Classify an exception as a device/slice LOSS (a preempted TPU
    slice, a halted core, a broken ICI link, or the injected chaos
    equivalent) — recoverable only by reforming the mesh on the
    surviving devices and resuming from checkpoints
    (core/membership.py).  OOMs and kernel-compile failures are NOT
    losses: they have their own in-place recovery ladders."""
    from h2o_tpu.core.chaos import ChaosSliceLossError
    if isinstance(exc, ChaosSliceLossError):
        return True
    if isinstance(exc, OOMError) or is_device_oom(exc):
        return False
    cls = type(exc)
    if cls.__name__ not in _OOM_CLASSES and \
            not cls.__module__.startswith(("jaxlib", "jax")):
        return False
    msg = str(exc)
    return any(m in msg for m in _LOSS_MARKERS)


# -- observability -----------------------------------------------------------

_RUNGS = ("oom_events", "sweeps", "shrinks", "host_fallbacks",
          "kernel_fallbacks", "unfused_fallbacks", "terminal")

_stats_lock = threading.Lock()
_sites: Dict[str, Dict[str, int]] = {}


def _note(site: str, rung: str, n: int = 1) -> None:
    with _stats_lock:
        d = _sites.setdefault(site, {r: 0 for r in _RUNGS})
        d[rung] += n
    TimeLine.record("safety", rung, site=site)


def stats() -> dict:
    """Cumulative ladder counters: totals plus the per-site breakdown
    the soak invariants and ``GET /3/Resilience`` assert against."""
    with _stats_lock:
        sites = {s: dict(d) for s, d in _sites.items()}
    return {
        "oom_events": sum(d["oom_events"] for d in sites.values()),
        "sweeps": sum(d["sweeps"] for d in sites.values()),
        "degradations": sum(d["shrinks"] + d["host_fallbacks"] +
                            d.get("kernel_fallbacks", 0) +
                            d.get("unfused_fallbacks", 0)
                            for d in sites.values()),
        "terminal_failures": sum(d["terminal"] for d in sites.values()),
        "sites": sites,
    }


def reset_stats() -> None:
    with _stats_lock:
        _sites.clear()


# -- ladder ------------------------------------------------------------------

def sweep_retries() -> int:
    """Rung (a) bound: how many sweep-then-retry attempts each site gets
    before descending to shrink/fallback (``H2O_TPU_OOM_SWEEP_RETRIES``,
    default 2 — sized so the acceptance drill's fail-first-2 injection
    is absorbed by sweeps alone at quantum-less sites)."""
    return int(os.environ.get("H2O_TPU_OOM_SWEEP_RETRIES", "2") or 2)


def _diagnostic(site: str) -> str:
    """Actionable terminal message: what is resident, what the budget
    is, and who the largest holders are (MemoryManager.stats())."""
    try:
        from h2o_tpu.core.memory import manager
        s = manager().stats()
        holders = ", ".join(f"{b}B" for b in s.get("largest_holders", []))
        return (f"resident_bytes={s['resident_bytes']} "
                f"budget={s['budget'] or 'unlimited'} "
                f"resident_vecs={s['resident_vecs']} "
                f"largest_holders=[{holders}] — lower the working set "
                f"(smaller frame / fewer columns), set a tighter "
                f"H2O_TPU_HBM_BUDGET so cold columns spill earlier, or "
                f"shrink the work quantum for {site}")
    except Exception:  # noqa: BLE001 — diagnostics must never mask OOM
        return "memory manager diagnostics unavailable"


def oom_ladder(site: str, attempt: Callable[[], object], *,
               shrink: Optional[Callable[[], bool]] = None,
               host_fallback: Optional[Callable[[], object]] = None,
               on_oom: Optional[Callable[[BaseException], None]] = None):
    """Run ``attempt()`` under the OOM recovery ladder (module
    docstring).  ``shrink()`` reduces the caller's work quantum and
    returns False once it cannot shrink further; ``host_fallback()``
    computes the result off-device; ``on_oom(exc)`` is invoked on every
    classified OOM (callers use it to e.g. disable buffer donation
    before a retry re-reads an input).  Non-OOM exceptions propagate
    untouched."""
    from h2o_tpu.core.chaos import chaos
    c = chaos()

    def _run():
        c.maybe_oom(site)
        return attempt()

    def _swallow_oom(e: BaseException) -> None:
        if not is_device_oom(e):
            raise e
        _note(site, "oom_events")
        if on_oom is not None:
            on_oom(e)

    try:
        return _run()
    except Exception as e:  # noqa: BLE001 — reclassified by _swallow_oom
        _swallow_oom(e)
    # rung (a): sweep the LRU — spill every cold column — and retry
    for i in range(sweep_retries()):
        _note(site, "sweeps")
        from h2o_tpu.core.memory import manager
        freed = manager().sweep()
        log.warning("%s: device OOM — swept %d bytes of cold columns, "
                    "retry %d/%d", site, freed, i + 1, sweep_retries())
        try:
            return _run()
        except Exception as e:  # noqa: BLE001
            _swallow_oom(e)
    # rung (b): shrink the work quantum and retry until it bottoms out
    if shrink is not None:
        while shrink():
            _note(site, "shrinks")
            log.warning("%s: device OOM persists — degraded to a "
                        "smaller work quantum", site)
            try:
                return _run()
            except Exception as e:  # noqa: BLE001
                _swallow_oom(e)
    # rung (c): compute off-device via the parity oracle
    if host_fallback is not None:
        _note(site, "host_fallbacks")
        log.warning("%s: device OOM persists — falling back to the "
                    "host path", site)
        return host_fallback()
    # rung (d): fail the JOB with a diagnostic, never the process
    _note(site, "terminal")
    raise OOMError(site, _diagnostic(site))
