"""Diagnostics — Timeline event ring, WaterMeter counters, profiling.

Reference (SURVEY §5.1):
- water/TimeLine.java:12-80 — a lock-free per-node ring of the last 2,048
  network events (send/recv, timestamp, task id), snapshotted cluster-wide
  and served at GET /3/Timeline;
- water/util/WaterMeterCpuTicks / WaterMeterIo — /proc-backed CPU and IO
  counters per node;
- ProfileCollectorTask / JStackCollectorTask — stack-sample profiler and
  thread dumps at /3/Profiler and /3/JStack.

TPU-native: the "network events" of this runtime are DKV traffic, job
transitions and device dispatches — recorded into the same fixed-size ring
(a deque under the GIL is the managed-runtime analog of the Unsafe CAS
ring); WaterMeter reads the same /proc files; the profiler snapshots
Python thread stacks (sys._current_frames — the JStack analog) and defers
device-side tracing to jax.profiler.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

MAX_EVENTS = 2048

# the jax monitoring events whose durations DispatchStats keeps, by their
# last path component (jax/_src/dispatch.py, jax/_src/compiler.py).
# backend_compile wraps the persistent-cache lookup, so cache_retrieval
# is a PART of it, not an addend.
_COMPILE_EVENTS = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                   "backend_compile_duration", "cache_retrieval_time_sec")


class DispatchStats:
    """Per-phase compile/dispatch/transfer counters — the data-plane
    observability the MRTask-era stack never needed (one JVM task = one
    "dispatch") but an XLA substrate lives or dies by: a hot loop that
    recompiles per call shows up here as compiles growing with
    dispatches instead of staying flat.

    Phases are free-form strings ("map_reduce", "tree_block", "rollups",
    "quantile"...).  ``xla_compiles`` counts BACKEND compiles globally
    via jax's monitoring events (install_xla_listener), so even jit
    sites that do not route through the dispatch cache are visible —
    the number the compile-count regression tests and the benchmark's
    ``window_compiles`` are built on.
    """

    _lock = threading.Lock()
    _compiles: Dict[str, int] = {}
    _dispatches: Dict[str, int] = {}
    _cache_hits: Dict[str, int] = {}
    _disk_hits: Dict[str, int] = {}
    _transfers: Dict[str, int] = {}
    _transfer_bytes: Dict[str, int] = {}
    _host_pulls: Dict[str, int] = {}
    _host_pull_bytes: Dict[str, int] = {}
    # per-phase, per-collective-kind byte accounting split by mesh level
    # (inner ICI "nodes" axis vs outer DCN "slices" axis) — trace-time,
    # static-shape based: the cloud.py hierarchical helpers note each
    # collective ONCE PER TRACE, so totals count bytes per compiled
    # program, not per dispatch (steady-state dispatches replay cached
    # executables and move the same bytes every call)
    _collectives: Dict[str, Dict[str, Dict[str, int]]] = {}
    _phase_local = threading.local()
    _xla_compiles = 0
    _compile_seconds: Dict[str, float] = {}
    _listener_installed = False

    @classmethod
    def _bump(cls, d: Dict[str, int], phase: str, n: int = 1) -> None:
        with cls._lock:
            d[phase] = d.get(phase, 0) + n

    @classmethod
    def note_compile(cls, phase: str) -> None:
        cls._bump(cls._compiles, phase)
        TimeLine.record("dispatch", "compile", phase=phase)

    @classmethod
    def note_dispatch(cls, phase: str) -> None:
        cls._bump(cls._dispatches, phase)

    @classmethod
    def note_cache_hit(cls, phase: str) -> None:
        cls._bump(cls._cache_hits, phase)

    @classmethod
    def note_disk_hit(cls, phase: str) -> None:
        """One executable warmed from the persistent store (a fresh
        process loading a serialized program instead of compiling —
        core/exec_store.py's AOT layer)."""
        cls._bump(cls._disk_hits, phase)
        TimeLine.record("dispatch", "disk_hit", phase=phase)

    @classmethod
    def note_transfer(cls, phase: str, nbytes: int = 0) -> None:
        cls._bump(cls._transfers, phase)
        cls._bump(cls._transfer_bytes, phase, int(nbytes))

    # -- device->host pull accounting (Vec.to_numpy instrumentation) ------

    @classmethod
    def current_phase(cls) -> str:
        """The phase the calling thread attributes host pulls to
        ("unattributed" outside any phase_scope)."""
        return getattr(cls._phase_local, "stack", ["unattributed"])[-1]

    @classmethod
    def phase_scope(cls, phase: str):
        """Context manager: host pulls on this thread inside the scope
        are attributed to ``phase`` — the munge verbs wrap themselves in
        ``phase_scope("munge")`` so HBM->host traffic per data-plane
        phase is visible at GET /3/Dispatch."""
        import contextlib

        @contextlib.contextmanager
        def scope():
            stack = getattr(cls._phase_local, "stack", None)
            if stack is None:
                stack = cls._phase_local.stack = ["unattributed"]
            stack.append(phase)
            try:
                yield
            finally:
                stack.pop()
        return scope()

    @classmethod
    def note_host_pull(cls, nbytes: int, phase: Optional[str] = None) -> None:
        """One device->host materialization of ``nbytes`` (a Vec payload
        pulled off HBM).  This is the traffic the device-munge layer
        exists to eliminate; the per-phase byte totals are the
        before/after evidence."""
        p = phase if phase is not None else cls.current_phase()
        cls._bump(cls._host_pulls, p)
        cls._bump(cls._host_pull_bytes, p, int(nbytes))

    @classmethod
    def host_pulls(cls, phase: str) -> int:
        with cls._lock:
            return cls._host_pulls.get(phase, 0)

    # -- per-axis collective byte accounting (two-level mesh) -------------

    @classmethod
    def note_collective(cls, kind: str, ici_bytes: int, dcn_bytes: int = 0,
                        phase: Optional[str] = None) -> None:
        """One hierarchical collective noted at TRACE time by the
        cloud.py helper layer (hpsum/hall_gather/hall_to_all).

        ``kind`` is "<collective>:<site-tag>" ("all_gather:sort.splitters",
        "psum:hist.table"...); ``ici_bytes`` is the per-participant payload
        crossing the inner (intra-slice ICI) level, ``dcn_bytes`` the
        payload crossing the outer (cross-slice DCN) level — 0 on a flat
        mesh, where no collective ever leaves the ICI island.  These are
        static-shape formulas evaluated once per compiled program, which
        is exactly what the dryrun_multichip rung compares across row
        counts: a combine whose dcn_bytes grows with rows is the bug the
        two-level mesh exists to prevent."""
        p = phase if phase is not None else cls.current_phase()
        with cls._lock:
            d = cls._collectives.setdefault(p, {}).setdefault(
                kind, {"n": 0, "ici_bytes": 0, "dcn_bytes": 0})
            d["n"] += 1
            d["ici_bytes"] += int(ici_bytes)
            d["dcn_bytes"] += int(dcn_bytes)

    @classmethod
    def install_xla_listener(cls) -> None:
        """Idempotent: register a jax monitoring listener that counts
        backend compiles (the '/jax/core/compile/backend_compile_
        duration' event — one per XLA executable actually built) and
        sums the seconds of each ``_COMPILE_EVENTS`` event."""
        with cls._lock:
            if cls._listener_installed:
                return
            cls._listener_installed = True
        from jax._src import monitoring

        def on_event(event: str, duration: float, **kw) -> None:
            name = event.rsplit("/", 1)[-1]
            if name not in _COMPILE_EVENTS:
                return
            with cls._lock:
                cls._compile_seconds[name] = \
                    cls._compile_seconds.get(name, 0.0) + float(duration)
                if name == "backend_compile_duration":
                    cls._xla_compiles += 1

        monitoring.register_event_duration_secs_listener(on_event)

    @classmethod
    def xla_compiles(cls) -> int:
        with cls._lock:
            return cls._xla_compiles

    @classmethod
    def compile_seconds(cls) -> Dict[str, float]:
        """Seconds this process spent getting programs ready, summed per
        jax monitoring event since ``install_xla_listener``: tracing,
        lowering to MLIR, the backend compile (which holds the
        persistent-cache lookup) and, of that, the cache retrievals."""
        with cls._lock:
            return dict(cls._compile_seconds)

    @classmethod
    def snapshot(cls) -> Dict[str, Any]:
        # stats-pack counters live in ops/statpack.py (the module owns
        # its own quantization telemetry); surfaced here so one snapshot
        # carries the whole dispatch/traffic/quantization picture
        from h2o_tpu.ops import statpack
        with cls._lock:
            return {"compiles": dict(cls._compiles),
                    "dispatches": dict(cls._dispatches),
                    "cache_hits": dict(cls._cache_hits),
                    "disk_hits": dict(cls._disk_hits),
                    "transfers": dict(cls._transfers),
                    "transfer_bytes": dict(cls._transfer_bytes),
                    "host_pulls": dict(cls._host_pulls),
                    "host_pull_bytes": dict(cls._host_pull_bytes),
                    "collectives": {p: {k: dict(v) for k, v in kinds.items()}
                                    for p, kinds in cls._collectives.items()},
                    "stats_pack": statpack.stats(),
                    "xla_compiles": cls._xla_compiles,
                    "compile_seconds": dict(cls._compile_seconds),
                    "xla_listener": cls._listener_installed}

    @classmethod
    def reset(cls) -> None:
        """Zero the per-phase counters (the global xla_compiles counter
        and compile_seconds keep running — monotone process-lifetime
        totals)."""
        with cls._lock:
            cls._compiles.clear()
            cls._dispatches.clear()
            cls._cache_hits.clear()
            cls._disk_hits.clear()
            cls._transfers.clear()
            cls._transfer_bytes.clear()
            cls._host_pulls.clear()
            cls._host_pull_bytes.clear()
            cls._collectives.clear()


class TimeLine:
    """Fixed-size event ring (water/TimeLine.java).

    Two kinds of entry share the ring: point events (``record``) and
    SPANS (``span``), which add ``dur_ns``, ``id``, ``parent`` and
    ``job``.  A span's duration is HOST time between entering and
    leaving the ``with`` block; device time comes from the ``h2o.*``
    named scopes in a profile, never from a sync added to a span."""

    _events: deque = deque(maxlen=MAX_EVENTS)
    _lock = threading.Lock()
    _ids = itertools.count(1)
    _open = threading.local()       # .stack: [(span id, job key)] per thread

    @classmethod
    def record(cls, kind: str, what: str, **info) -> None:
        ev = {"ns": time.time_ns(), "kind": kind, "what": what,
              "thread": threading.get_ident(), **info}
        with cls._lock:
            cls._events.append(ev)

    @classmethod
    @contextlib.contextmanager
    def span(cls, kind: str, what: str, job: Optional[str] = None, **info):
        """Context manager: ONE ring event when the block is left (also
        by an exception), stamped with the block's start (``ns``) and
        host duration (``dur_ns``), its ``id``, the ``parent`` span open
        on this thread and the ``job`` key — given by a root span,
        inherited by everything opened under it.  The block also runs
        under ``TraceAnnotation("h2o:<kind>.<what>", **info)``: while a
        profile is being taken the span lies in its host plane on the
        device events' clock, ``info`` among its stats; otherwise that
        is a flag test.  ``with ... as fields`` hands the block the
        event's own dict: what it counts while open (host work on data
        it already holds) lands in the ring event, not in the profile."""
        stack = getattr(cls._open, "stack", None)
        if stack is None:
            stack = cls._open.stack = []
        parent, inherited = stack[-1] if stack else (None, None)
        if job is None:
            job = inherited
        sid = next(cls._ids)
        stack.append((sid, job))
        ns, t0 = time.time_ns(), time.perf_counter_ns()
        # the ring event IS the dict the block is handed: a field whose
        # value is still on the device when the span closes (a count
        # fetched with a later sync) may be written into it afterwards
        ev = {"ns": ns, "kind": kind, "what": what,
              "thread": threading.get_ident(), "dur_ns": None,
              "id": sid, "parent": parent, "job": job, **info}
        try:
            with TraceAnnotation(f"h2o:{kind}.{what}", **info):
                yield ev
        finally:
            ev["dur_ns"] = time.perf_counter_ns() - t0
            stack.pop()
            with cls._lock:
                cls._events.append(ev)

    @classmethod
    def snapshot(cls) -> List[Dict[str, Any]]:
        """Consistent copy of the ring (TimelineSnapshot analog)."""
        with cls._lock:
            return list(cls._events)

    @classmethod
    def clear(cls) -> None:
        with cls._lock:
            cls._events.clear()


def water_meter_cpu_ticks() -> Dict[str, Any]:
    """Per-CPU (user, sys, other, idle) ticks (WaterMeterCpuTicks)."""
    cpus = []
    try:
        with open("/proc/stat") as f:
            for ln in f:
                if ln.startswith("cpu") and ln[3:4].isdigit():
                    parts = ln.split()
                    user, nice, system, idle = (int(x)
                                                for x in parts[1:5])
                    other = sum(int(x) for x in parts[5:8])
                    cpus.append([user + nice, system, other, idle])
    except OSError:
        pass
    return {"cpu_ticks": cpus}


def water_meter_io() -> Dict[str, Any]:
    """Process IO byte counters (WaterMeterIo)."""
    out = {"read_bytes": 0, "write_bytes": 0}
    try:
        with open("/proc/self/io") as f:
            for ln in f:
                k, _, v = ln.partition(":")
                if k in ("read_bytes", "write_bytes"):
                    out[k] = int(v)
    except OSError:
        pass
    return out


def jstack() -> List[Dict[str, Any]]:
    """All-thread stack dump (JStackCollectorTask analog)."""
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in frames.items():
        out.append({"thread_id": tid,
                    "name": names.get(tid, f"thread-{tid}"),
                    "stack": traceback.format_stack(frame)})
    return out


class Profiler:
    """Stack-sampling profiler (ProfileCollectorTask analog): sample all
    thread stacks at an interval, report frame hit counts."""

    def __init__(self, interval_s: float = 0.01):
        self.interval = interval_s
        self.counts: Dict[str, int] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Profiler":
        """Idempotent: a second ``start()`` while sampling is a no-op —
        never a second (leaked) sampler thread.  Restarting a stopped
        profiler resumes sampling into the same counts."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()

        def run():
            while not self._stop.wait(self.interval):
                for frame in sys._current_frames().values():
                    f = frame
                    while f is not None:
                        key = (f"{f.f_code.co_filename}:"
                               f"{f.f_code.co_name}:{f.f_lineno}")
                        self.counts[key] = self.counts.get(key, 0) + 1
                        f = f.f_back
        # daemon: a forgotten profiler must never block interpreter exit
        self._thread = threading.Thread(target=run, daemon=True,
                                        name="h2o-tpu-profiler")
        self._thread.start()
        return self

    def stop(self) -> Dict[str, int]:
        """Idempotent: ``stop()`` after ``stop()`` just returns the
        counts again."""
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=1.0)
        return dict(sorted(self.counts.items(), key=lambda kv: -kv[1]))


def device_memory() -> List[Dict[str, Any]]:
    """Per-device memory stats (the Cloud-status heap columns analog)."""
    import jax
    out = []
    for d in jax.devices():
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — not all backends expose stats
            pass
        out.append({"device": str(d), "platform": d.platform,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit")})
    return out
