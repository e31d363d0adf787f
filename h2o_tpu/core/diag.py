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
import re
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

MAX_EVENTS = 2048
MAX_PROGRAMS = 4096

# the jax monitoring events that make one program ready, by their last
# path component (jax/_src/dispatch.py log_elapsed_time), in the order
# they fire for a program, each with the field of its record.  The
# backend compile wraps the persistent-cache lookup, so the cache's
# ``cache_retrieval_time_sec`` is a PART of it, not an addend.
_READY_FIELDS = {"jaxpr_trace_duration": "trace_s",
                 "jaxpr_to_mlir_module_duration": "lower_s",
                 "backend_compile_duration": "compile_s"}
_READY_ORDER = tuple(_READY_FIELDS.values())
_RETRIEVAL = "cache_retrieval_time_sec"

# one collective operation of a compiled module's text, counted once: its
# synchronous form or the ``-start`` half of an asynchronous pair
_COLLECTIVE_OP = re.compile(
    r"\s(?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"ragged-all-to-all|collective-permute|collective-broadcast)"
    r"(?:-start)?\(")


def unowned_collectives(hlo_text: str) -> int:
    """Collective operations of a compiled module's text that no
    ``h2o.coll.`` scope owns: those the partitioner put in (an operand it
    gathered, a reduction it completed), not a ``core/cloud.py`` helper."""
    return sum(1 for line in hlo_text.splitlines()
               if _COLLECTIVE_OP.search(line) and "h2o.coll." not in line)


def _executable_unowned(executable, devices) -> Optional[int]:
    """``unowned_collectives`` over an executable's modules; 0 on one
    device (no collective can be there: its text is not read), None
    where the executable shows no text."""
    if getattr(devices, "size", 1) <= 1:
        return 0
    try:
        return sum(unowned_collectives(m.to_string())
                   for m in executable.hlo_modules())
    except Exception:  # noqa: BLE001 - a backend without module text
        return None


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
    _programs: deque = deque(maxlen=MAX_PROGRAMS)
    _ready_local = threading.local()    # .stack, .open: see _on_ready_end
    _listener_installed = False
    # program key -> {collective kind: ICI bytes} of its explicit
    # collectives, as their trace noted them
    _program_ici: Dict[Any, int] = {}

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
        open_ici = getattr(cls._phase_local, "ici", None)
        if open_ici is not None:
            open_ici[kind] = open_ici.get(kind, 0) + int(ici_bytes)
        with cls._lock:
            d = cls._collectives.setdefault(p, {}).setdefault(
                kind, {"n": 0, "ici_bytes": 0, "dcn_bytes": 0})
            d["n"] += 1
            d["ici_bytes"] += int(ici_bytes)
            d["dcn_bytes"] += int(dcn_bytes)

    @classmethod
    def program_ici(cls, key, call):
        """``(call(), {collective kind: ICI bytes})``: what the explicit
        collectives of the program ``call`` runs ship, each collective
        once as its trace holds it (one in a loop body counts once), as
        the helpers noted them while it was traced on this thread; kept
        under ``key`` for the calls that replay it untraced (empty where
        this process never traced it here)."""
        st = cls._phase_local
        outer = getattr(st, "ici", None)
        st.ici = {}
        try:
            out = call()
        finally:
            traced = st.ici
            st.ici = outer
            if outer is not None:
                for k, v in traced.items():
                    outer[k] = outer.get(k, 0) + v
        with cls._lock:
            if traced:
                cls._program_ici[key] = traced
            return out, dict(cls._program_ici.get(key, {}))

    # -- programs made ready (jax monitoring) -----------------------------

    @classmethod
    def install_xla_listener(cls) -> None:
        """Idempotent: listen to the jax monitoring events that make a
        program ready.  JAX brackets a program's tracing, its lowering
        to MLIR and its backend compile (which holds the persistent-cache
        lookup) each in ``dispatch.log_elapsed_time``, which reports the
        start as a scalar and the end as a time span, synchronously on
        the thread that makes the program; a persistent-cache hit or
        write fires an event inside the backend compile.  From these the
        listeners count backend compiles (``xla_compiles``: one per XLA
        executable actually built) and keep one record per program
        (``programs``), whose seconds are ``compile_seconds``'."""
        with cls._lock:
            if cls._listener_installed:
                return
            cls._listener_installed = True
        from jax._src import compiler, monitoring
        monitoring.register_scalar_listener(cls._on_ready_start)
        compile_program = compiler.compile_or_get_cached

        def compile_counted(backend, computation, devices, *a, **kw):
            # inside the backend compile's event: the record it closes
            # carries the count of the collectives no helper put there
            exe = compile_program(backend, computation, devices, *a, **kw)
            frame = cls._open_compile()
            if frame is not None:
                frame["gspmd_collectives"] = _executable_unowned(exe,
                                                                 devices)
            return exe

        compiler.compile_or_get_cached = compile_counted
        monitoring.register_event_time_span_listener(cls._on_ready_end)
        monitoring.register_event_listener(cls._on_cache_event)
        monitoring.register_event_duration_secs_listener(cls._on_duration)

    @classmethod
    def _ready_state(cls):
        """This thread's open events (``stack``, innermost last) and, by
        nesting depth, the program being assembled there (``open``)."""
        st = cls._ready_local
        if not hasattr(st, "stack"):
            st.stack, st.open = [], {}
        return st

    @staticmethod
    def _frame(event: str = "") -> Dict[str, Any]:
        return {"event": event, "carved": 0.0, "cache": "uncached",
                "retrieve_s": None, "gspmd_collectives": None}

    @classmethod
    def _open_compile(cls) -> Optional[Dict[str, Any]]:
        """The backend compile open on this thread, if it is the
        innermost open event."""
        stack = cls._ready_state().stack
        if stack and stack[-1]["event"].endswith("/backend_compile_duration"):
            return stack[-1]
        return None

    @classmethod
    def _on_ready_start(cls, event: str, value: float, **kw) -> None:
        if event.rsplit("/", 1)[-1] in _READY_FIELDS:
            cls._ready_state().stack.append(cls._frame(event))

    @classmethod
    def _on_cache_event(cls, event: str, **kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if name in ("cache_hits", "cache_misses"):
            frame = cls._open_compile()
            if frame is not None:
                frame["cache"] = "hit" if name == "cache_hits" \
                    else "compiled"

    @classmethod
    def _on_duration(cls, event: str, duration: float, **kw) -> None:
        if event.rsplit("/", 1)[-1] != _RETRIEVAL:
            return
        with cls._lock:
            cls._compile_seconds[_RETRIEVAL] = \
                cls._compile_seconds.get(_RETRIEVAL, 0.0) + float(duration)
        frame = cls._open_compile()
        if frame is not None:
            frame["retrieve_s"] = float(duration)

    @classmethod
    def _on_ready_end(cls, event: str, start: float, end: float,
                      fun_name: str = "", **kw) -> None:
        """One of a program's three events ended.  Its own seconds are
        its duration less what it holds of programs made ready inside it
        (an eager op run while tracing is a program of its own), so no
        second is counted twice; a nested trace that compiles nothing (a
        jitted function traced into its caller) stays in the seconds of
        the event that encloses it.  A program's events follow each
        other at one depth, and its backend compile closes it; an event
        that comes again before that closes the program before, which is
        recorded (``cache`` "traced") where nothing encloses it."""
        field = _READY_FIELDS.get(event.rsplit("/", 1)[-1])
        if field is None:
            return
        st = cls._ready_state()
        frame = st.stack.pop() if st.stack and \
            st.stack[-1]["event"] == event else cls._frame()
        depth = len(st.stack)
        if depth:
            st.stack[-1]["carved"] += frame["carved"]
        for d in [d for d in st.open if d > depth]:
            del st.open[d]
        rank = _READY_ORDER.index(field)
        prog = st.open.get(depth)
        if prog is not None and rank <= prog["rank"]:
            del st.open[depth]
            if not depth:
                cls._publish(prog, "traced")
            prog = None
        if prog is None:
            prog = st.open[depth] = {"start": start, "trace_s": 0.0,
                                     "lower_s": 0.0, "compile_s": 0.0,
                                     "retrieve_s": None,
                                     "at": TimeLine.open_span()}
        prog[field] = (end - start) - frame["carved"]
        prog.update(rank=rank, end=end, fun=fun_name)
        if field == "compile_s":
            with cls._lock:
                cls._xla_compiles += 1
            del st.open[depth]
            prog["retrieve_s"] = frame["retrieve_s"]
            prog["gspmd_collectives"] = frame["gspmd_collectives"]
            cls._publish(prog, frame["cache"])
            if depth:
                st.stack[-1]["carved"] += \
                    prog["trace_s"] + prog["lower_s"] + prog["compile_s"]

    @classmethod
    def _publish(cls, prog: Dict[str, Any], cache: str) -> None:
        """A program's record: into the table and, as span
        ``exec.ready``, into the ring."""
        parent, job = prog["at"]
        ev = {"ns": int(prog["start"] * 1e9), "kind": "exec",
              "what": "ready", "thread": threading.get_ident(),
              "dur_ns": int((prog["end"] - prog["start"]) * 1e9),
              "id": TimeLine.new_id(), "parent": parent, "job": job,
              "fun": prog["fun"], "trace_s": prog["trace_s"],
              "lower_s": prog["lower_s"], "compile_s": prog["compile_s"],
              "cache": cache, "retrieve_s": prog["retrieve_s"],
              "gspmd_collectives": prog.get("gspmd_collectives")}
        with cls._lock:
            for name, field in _READY_FIELDS.items():
                cls._compile_seconds[name] = \
                    cls._compile_seconds.get(name, 0.0) + ev[field]
            cls._programs.append(ev)
        TimeLine.add(ev)

    @classmethod
    def xla_compiles(cls) -> int:
        with cls._lock:
            return cls._xla_compiles

    @classmethod
    def compile_seconds(cls) -> Dict[str, float]:
        """Seconds this process spent getting programs ready since
        ``install_xla_listener``, by jax monitoring event: tracing,
        lowering to MLIR, the backend compile (which holds the
        persistent-cache lookup) and, of that, the cache retrievals.
        A second inside a nested event counts once.  The first three
        are ``programs()``' seconds, summed."""
        with cls._lock:
            return dict(cls._compile_seconds)

    @classmethod
    def programs(cls) -> List[Dict[str, Any]]:
        """One record per program made ready since
        ``install_xla_listener`` (the newest ``MAX_PROGRAMS``), oldest
        first: ``fun`` (JAX's name, ``jit(f)``), ``trace_s`` (the
        outermost trace: jitted calls traced into it are not added
        again), ``lower_s``, ``compile_s`` (the backend compile, a cache
        retrieval inside it); ``cache``: "hit" (loaded from the
        persistent compile cache, ``retrieve_s`` of it), "compiled"
        (compiled and written to that cache: the next process loads
        it), "uncached" (compiled and not kept: no cache, or a compile
        quicker than ``jax_persistent_cache_min_compile_time_secs``, so
        every process compiles it again) or "traced" (no backend compile
        followed: an ``eval_shape``, a ``lower()`` alone);
        ``gspmd_collectives``: the compiled module's collective
        operations that no ``h2o.coll.`` scope owns (``unowned_
        collectives``: what the partitioner inserted; 0 on one device,
        None where nothing was compiled); ``ns`` and
        ``dur_ns`` (the first event's start to the last one's end, on
        the ring's clock); ``parent`` and ``job`` (the ``TimeLine`` span
        open on the thread that made it).  The same dicts are the
        ring's ``exec.ready`` spans."""
        with cls._lock:
            return [dict(p) for p in cls._programs]

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
                    "programs": [dict(p) for p in cls._programs],
                    "xla_listener": cls._listener_installed}

    @classmethod
    def reset(cls) -> None:
        """Zero the per-phase counters (the global xla_compiles counter,
        compile_seconds and programs keep running — monotone
        process-lifetime totals)."""
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
    named scopes in a profile, never from a sync added to a span.
    ``exec.ready`` spans (``DispatchStats.programs``) come from JAX's
    own clock and are in no profile."""

    _events: deque = deque(maxlen=MAX_EVENTS)
    _lock = threading.Lock()
    _ids = itertools.count(1)
    _open = threading.local()       # .stack: [(span id, job key)] per thread

    @classmethod
    def record(cls, kind: str, what: str, **info) -> None:
        cls.add({"ns": time.time_ns(), "kind": kind, "what": what,
                 "thread": threading.get_ident(), **info})

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
            cls.add(ev)

    @classmethod
    def open_span(cls) -> tuple:
        """``(id, job)`` of the innermost span open on this thread, or
        ``(None, None)``."""
        stack = getattr(cls._open, "stack", None)
        return stack[-1] if stack else (None, None)

    @classmethod
    def new_id(cls) -> int:
        return next(cls._ids)

    @classmethod
    def add(cls, ev: Dict[str, Any]) -> None:
        """One event into the ring: a point event, a closed span, or a
        span assembled from JAX's own events (``exec.ready``)."""
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
