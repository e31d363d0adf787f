"""One executable store under every kernel cache — with persistent AOT.

The platform's speed story is "compile once, dispatch forever", but that
premise was re-implemented three times: ``DispatchCache`` in
core/mrtask.py (PR 3) for the MRTask verbs, the serve predict cache in
serve/engine.py (PR 2) for online scoring, and the munge ``cached_kernel``
buckets (PR 4) for the Rapids data plane — each with its own LRU bound,
donation policy and OOM handling.  That is the exact analog of the
reference funneling every distributed verb through ONE ``MRTask`` /
``TypeMap`` substrate (water/MRTask.java, water/TypeMap.java) instead of
per-algorithm plumbing, so this module is that substrate: a single
``ExecStore`` that owns

- the **LRU bound** (``H2O_TPU_EXEC_STORE`` entries, default 256);
- **shape-bucketing** helpers (``bucket_pow2`` — the serve layer's
  power-of-two batch discipline, reused by the munge row buckets);
- the **buffer-donation policy**: callers declare ``donate_argnums`` /
  ``donate_argnames`` and the store applies them per the backend policy
  (core/cloud.donation_enabled), keying donating and non-donating
  variants as distinct entries so an OOM retry can re-route through the
  non-donating twin without recompiling the donating one;
- **OOM-ladder integration** (``dispatch``): every store-routed call
  runs under core/oom.oom_ladder, with the donate->no-donate re-route
  handled here instead of per call site;
- **per-phase dispatch stats** (core/diag.DispatchStats): a memory miss
  is a compile, a memory hit is a cache hit, a disk load is a disk hit —
  the compile-count regression tests assert on exactly this;
- and the headline unlock: **persistent ahead-of-time serialization** of
  compiled executables.  Entries fetched with example ``args`` are
  AOT-lowered and compiled immediately; the compiled executable is
  serialized to ``H2O_TPU_EXEC_STORE_DIR`` via
  ``jax.experimental.serialize_executable`` keyed on (schema version,
  caller-stable name, statics, argument avals incl. shardings, donation,
  jax version, backend topology).  A fresh process — a restarted node, a
  new serve replica — warms its kernel set from disk instead of paying
  XLA again.  Where executable serialization is unsupported (jit-level
  entries with static-argname shape polymorphism, backends without
  SerializeExecutable), the store falls back to the XLA persistent
  compile cache (core/cloud._enable_compile_cache) so the backend
  compile — the expensive half — still warms from disk.

Disk entries are schema-versioned: a header mismatch (schema bump,
h2o_tpu or jax upgrade, different device topology, key collision)
invalidates the entry cleanly — it is ignored and rebuilt, never
half-loaded.  Because a serialized executable bakes its closure
constants in (serve predict entries embed the MODEL WEIGHTS; kernels
embed their traced body), the disk key also carries a **content
fingerprint**: a digest of the persisted function's compiled body
(``code_fingerprint``) or, for serve entries, of the model's parameter
arrays — so a different model under a reused model_id, or an upgraded
kernel under an unchanged qualname, can never silently load the stale
program.

TRUST BOUNDARY: disk entries are unpickled on load, and unpickling is
code execution.  ``H2O_TPU_EXEC_STORE_DIR`` must only point at a
directory writable solely by principals already trusted to run code in
every process that warms from it (the store writes 0o600 files in a
0o700 directory and warns once if the directory is group/other-
writable); the header/magic checks authenticate nothing.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import struct
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

from h2o_tpu.core import lockwitness
from h2o_tpu.core.diag import DispatchStats, TimeLine
from h2o_tpu.core.log import get_logger

log = get_logger("exec_store")

_AUDIT_TRUE = ("1", "on", "true", "yes")


def _audit_enabled() -> bool:
    """H2O_TPU_AUDIT — record per-compile executable summaries for the
    graftlint IR tier (h2o_tpu/lint/audit.py).  Checked before any lint
    import so the off path costs one env lookup on the COMPILE path
    only (never per dispatch)."""
    return os.environ.get("H2O_TPU_AUDIT", "").strip().lower() \
        in _AUDIT_TRUE

SCHEMA_VERSION = 2     # 2: header carries the execution devices
_MAGIC = b"H2OEXEC1"
_DEFAULT_ENTRIES = 256


def _env_capacity() -> int:
    return int(os.environ.get("H2O_TPU_EXEC_STORE") or _DEFAULT_ENTRIES)


def store_dir() -> Optional[str]:
    """H2O_TPU_EXEC_STORE_DIR: directory for serialized executables
    (empty/unset = the disk layer is off and only the in-memory LRU —
    plus the XLA persistent compile cache, where enabled — applies)."""
    d = os.environ.get("H2O_TPU_EXEC_STORE_DIR", "").strip()
    return d or None


def bucket_pow2(n: int) -> int:
    """Smallest power of two >= n — THE shape bucket (serve batches,
    munge row buckets): workloads compile at most log2(max) programs
    per verb instead of one per distinct size."""
    return 1 if n <= 1 else 1 << int(n - 1).bit_length()


def aval_key(x) -> Tuple:
    """Hashable signature of one argument: shape/dtype/sharding for
    arrays (a resharded input is a different program), value for
    hashable statics.  Containers (the DL layer-param pytrees, optimizer
    states) recurse so a whole pytree argument keys on its leaf avals."""
    import jax
    import numpy as np
    if isinstance(x, jax.Array):
        try:
            shard = repr(x.sharding)
        except Exception:  # noqa: BLE001 — deleted/donated arrays
            shard = None
        return ("arr", x.shape, str(x.dtype), shard)
    if isinstance(x, np.ndarray):
        return ("np", x.shape, str(x.dtype))
    if isinstance(x, (list, tuple)):
        return ("seq", type(x).__name__,
                tuple(aval_key(v) for v in x))
    if isinstance(x, dict):
        return ("dict", tuple((k, aval_key(v))
                              for k, v in sorted(x.items())))
    return ("static", type(x).__name__, x)


def _backend_fingerprint() -> Tuple[str, int]:
    import jax
    return jax.default_backend(), jax.device_count()


def backend_fingerprint() -> Tuple[str, int]:
    """Public (platform, device_count) identity of the live backend —
    the backend half of every disk key, shared with the autotuner's
    decision table (core/autotune.py) so a decision probed on one
    backend can never be replayed on another."""
    return _backend_fingerprint()


def _is_deleted_array(x) -> bool:
    import jax
    if not isinstance(x, jax.Array):
        return False
    try:
        return bool(x.is_deleted())
    except Exception:  # noqa: BLE001 — tracers etc. count as alive
        return False


def code_fingerprint(fn) -> str:
    """Digest of a function's COMPILED BODY (co_code + consts + names,
    nested code objects recursed, defaults) — the content half of a
    disk key.  A persisted executable embeds its traced body, so a
    changed implementation under an unchanged ``module.qualname`` must
    select a different disk entry, never load the stale program."""
    h = hashlib.sha256()

    def walk(code) -> None:
        h.update(code.co_code)
        h.update(",".join(code.co_names).encode())
        h.update(",".join(code.co_varnames).encode())
        for c in code.co_consts:
            if hasattr(c, "co_code"):
                walk(c)
            else:
                h.update(repr(c).encode())

    code = getattr(fn, "__code__", None)
    if code is None:                       # builtins / C extensions
        h.update(f"{getattr(fn, '__module__', '')}."
                 f"{getattr(fn, '__qualname__', repr(type(fn)))}".encode())
    else:
        walk(code)
        for d in getattr(fn, "__defaults__", None) or ():
            h.update(repr(d).encode())
    return h.hexdigest()[:16]


def stable_fn_name(fn) -> Optional[str]:
    """Cross-process-stable identity for a map function, or None when
    there is none.  Only a plain module-level function qualifies: a
    closure (or a ``<locals>`` qualname) can capture per-call state two
    instances of which would collide on the same disk key — those
    entries stay memory-only (keyed on object identity) and warm via
    the XLA persistent compile cache instead."""
    closure = getattr(fn, "__closure__", None)
    qualname = getattr(fn, "__qualname__", "")
    module = getattr(fn, "__module__", "")
    if closure or not qualname or not module or "<locals>" in qualname:
        return None
    return f"{module}.{qualname}"


class ExecStore:
    """Bounded LRU of compiled programs with a persistent AOT layer.

    One entry = one executable: ``build`` returns the RAW python
    callable and the store jits (and, with example args, AOT-compiles
    and serializes) it — so ``misses`` IS the trace-or-load count for
    everything routed through the store.  Entries pin their key's
    function object, so ``id`` reuse is impossible while the entry
    lives; the LRU bound keeps that pinning finite.
    """

    def __init__(self, max_entries: Optional[int] = None):
        self.max_entries = int(max_entries or _env_capacity())
        self._lock = lockwitness.make_rlock("exec_store.ExecStore._lock")
        self._entries: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._aot: set = set()            # keys holding AOT executables
        self.hits = 0
        self.misses = 0
        self.disk_hits = 0
        self.disk_stores = 0
        self.disk_invalid = 0             # schema/key-mismatch discards
        self.serialize_unsupported = 0
        self.evictions = 0
        self.disk_bytes_written = 0
        self.disk_bytes_read = 0

    # -- donation policy -----------------------------------------------------

    @staticmethod
    def donation_on() -> bool:
        """THE buffer-donation policy (H2O_TPU_DONATE / on-TPU default;
        core/cloud.donation_enabled) — call sites declare donatable
        argnums and the store decides whether they apply."""
        from h2o_tpu.core.cloud import donation_enabled
        return donation_enabled()

    # -- fetch-or-compile ----------------------------------------------------

    def get_or_build(self, phase: str, key: Tuple,
                     build: Callable[[], Callable], *,
                     donate_argnums: Tuple[int, ...] = (),
                     donate_argnames: Tuple[str, ...] = (),
                     donate: Optional[bool] = None,
                     jit_kwargs: Optional[Dict[str, Any]] = None,
                     persist: Optional[str] = None,
                     content: Optional[str] = None,
                     args: Optional[Tuple] = None,
                     kwargs: Optional[Dict[str, Any]] = None):
        """Fetch the executable for ``key`` (+ the resolved donation
        flag), building it at most once process-wide.

        ``build()`` returns the raw python callable — the store applies
        ``jax.jit`` (with ``jit_kwargs``) and the donation policy
        itself, so no call site owns a jit wrapper.  When example
        ``args`` (and optional ``kwargs``) are given the entry is
        AOT-compiled for exactly those avals; with ``persist`` set and
        ``H2O_TPU_EXEC_STORE_DIR`` configured, the compiled executable
        is serialized to disk on build and loaded from disk — skipping
        trace AND backend compile — on the first fetch of a fresh
        process.  ``content`` is the caller's content fingerprint
        (``code_fingerprint`` of the persisted function, a digest of a
        model's parameters) folded into the disk key so a changed body
        under an unchanged name invalidates instead of loading stale."""
        dn = bool(donate_argnums or donate_argnames) and \
            (self.donation_on() if donate is None else bool(donate))
        k = (phase,) + tuple(key) + (("__donate__", dn),)
        with self._lock:
            fn = self._entries.get(k)
            if fn is not None:
                self._entries.move_to_end(k)
                self.hits += 1
        if fn is not None:
            DispatchStats.note_cache_hit(phase)
            return fn
        disk_key = None
        if persist is not None and args is not None and store_dir():
            disk_key = self._disk_key(persist, content, dn, jit_kwargs,
                                      args, kwargs)
            with TimeLine.span("exec", "disk_load", phase=phase):
                fn = self._disk_load(phase, disk_key)
            if fn is not None:
                self._insert(k, fn, aot=True)
                return fn
        # build outside the lock: tracing can be slow and may itself
        # dispatch; a rare concurrent double-build is harmless (last
        # writer wins, both executables are correct)
        import jax
        jkw = dict(jit_kwargs or {})
        if dn:
            if donate_argnums:
                jkw.setdefault("donate_argnums", tuple(donate_argnums))
            if donate_argnames:
                jkw.setdefault("donate_argnames", tuple(donate_argnames))
        # graftlint: disable=GL603  the store IS the sanctioned jit
        # point: entries are LRU-bounded, donation-policed, counted
        fn = jax.jit(build(), **jkw)
        if args is not None:
            try:
                lowered = fn.lower(*args, **(kwargs or {}))
                compiled = lowered.compile()
            except Exception as e:  # noqa: BLE001 — AOT is an
                # optimisation; the jit wrapper stays correct (and the
                # XLA persistent compile cache still warms the backend
                # half)
                log.debug("AOT lowering failed for %s (%r); keeping "
                          "the jit-level entry", phase, e)
                self._insert(k, fn, aot=False)
                self._note_audit_compile(phase, key, args)
                DispatchStats.note_compile(phase)
                return fn
            if disk_key is not None:
                self._disk_store(disk_key, compiled)
            if _audit_enabled():
                self._record_audit(phase, key, lowered, compiled,
                                   declared=bool(donate_argnums or
                                                 donate_argnames),
                                   resolved=dn, args=args)
            fn = compiled
            self._insert(k, fn, aot=True)
        else:
            self._insert(k, fn, aot=False)
        self._note_audit_compile(phase, key, args)
        DispatchStats.note_compile(phase)
        return fn

    # -- graftlint IR-audit hooks (H2O_TPU_AUDIT) ---------------------------

    @staticmethod
    def _audit_site(phase: str, key: Tuple) -> str:
        """Stable per-site label: kernel/serve keys lead with a name
        string; anonymous keys fall back to the phase."""
        if key and isinstance(key[0], str):
            return f"{phase}:{key[0]}"
        return phase

    def _note_audit_compile(self, phase: str, key: Tuple,
                            args: Optional[Tuple]) -> None:
        """Per-site distinct-aval-key accounting (GL704 recompile
        churn) — every compile miss, AOT or jit-level."""
        if not _audit_enabled():
            return
        from h2o_tpu.lint import audit
        digest = repr(tuple(aval_key(a) for a in args)) \
            if args is not None else repr(key)
        audit.note_compile(self._audit_site(phase, key), digest)

    def _record_audit(self, phase: str, key: Tuple, lowered, compiled,
                      *, declared: bool, resolved: bool,
                      args: Tuple) -> None:
        from h2o_tpu.lint import audit
        try:
            audit.record_executable(
                phase, self._audit_site(phase, key), declared, resolved,
                lowered, compiled, args)
        except Exception as e:  # noqa: BLE001 — the audit observes, it
            # must never fail a build
            log.debug("exec audit record failed for %s (%r)", phase, e)

    def _insert(self, k: Tuple, fn, aot: bool) -> None:
        with self._lock:
            self._entries[k] = fn
            self.misses += 1
            if aot:
                self._aot.add(k)
            while len(self._entries) > self.max_entries:
                old, _ = self._entries.popitem(last=False)
                self._aot.discard(old)
                self.evictions += 1

    # -- dispatch under the OOM ladder --------------------------------------

    def dispatch(self, phase: str, key: Tuple,
                 build: Callable[[], Callable], args: Tuple, *,
                 site: Optional[str] = None,
                 donate_argnums: Tuple[int, ...] = (),
                 donate: Optional[bool] = None,
                 jit_kwargs: Optional[Dict[str, Any]] = None,
                 persist: Optional[str] = None,
                 content: Optional[str] = None,
                 aot: bool = True,
                 shrink: Optional[Callable[[], bool]] = None,
                 host_fallback: Optional[Callable[[], object]] = None,
                 on_oom: Optional[Callable] = None):
        """Fetch-or-compile, then EXECUTE under the OOM degradation
        ladder (core/oom.py).  When the entry donates input buffers, an
        OOM retry re-routes through the non-donating twin — a retry
        re-reads its inputs, so re-donating them would be wrong.  If the
        failed donating run already CONSUMED a donated input (XLA may
        invalidate donated buffers even on a RESOURCE_EXHAUSTED
        execution), no retry can re-read it: that surfaces as a terminal
        OOMError naming the dead argument instead of an unclassified
        'Array has been deleted' mid-ladder."""
        from h2o_tpu.core.oom import oom_ladder
        fn = self.get_or_build(
            phase, key, build, donate_argnums=donate_argnums,
            donate=donate, jit_kwargs=jit_kwargs, persist=persist,
            content=content, args=args if aot else None)
        # GL802 runtime witness: executing under any witnessed lock
        # stalls every thread contending for it (no-op when off)
        lockwitness.note_device_dispatch(site or phase)
        DispatchStats.note_dispatch(phase)
        state = {"fn": fn}

        def _on_oom(exc):
            if donate_argnums and \
                    (self.donation_on() if donate is None else donate):
                dead = [i for i, a in enumerate(args)
                        if _is_deleted_array(a)]
                if dead:
                    from h2o_tpu.core.oom import OOMError
                    raise OOMError(
                        f"device out of memory at {site or phase}: the "
                        f"donating executable consumed donated input "
                        f"buffer(s) {dead} before the OOM retry could "
                        f"re-read them — re-materialize the inputs or "
                        f"dispatch with donate=False") from exc
                state["fn"] = self.get_or_build(
                    phase, key, build, donate_argnums=donate_argnums,
                    donate=False, jit_kwargs=jit_kwargs,
                    args=args if aot else None)
            if on_oom is not None:
                on_oom(exc)

        return oom_ladder(site or phase, lambda: state["fn"](*args),
                          shrink=shrink, host_fallback=host_fallback,
                          on_oom=_on_oom)

    # -- persistence ---------------------------------------------------------

    def _disk_key(self, persist: str, content: Optional[str],
                  donate: bool, jit_kwargs, args,
                  kwargs) -> Tuple[str, str]:
        """(human keystring, sha256 filename stem).  Everything that
        selects a different executable is in the string: schema version,
        the caller's stable name, the CONTENT fingerprint (function body
        / model parameters — the executable bakes closure constants in),
        jit statics, donation, every argument aval (shape/dtype/
        sharding), h2o_tpu + jax versions and backend topology — a
        mismatch on load is an invalidation, never a wrong program."""
        import jax
        import h2o_tpu
        plat, ndev = _backend_fingerprint()
        parts = [f"schema={SCHEMA_VERSION}", f"name={persist}",
                 f"content={content}",
                 f"jit={sorted((jit_kwargs or {}).items())!r}",
                 f"donate={donate}",
                 f"args={tuple(aval_key(a) for a in args)!r}",
                 f"kwargs={sorted((kwargs or {}).items(), key=lambda kv: kv[0])!r}"
                 if kwargs else "kwargs=()",
                 f"h2o={h2o_tpu.__version__}",
                 f"jax={jax.__version__}", f"backend={plat}x{ndev}"]
        keystr = ";".join(parts)
        return keystr, hashlib.sha256(keystr.encode()).hexdigest()

    def _path(self, stem: str) -> str:
        return os.path.join(store_dir(), f"{stem}.exec")

    _trust_warned = False

    def _check_dir_trust(self) -> None:
        """Loading an entry unpickles it — code execution.  Warn (once)
        when the store directory is writable by group/other, since any
        writer there owns every process that warms from it."""
        if ExecStore._trust_warned:
            return
        try:
            mode = os.stat(store_dir()).st_mode
        except OSError:
            return
        if mode & 0o022:
            ExecStore._trust_warned = True
            log.warning(
                "exec store: %s is group/other-writable (mode %o) — "
                "serialized executables are unpickled on load, so any "
                "principal that can write here can execute code in "
                "every process warming from it; chmod 700 the "
                "directory or unset H2O_TPU_EXEC_STORE_DIR",
                store_dir(), mode & 0o777)

    def _disk_store(self, disk_key: Tuple[str, str], compiled) -> None:
        keystr, stem = disk_key
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree),
                                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:  # noqa: BLE001 — backends without
            # SerializeExecutable fall back to the XLA persistent cache
            with self._lock:
                self.serialize_unsupported += 1
            log.debug("executable serialization unsupported (%r)", e)
            return
        # the executable is bound to the devices it was compiled for (one
        # device for a serve predict, the whole mesh for a sharded
        # kernel); the loader must hand the same ones back
        devices = [d.id for d in
                   compiled._executable._unloaded_executable.device_list]
        header = json.dumps({"schema": SCHEMA_VERSION, "key": keystr,
                             "devices": devices}).encode()
        try:
            os.makedirs(store_dir(), mode=0o700, exist_ok=True)
            self._check_dir_trust()
            path = self._path(stem)
            tmp = f"{path}.tmp.{os.getpid()}"
            fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC,
                         0o600)
            with os.fdopen(fd, "wb") as f:
                f.write(_MAGIC)
                f.write(struct.pack("<I", len(header)))
                f.write(header)
                f.write(blob)
            os.replace(tmp, path)
            with self._lock:
                self.disk_stores += 1
                self.disk_bytes_written += len(blob) + len(header)
        except OSError as e:
            log.warning("exec store: could not persist %s: %r", stem, e)

    def _disk_load(self, phase: str, disk_key: Tuple[str, str]):
        """Load one serialized executable.  NOTE: the payload is
        unpickled — the store directory is a trust boundary (module
        docstring); the header check below validates the KEY, it does
        not authenticate the writer."""
        keystr, stem = disk_key
        path = self._path(stem)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return None
        self._check_dir_trust()
        try:
            buf = io.BytesIO(raw)
            if buf.read(len(_MAGIC)) != _MAGIC:
                raise ValueError("bad magic")
            (hlen,) = struct.unpack("<I", buf.read(4))
            header = json.loads(buf.read(hlen).decode())
            if header.get("schema") != SCHEMA_VERSION or \
                    header.get("key") != keystr:
                raise ValueError("schema/key mismatch")
            payload, in_tree, out_tree = pickle.loads(buf.read())
            import jax
            from jax.experimental import serialize_executable as se
            by_id = {d.id: d for d in jax.devices()}
            fn = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in header["devices"]])
        except Exception as e:  # noqa: BLE001 — an unreadable entry is
            # an invalidation: drop it and rebuild fresh
            with self._lock:
                self.disk_invalid += 1
            log.info("exec store: invalidating %s (%r)", stem, e)
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        with self._lock:
            self.disk_hits += 1
            self.disk_bytes_read += len(raw)
        DispatchStats.note_disk_hit(phase)
        return fn

    # -- lifecycle / observability ------------------------------------------

    def evict(self, match: Callable[[Tuple], bool]) -> int:
        """Drop every entry whose full key (phase-prefixed tuple)
        matches — undeploy/rollback of a serve version, tests."""
        with self._lock:
            victims = [k for k in self._entries if match(k)]
            for k in victims:
                self._entries.pop(k, None)
                self._aot.discard(k)
            return len(victims)

    def keys(self) -> list:
        """Snapshot of live entry keys — callers that keep their own
        bookkeeping over a key subset (the serve engine's bucket map)
        reconcile against this so LRU evictions by OTHER phases never
        leave them reporting a warm program that would recompile."""
        with self._lock:
            return list(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._aot.clear()

    def kernel_names(self) -> Dict[str, list]:
        """Distinct named kernel entries per phase (keys shaped
        ``(phase, name, statics, avals..., donate)``) — how REST
        observability proves e.g. the SHARDED munge variants are
        separate compiled programs from the global ones."""
        out: Dict[str, set] = {}
        with self._lock:
            for k in self._entries:
                if len(k) >= 2 and isinstance(k[0], str) and \
                        isinstance(k[1], str):
                    out.setdefault(k[0], set()).add(k[1])
        return {ph: sorted(names) for ph, names in sorted(out.items())}

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"entries": len(self._entries),
                    "capacity": self.max_entries,
                    "hits": self.hits, "misses": self.misses,
                    "aot_entries": len(self._aot),
                    "evictions": self.evictions,
                    "disk_hits": self.disk_hits,
                    "disk_stores": self.disk_stores,
                    "disk_invalid": self.disk_invalid,
                    "serialize_unsupported": self.serialize_unsupported,
                    "serialized_bytes_written": self.disk_bytes_written,
                    "serialized_bytes_read": self.disk_bytes_read,
                    "dir": store_dir(),
                    "kernels": self.kernel_names()}


_STORE: Optional[ExecStore] = None
_STORE_LOCK = lockwitness.make_lock("exec_store._STORE_LOCK")


def exec_store() -> ExecStore:
    """The process-wide executable store (REST, tests, every cache)."""
    global _STORE
    if _STORE is None:
        with _STORE_LOCK:
            if _STORE is None:
                _STORE = ExecStore()
    return _STORE


def cached_kernel(phase: str, name: str, statics: Tuple,
                  build: Callable[[], Callable], *arrays,
                  persist: bool = True) -> Any:
    """Fetch-or-compile a kernel through the shared store, keyed on
    (phase, name, statics, argument avals) — the munge verbs' (and any
    future kernel layer's) route into the compile-once contract.
    ``build`` returns the RAW kernel function; the store jits, AOT-
    compiles at the given arrays' avals, and (``persist``) serializes it
    under a stable ``phase:name:statics`` disk name, content-keyed on
    the builder's compiled body so an upgraded kernel never loads the
    previous version's program."""
    key = (name, statics, tuple(aval_key(a) for a in arrays))
    fn = exec_store().get_or_build(
        phase, key, build,
        persist=f"{phase}:{name}:{statics!r}" if persist else None,
        content=code_fingerprint(build) if persist else None,
        args=tuple(arrays))
    lockwitness.note_device_dispatch(f"{phase}:{name}")
    DispatchStats.note_dispatch(phase)
    return fn
