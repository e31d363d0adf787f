"""Measured per-backend kernel-lever selection — the autotuner.

The tree engine carries performance levers that are backend-sensitive:
the fused Pallas histogram (H2O_TPU_HIST_PALLAS), the one-hot-matmul
row router (H2O_TPU_MATMUL_ROUTE), sibling subtraction
(H2O_TPU_SIBLING_SUBTRACT), and the packed binned-matrix dtype
(H2O_TPU_BINS_PACK — ops/binpack.py).  Which side wins depends on the chip, the
mesh, and the shape — a hand-run hardware A/B does not survive the
next backend.  This module makes the selection automatic:

* A **lever registry** declares each tunable site with its candidate
  variants (reference FIRST), an example workload per shape-bucket,
  and a joint code fingerprint of every candidate body.
* On first use of a site x bucket, each candidate is compiled ON THE
  LIVE BACKEND and pushed through a two-phase probe:
    1. parity gate — the candidate's output must match its reference
       variant to the lever's tolerance.  A Mosaic miscompile (or any
       wrong-answer variant) is DISQUALIFIED here instead of
       corrupting training; this retires the old "interpret-mode-only
       validated" caveat on the Pallas histogram.
    2. timed steady state — warm-up + median-of-k wall times.  The
       compiling first run sits under the OOM ladder at the dedicated
       ``autotune`` site (GET /3/Resilience), so a probe OOM degrades
       the probe rather than killing the training job.
* The winner (fastest qualified candidate, and only if it beats its
  reference by H2O_TPU_AUTOTUNE_MARGIN) lands in a **decision table**:
  one JSON ``.tune`` record per site x bucket next to the
  H2O_TPU_EXEC_STORE_DIR executables, keyed like disk executables —
  schema, backend platform x device-count, jax + h2o versions, and the
  code fingerprint of every candidate.  A fresh process or replica
  (and the serving ``warm()`` path) reuses decisions with ZERO probe
  runs; an upgraded kernel body, a jax upgrade, or a new backend keys
  to a different record and re-probes cleanly.

Escape hatches (all resolved ONLY here — lint-enforced):
  H2O_TPU_AUTOTUNE=0        reference variants everywhere, zero probes
  H2O_TPU_AUTOTUNE=force    probe on any backend (tests; default
                            ``auto`` probes on TPU only, so CPU tiers
                            stay bitwise-identical to the references)
  H2O_TPU_HIST_PALLAS / H2O_TPU_MATMUL_ROUTE / H2O_TPU_SIBLING_SUBTRACT
  / H2O_TPU_BINS_PACK       tri-state: 1 forces the variant on, 0 off,
                            auto/unset defers to the measured decision.
  H2O_TPU_AUTOTUNE_REPS / _ROWS / _MARGIN
                            probe depth / probe row cap / flip margin.

Consumers (train_forest, histogram_build, the driver) call
``resolve_flag(site)`` at the jit boundary and pass the result in as a
STATIC arg — never re-read env inside a trace.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import statistics
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.exec_store import (SCHEMA_VERSION, backend_fingerprint,
                                     code_fingerprint, store_dir)
from h2o_tpu.core.lockwitness import make_rlock
from h2o_tpu.core.log import get_logger
from h2o_tpu.ops.histogram import (N_STATS, _pallas_eligible,
                                   histogram_build_traced)

log = get_logger("autotune")

_TRUE = ("1", "on", "true", "yes")
_FALSE = ("0", "off", "false", "no")

_LOCK = make_rlock("autotune._LOCK")
_REGISTRY: Dict[str, "Lever"] = {}
_DECISIONS: Dict[Tuple[str, Tuple], dict] = {}
_STATS = {"probes": 0, "probe_runs": 0, "parity_disqualified": 0,
          "probe_failures": 0, "memory_hits": 0, "disk_hits": 0,
          "disk_stores": 0, "disk_invalid": 0, "resolve_errors": 0}


# ---------------------------------------------------------------------------
# env knobs — the ONE module allowed to read them (lint-enforced:
# graftlint GL620/GL621 ban these names everywhere else, so
# decisions always reach traced code as static args)
# ---------------------------------------------------------------------------


def _env_value(var: str) -> str:
    """THE single read point for the autotune / lever env knobs."""
    return os.environ.get(var, "").strip().lower()


def tri_state(var: str) -> Optional[bool]:
    """1/on -> forced True, 0/off -> forced False, auto/unset/other ->
    None (defer to the measured decision)."""
    v = _env_value(var)
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    return None


def stats_dtype_forced() -> Optional[str]:
    """H2O_TPU_STATS_DTYPE named spellings (the tri-state 1/0 pair plus
    explicit carrier names): 1/on/int16 -> "int16", int8 -> "int8",
    0/off/f32/float32 -> "f32", auto/unset/other -> None (defer to the
    ``tree.stats_dtype`` measured decision).  Consumers go through
    ``ops.statpack.resolve_stats_dtype`` — a forced name wins with zero
    probes, exactly like the 1/0 fast path in ``resolve_flag``."""
    v = _env_value("H2O_TPU_STATS_DTYPE")
    if v in _TRUE or v == "int16":
        return "int16"
    if v == "int8":
        return "int8"
    if v in _FALSE or v in ("f32", "float32"):
        return "f32"
    return None


def autotune_mode() -> str:
    """H2O_TPU_AUTOTUNE: ``off`` (0) = reference variants everywhere,
    ``force`` = probe on any backend, default ``auto`` = probe on TPU
    backends only (CPU tiers keep the exact pre-tuner behavior)."""
    v = _env_value("H2O_TPU_AUTOTUNE")
    if v in _FALSE:
        return "off"
    if v == "force":
        return "force"
    return "auto"


def probe_reps() -> int:
    """H2O_TPU_AUTOTUNE_REPS (default 5): timed reps per candidate; the
    recorded figure is the median (steady state, ignores stragglers)."""
    return max(int(_env_value("H2O_TPU_AUTOTUNE_REPS") or "5"), 1)


def probe_margin() -> float:
    """H2O_TPU_AUTOTUNE_MARGIN (default 0.03): a candidate must beat
    its reference by this fraction to flip — hysteresis against timing
    noise flapping a persisted decision."""
    return float(_env_value("H2O_TPU_AUTOTUNE_MARGIN") or "0.03")


def _probe_rows(r: int) -> int:
    """Probe row count: the bucket's rows capped by
    H2O_TPU_AUTOTUNE_ROWS (default 64Ki — probes must stay cheap next
    to the training they tune) and rounded up to the mesh row quantum
    so the histogram shard_map divides evenly."""
    cap = int(_env_value("H2O_TPU_AUTOTUNE_ROWS") or str(1 << 16))
    from h2o_tpu.core.cloud import cloud
    q = cloud().row_multiple()
    n = max(min(int(r), cap), 1)
    return ((n + q - 1) // q) * q


def hist_bucket(rows: int, cols: int, nbins: int, leaves: int) -> Tuple:
    """The hist.kernel lever's shape bucket: pow2 rows (capped) and
    cols so nearby workloads share one decision, exact nbins/leaves
    (they change kernel eligibility and tile shapes outright)."""
    from h2o_tpu.core.exec_store import bucket_pow2
    return (min(bucket_pow2(int(rows)), 1 << 20),
            bucket_pow2(int(cols)), int(nbins), int(leaves))


# ---------------------------------------------------------------------------
# the lever registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Lever:
    """One tunable site.  ``variants[0]`` is the REFERENCE — the
    always-correct portable path that wins all ties and every
    disqualification.  ``true_variants`` maps the winner back onto the
    boolean the consumer passes as a static arg."""
    site: str
    env_var: str
    variants: Tuple[str, ...]
    true_variants: frozenset
    default_bucket: Tuple
    make_workload: Callable[[Tuple], dict]
    run_variant: Callable[[str, dict], Any]
    fingerprint: Callable[[], str]
    eligible: Callable[[str, dict], bool] = lambda v, w: True
    parity_ref: Callable[[str], Optional[str]] = lambda v: None
    tol: Tuple[float, float] = (1e-3, 1e-2)

    @property
    def reference(self) -> str:
        return self.variants[0]

    @property
    def reference_flag(self) -> bool:
        return self.variants[0] in self.true_variants


def register_lever(lever: Lever) -> None:
    """Add (or replace) a lever.  Tests register throwaway levers to
    drive the parity gate; replacing drops any in-memory decisions."""
    with _LOCK:
        _REGISTRY[lever.site] = lever
        for k in [k for k in _DECISIONS if k[0] == lever.site]:
            del _DECISIONS[k]


def unregister_lever(site: str) -> None:
    with _LOCK:
        _REGISTRY.pop(site, None)
        for k in [k for k in _DECISIONS if k[0] == site]:
            del _DECISIONS[k]


def sites() -> Tuple[str, ...]:
    with _LOCK:
        return tuple(_REGISTRY)


def lever(site: str) -> Lever:
    return _REGISTRY[site]


# ---------------------------------------------------------------------------
# decision keys + persistence (JSON data records — NOT pickles; loading
# a tampered record can flip a lever but never executes code)
# ---------------------------------------------------------------------------


def _environ_key() -> Dict[str, str]:
    import h2o_tpu
    plat, ndev = backend_fingerprint()
    return {"h2o": h2o_tpu.__version__, "jax": jax.__version__,
            "backend": f"{plat}x{ndev}"}


def _decision_key(lv: Lever, bucket: Tuple) -> str:
    """Keystr mirroring the exec store's disk keys: schema, site,
    bucket, per-candidate code fingerprints, versions, backend.  Any
    component changing (kernel upgrade, jax bump, new backend) selects
    a different record — stale winners are unreachable, not checked."""
    env = _environ_key()
    return (f"schema={SCHEMA_VERSION};tune={lv.site};"
            f"bucket={tuple(bucket)!r};cands={lv.fingerprint()};"
            f"h2o={env['h2o']};jax={env['jax']};"
            f"backend={env['backend']}")


def _decision_path(keystr: str) -> Optional[str]:
    d = store_dir()
    if d is None:
        return None
    stem = hashlib.sha256(keystr.encode()).hexdigest()[:24]
    return os.path.join(d, stem + ".tune")


def _load_decision(lv: Lever, bucket: Tuple) -> Optional[dict]:
    keystr = _decision_key(lv, bucket)
    path = _decision_path(keystr)
    if path is None or not os.path.exists(path):
        return None
    try:
        with open(path, "r", encoding="utf-8") as f:
            rec = json.load(f)
    except (OSError, ValueError):
        _STATS["disk_invalid"] += 1
        return None
    if rec.get("schema") != SCHEMA_VERSION or rec.get("key") != keystr \
            or rec.get("winner") not in lv.variants:
        _STATS["disk_invalid"] += 1
        return None
    _STATS["disk_hits"] += 1
    rec["source"] = "disk"
    return rec


def _store_decision(rec: dict) -> None:
    path = _decision_path(rec["key"])
    if path is None:
        return
    d = os.path.dirname(path)
    os.makedirs(d, mode=0o700, exist_ok=True)
    tmp = f"{path}.tmp{os.getpid()}"
    try:
        fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(rec, f, sort_keys=True)
        os.replace(tmp, path)
        _STATS["disk_stores"] += 1
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# the two-phase probe
# ---------------------------------------------------------------------------


def _complete(out):
    """Host-fetch barrier: a device->host
    scalar fetch cannot complete until the whole dependency chain has
    executed, so the timed region ends when the work does."""
    leaves = jax.tree_util.tree_leaves(out)
    if leaves:
        float(jnp.sum(leaves[0]))
    return out


def _measure(lv: Lever, name: str, w: dict, reps: int):
    """Compile + run one variant, then median-of-k steady-state times.
    The first (compiling, allocating) execution runs under the OOM
    ladder at the dedicated ``autotune`` site: a transient probe OOM
    sweeps and retries, a terminal one raises OOMError here and the
    caller disqualifies the CANDIDATE — never the training job."""
    from h2o_tpu.core.oom import oom_ladder
    out = oom_ladder(
        "autotune", lambda: _complete(lv.run_variant(name, w)))
    _STATS["probe_runs"] += 1
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _complete(lv.run_variant(name, w))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, float(statistics.median(times))


def _probe(lv: Lever, bucket: Tuple) -> dict:
    reps = probe_reps()
    margin = probe_margin()
    w = lv.make_workload(bucket)
    _STATS["probes"] += 1
    ref_cache: Dict[str, Tuple[Any, float]] = {}

    def baseline(name: str):
        if name not in ref_cache:
            ref_cache[name] = _measure(lv, name, w, reps)
        return ref_cache[name]

    cands: Dict[str, dict] = {}
    _, ref_ms = baseline(lv.reference)
    cands[lv.reference] = {"status": "ok", "median_ms": ref_ms,
                           "vs_ref": 1.0}
    winner, best = lv.reference, 1.0 + margin
    for name in lv.variants[1:]:
        if not lv.eligible(name, w):
            cands[name] = {"status": "ineligible"}
            continue
        rname = lv.parity_ref(name) or lv.reference
        try:
            r_out, r_ms = baseline(rname)
            out, ms = _measure(lv, name, w, reps)
        except Exception as e:  # noqa: BLE001 — OOM/compile kills the
            _STATS["probe_failures"] += 1       # candidate, not the job
            TimeLine.record("safety", "probe_failures", site=lv.site,
                            variant=name)
            cands[name] = {"status": "error",
                           "error": f"{type(e).__name__}: {e}"[:300]}
            continue
        rtol, atol = lv.tol
        if not np.allclose(np.asarray(out), np.asarray(r_out),
                           rtol=rtol, atol=atol, equal_nan=True):
            _STATS["parity_disqualified"] += 1
            TimeLine.record("safety", "parity_disqualified", site=lv.site,
                            variant=name)
            cands[name] = {"status": "parity_fail", "median_ms": ms,
                           "ref": rname}
            continue
        vs = (r_ms / ms) if ms > 0 else 0.0
        cands[name] = {"status": "ok", "median_ms": ms, "ref": rname,
                       "ref_ms": r_ms, "vs_ref": vs}
        if vs >= best:
            best, winner = vs, name
    env = _environ_key()
    return {"schema": SCHEMA_VERSION, "key": _decision_key(lv, bucket),
            "site": lv.site, "bucket": list(bucket), "winner": winner,
            "reference": lv.reference,
            "flag": winner in lv.true_variants, "source": "probe",
            "probe_reps": reps, "margin": margin,
            "candidates": cands, **env}


# ---------------------------------------------------------------------------
# resolution — the consumer surface
# ---------------------------------------------------------------------------


def resolve(site: str, bucket=None) -> dict:
    """The decision record for ``site`` x ``bucket`` (default bucket if
    None): memory -> disk (zero probe runs) -> fresh two-phase probe,
    persisted.  Bypasses the mode/env gating — callers that want the
    gated boolean use ``resolve_flag``."""
    lv = _REGISTRY[site]
    bkt = tuple(bucket) if bucket is not None else lv.default_bucket
    with _LOCK:
        rec = _DECISIONS.get((site, bkt))
        if rec is not None:
            _STATS["memory_hits"] += 1
            return rec
    # probe OUTSIDE the registry lock: a probe compiles and executes
    # device work for seconds, and holding _LOCK across it stalled
    # every other lever resolution — the first real inversion the
    # GL802 runtime witness flagged.  A rare concurrent double-probe
    # is harmless: the first inserter wins, the loser's record (same
    # candidates, same backend) is discarded unpersisted.
    rec = _load_decision(lv, bkt)
    probed = rec is None
    if probed:
        rec = _probe(lv, bkt)
    with _LOCK:
        prior = _DECISIONS.get((site, bkt))
        if prior is not None:
            _STATS["memory_hits"] += 1
            return prior
        _DECISIONS[(site, bkt)] = rec
    if probed:
        _store_decision(rec)
    return rec


def resolve_flag(site: str, bucket=None) -> bool:
    """The lever boolean consumers pass as a static arg at the jit
    boundary.  Explicit env 1/0 wins outright (zero probes); otherwise
    H2O_TPU_AUTOTUNE gating applies (off -> reference; auto -> measured
    on TPU, reference elsewhere; force -> measured everywhere).  Any
    probe failure degrades to the reference variant — the autotuner
    must never take a training job down."""
    lv = _REGISTRY[site]
    forced = tri_state(lv.env_var)
    if forced is not None:
        return forced
    mode = autotune_mode()
    if mode == "off":
        return lv.reference_flag
    if mode != "force":
        from h2o_tpu.core.cloud import backend_is_tpu
        if not backend_is_tpu():
            return lv.reference_flag
    try:
        return bool(resolve(site, bucket)["flag"])
    except Exception:  # noqa: BLE001 — degrade, never kill training
        _STATS["resolve_errors"] += 1
        TimeLine.record("safety", "resolve_errors", site=site)
        log.warning("autotune: resolving %s at %s failed; using the "
                    "reference variant", site, bucket, exc_info=True)
        return lv.reference_flag


def stats() -> dict:
    with _LOCK:
        out = dict(_STATS)
        out["decisions"] = len(_DECISIONS)
        return out


def invalidate_decisions() -> None:
    """Drop the in-memory decision cache ONLY (counters keep running).
    Called by ``Cloud.reform``: decisions are keyed per platform×ndev on
    DISK (``_environ_key``), but the memory cache is keyed (site,
    bucket) alone — after a mesh resize it would keep serving winners
    measured on the old device set.  The next ``resolve`` re-reads the
    correctly-keyed disk record (or re-probes) for the new mesh."""
    with _LOCK:
        _DECISIONS.clear()


def reset() -> None:
    """Drop in-memory decisions and zero the counters (tests; persisted
    ``.tune`` records are untouched — delete the store dir for that)."""
    with _LOCK:
        _DECISIONS.clear()
        for k in _STATS:
            _STATS[k] = 0


def autotune_payload() -> dict:
    """The GET /3/Autotune body."""
    env = _environ_key()
    with _LOCK:
        decisions = [dict(rec) for rec in _DECISIONS.values()]
        levers = [{"site": lv.site, "env": lv.env_var,
                   "variants": list(lv.variants),
                   "reference": lv.reference,
                   "forced": tri_state(lv.env_var)}
                  for lv in _REGISTRY.values()]
    return {"mode": autotune_mode(), "backend": env["backend"],
            "store_dir": store_dir(), "levers": levers,
            "decisions": decisions, "stats": stats()}


# ---------------------------------------------------------------------------
# built-in levers.  Probe workloads are module-level jits (the lint
# suite allows jit only at module scope outside the store) over the
# REAL kernel bodies, so the fingerprints — and therefore the decision
# keys — track the production code.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_leaves", "nbins", "pallas"))
def _hist_plain(bins, leaf, stats_, *, n_leaves, nbins, pallas):
    return histogram_build_traced(bins, leaf, stats_, n_leaves, nbins,
                                  pallas=pallas)


@functools.partial(jax.jit,
                   static_argnames=("n_leaves", "nbins", "fine_na",
                                    "pallas"))
def _hist_adaptive(bins, leaf, stats_, lo, hi, off, is_cat, *, n_leaves,
                   nbins, fine_na, pallas):
    return histogram_build_traced(
        bins, leaf, stats_, n_leaves, nbins,
        fine_map=(lo, hi, off, is_cat, fine_na), pallas=pallas)


def _hist_workload(bucket: Tuple) -> dict:
    R, C, B, L = bucket
    R = _probe_rows(R)
    kb, kl, ks = jax.random.split(jax.random.PRNGKey(42), 3)
    return {
        "bins": jax.random.randint(kb, (R, C), 0, B + 1, jnp.int32),
        "leaf": jax.random.randint(kl, (R,), 0, L, jnp.int32),
        "stats": jax.random.uniform(ks, (R, N_STATS), jnp.float32),
        # identity fine grid: adaptive candidates bucket to the same
        # histogram as the plain grid, so their parity pair is exact
        "lo": jnp.zeros((L, C), jnp.int32),
        "hi": jnp.full((L, C), B - 1, jnp.int32),
        "off": jnp.zeros((L, C), jnp.int32),
        "is_cat": jnp.zeros((C,), bool),
        "C": C, "B": B, "L": L,
    }


def _hist_run(v: str, w: dict):
    if v in ("xla", "pallas"):
        return _hist_plain(w["bins"], w["leaf"], w["stats"],
                           n_leaves=w["L"], nbins=w["B"],
                           pallas=v == "pallas")
    return _hist_adaptive(w["bins"], w["leaf"], w["stats"], w["lo"],
                          w["hi"], w["off"], w["is_cat"],
                          n_leaves=w["L"], nbins=w["B"], fine_na=w["B"],
                          pallas=v == "pallas_adaptive")


def _hist_eligible(v: str, w: dict) -> bool:
    if v == "pallas":
        return _pallas_eligible(w["C"], w["B"] + 1, w["L"], N_STATS,
                                None, True)
    if v == "pallas_adaptive":
        fm = (w["lo"], w["hi"], w["off"], w["is_cat"], w["B"])
        return _pallas_eligible(w["C"], w["B"] + 1, w["L"], N_STATS,
                                fm, True)
    return True


def _hist_fp() -> str:
    from h2o_tpu.ops import hist_pallas as hp
    from h2o_tpu.ops import histogram as hg
    return ",".join(code_fingerprint(f) for f in (
        hg.histogram_build_traced, hg._block_hist, hg.map_buckets,
        hp.hist_pallas, hp.hist_pallas_adaptive))


def _route_gather_impl(bins, lf, col, bitset, na_left, do_split, thr,
                       cat_choice, *, L, Bd):
    """The engine's per-level router on the adaptive path, in the form
    the level's shape picks — the reference the matmul router must
    match bitwise."""
    from h2o_tpu.models.tree.jit_engine import _route_level
    s = {"col": col, "bitset": bitset > 0.5, "na_left": na_left > 0.5}
    go, do = _route_level(bins, lf, s, do_split, Bd, cat_choice,
                          True, thr, Bd)
    return jnp.stack([go, do], axis=1).astype(jnp.float32)


def _route_mm_impl(bins, lf, col, bitset, na_left, do_split, thr,
                   cat_choice, *, L, Bd):
    from h2o_tpu.models.tree.jit_engine import _mm_route_level
    s = {"col": col, "bitset": bitset, "na_left": na_left}
    go, do = _mm_route_level(bins, lf, s, do_split, L, Bd, cat_choice,
                             True, thr, Bd)
    return jnp.stack([go, do], axis=1).astype(jnp.float32)


_route_gather = jax.jit(_route_gather_impl, static_argnames=("L", "Bd"))
_route_mm = jax.jit(_route_mm_impl, static_argnames=("L", "Bd"))


def _mm_workload(bucket: Tuple) -> dict:
    R, C, L, Bd = bucket
    R = _probe_rows(R)
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    return {
        # bin value Bd doubles as the NA sentinel (the adaptive fine
        # grid's F), exercising the na_left branch of both routers
        "bins": jax.random.randint(ks[0], (R, C), 0, Bd + 1, jnp.int32),
        "lf": jax.random.randint(ks[1], (R,), 0, L, jnp.int32),
        "col": jax.random.randint(ks[2], (L,), 0, C, jnp.int32),
        "bitset": (jax.random.uniform(ks[3], (L, Bd + 1)) > 0.5
                   ).astype(jnp.float32),
        "na_left": (jax.random.uniform(ks[4], (L,)) > 0.5
                    ).astype(jnp.float32),
        "do_split": jax.random.uniform(ks[5], (L,)) > 0.5,
        "thr": jax.random.randint(ks[6], (L,), 0, Bd,
                                  jnp.int32).astype(jnp.float32),
        "cat_choice": jax.random.uniform(ks[7], (L,)) > 0.5,
        "L": L, "Bd": Bd,
    }


def _mm_run(v: str, w: dict):
    fn = _route_mm if v == "matmul" else _route_gather
    return fn(w["bins"], w["lf"], w["col"], w["bitset"], w["na_left"],
              w["do_split"], w["thr"], w["cat_choice"], L=w["L"],
              Bd=w["Bd"])


def _mm_fp() -> str:
    from h2o_tpu.models.tree import jit_engine as je
    return ",".join(code_fingerprint(f) for f in (
        je._mm_route_level, je._mm_pick, je._route_level))


def _sib_on_impl(bins, slot, stats_, parent, *, L, B):
    """``_hist_level_with_sibling``'s arithmetic on a fully-split
    parent level: histogram the LEFT children only, right = parent -
    left.  ``parent`` arrives precomputed (untimed) — in the engine the
    parent histogram is the previous level's output, i.e. free."""
    half = L // 2
    left_slot = jnp.where((slot >= 0) & (slot % 2 == 0), slot // 2, -1)
    left = histogram_build_traced(bins, left_slot, stats_, half, B)
    right = parent - left
    return jnp.stack([left, right], axis=1).reshape(L, *left.shape[1:])


def _sib_off_impl(bins, slot, stats_, parent, *, L, B):
    return histogram_build_traced(bins, slot, stats_, L, B)


_sib_on = jax.jit(_sib_on_impl, static_argnames=("L", "B"))
_sib_off = jax.jit(_sib_off_impl, static_argnames=("L", "B"))


def _sib_workload(bucket: Tuple) -> dict:
    R, C, B, L = bucket
    R = _probe_rows(R)
    kb, kl, ks = jax.random.split(jax.random.PRNGKey(11), 3)
    bins = jax.random.randint(kb, (R, C), 0, B + 1, jnp.int32)
    slot = jax.random.randint(kl, (R,), 0, L, jnp.int32)
    stats_ = jax.random.uniform(ks, (R, N_STATS), jnp.float32)
    parent = jax.block_until_ready(_hist_plain(
        bins, slot // 2, stats_, n_leaves=L // 2, nbins=B, pallas=False))
    return {"bins": bins, "slot": slot, "stats": stats_,
            "parent": parent, "B": B, "L": L}


def _sib_run(v: str, w: dict):
    fn = _sib_on if v == "on" else _sib_off
    return fn(w["bins"], w["slot"], w["stats"], w["parent"], L=w["L"],
              B=w["B"])


def _sib_fp() -> str:
    from h2o_tpu.models.tree import jit_engine as je
    return ",".join(code_fingerprint(f) for f in (
        je._hist_level_with_sibling, histogram_build_traced))


def _pack_workload(bucket: Tuple) -> dict:
    from h2o_tpu.ops import binpack
    R, C, F = bucket                    # (rows, C, fine_nbins)
    R = _probe_rows(R)
    kb, kl, ks = jax.random.split(jax.random.PRNGKey(23), 3)
    L = 32
    # int32 reference matrix spanning the full alphabet [0, F] (F is
    # the NA sentinel); the packed candidate is the SAME values in the
    # narrow carrier — the decode contract says they must histogram
    # bitwise-identically
    bins32 = jax.random.randint(kb, (R, C), 0, F + 1, jnp.int32)
    return {
        "bins32": bins32,
        "bins_packed": binpack.cast_bins(bins32,
                                         binpack.bins_dtype_for(F)),
        "leaf": jax.random.randint(kl, (R,), 0, L, jnp.int32),
        "stats": jax.random.uniform(ks, (R, N_STATS), jnp.float32),
        "F": F, "L": L,
    }


def _pack_run(v: str, w: dict):
    bins = w["bins_packed"] if v == "packed" else w["bins32"]
    return _hist_plain(bins, w["leaf"], w["stats"], n_leaves=w["L"],
                       nbins=w["F"], pallas=False)


def _pack_fp() -> str:
    from h2o_tpu.models.tree import shared_tree as st
    from h2o_tpu.ops import binpack as bp
    from h2o_tpu.ops import histogram as hg
    return ",".join(code_fingerprint(f) for f in (
        bp.bins_dtype_for, bp.cast_bins, bp.widen_bins,
        hg._block_hist, hg.histogram_build_traced, st._bin_all))


register_lever(Lever(
    site="hist.kernel",
    env_var="H2O_TPU_HIST_PALLAS",
    variants=("xla", "pallas", "pallas_adaptive"),
    true_variants=frozenset({"pallas", "pallas_adaptive"}),
    default_bucket=(1 << 16, 32, 64, 32),       # (rows, C, nbins, L)
    make_workload=_hist_workload,
    run_variant=_hist_run,
    fingerprint=_hist_fp,
    eligible=_hist_eligible,
    # the adaptive Pallas kernel's parity/timing pair is the XLA scan
    # with the SAME fused fine_map, not the plain-grid reference
    parity_ref=lambda v: "xla_adaptive" if v == "pallas_adaptive"
    else None,
    tol=(1e-3, 1e-2),
))

# note: the "xla_adaptive" baseline above is runnable (run_variant's
# fallthrough handles any non-plain name) but is never a candidate —
# it exists only as pallas_adaptive's parity/timing pair

register_lever(Lever(
    site="tree.matmul_route",
    env_var="H2O_TPU_MATMUL_ROUTE",
    variants=("gather", "matmul"),
    true_variants=frozenset({"matmul"}),
    default_bucket=(1 << 16, 32, 32, 64),       # (rows, C, L, Bd)
    make_workload=_mm_workload,
    run_variant=_mm_run,
    fingerprint=_mm_fp,
    tol=(0.0, 0.0),                             # bitwise by design
))

register_lever(Lever(
    site="tree.sibling_subtract",
    env_var="H2O_TPU_SIBLING_SUBTRACT",
    variants=("on", "off"),                     # pre-tuner default: on
    true_variants=frozenset({"on"}),
    default_bucket=(1 << 16, 32, 64, 16),       # (rows, C, nbins, L)
    make_workload=_sib_workload,
    run_variant=_sib_run,
    fingerprint=_sib_fp,
    tol=(1e-3, 1e-2),                           # f32 reorder only
))

register_lever(Lever(
    site="tree.bins_dtype",
    env_var="H2O_TPU_BINS_PACK",
    variants=("int32", "packed"),
    true_variants=frozenset({"packed"}),
    default_bucket=(1 << 16, 32, 64),           # (rows, C, fine_nbins)
    make_workload=_pack_workload,
    run_variant=_pack_run,
    fingerprint=_pack_fp,
    # the decode contract (ops/binpack.py) promises identical INTEGER
    # bin values under both carriers, so the histograms — and therefore
    # whole forests — must match bitwise, not approximately
    tol=(0.0, 0.0),
))


def _stats_workload(bucket: Tuple) -> dict:
    from h2o_tpu.ops import statpack
    R, C, B = bucket                    # (rows, C, nbins)
    R = _probe_rows(R)
    kb, kl, ks, kq = jax.random.split(jax.random.PRNGKey(29), 4)
    L = 32
    # signed stats (gradients change sign) so stochastic rounding is
    # exercised on both sides of zero.  Quantization happens ONCE per
    # tree in production against per-LEVEL histogram builds, so the
    # probe pre-quantizes in the workload and times the hist alone —
    # the same amortization the training loop gets.
    stats_ = jax.random.uniform(ks, (R, N_STATS), jnp.float32,
                                -1.0, 1.0)
    qmax = statpack.stats_qmax(R, "int16")
    q, inv = statpack.quantize_stats(stats_, kq, "int16", qmax)
    return {
        "bins": jax.random.randint(kb, (R, C), 0, B + 1, jnp.int32),
        "leaf": jax.random.randint(kl, (R,), 0, L, jnp.int32),
        "stats": stats_, "qstats": q, "inv_scale": inv,
        "B": B, "L": L,
    }


def _stats_run(v: str, w: dict):
    from h2o_tpu.ops import statpack
    if v == "f32":
        return _hist_plain(w["bins"], w["leaf"], w["stats"],
                           n_leaves=w["L"], nbins=w["B"], pallas=False)
    t = _hist_plain(w["bins"], w["leaf"], w["qstats"],
                    n_leaves=w["L"], nbins=w["B"], pallas=False)
    return statpack.dequant_table(t, w["inv_scale"])


def _stats_fp() -> str:
    from h2o_tpu.models.tree import jit_engine as je
    from h2o_tpu.ops import histogram as hg
    from h2o_tpu.ops import statpack as sp
    return ",".join(code_fingerprint(f) for f in (
        sp.quantize_stats, sp.dequant_table, sp.stats_qmax,
        hg._block_hist, hg.histogram_build_traced,
        je._hist_level_with_sibling))


register_lever(Lever(
    site="tree.stats_dtype",
    env_var="H2O_TPU_STATS_DTYPE",
    variants=("f32", "int16"),
    true_variants=frozenset({"int16"}),
    default_bucket=(1 << 16, 32, 64),           # (rows, C, nbins)
    make_workload=_stats_workload,
    run_variant=_stats_run,
    fingerprint=_stats_fp,
    # NOT bitwise: stochastic rounding perturbs each table entry by
    # < max|f|/qmax per row.  The band is ops/statpack.py TABLE_TOL;
    # whole-forest metric drift is additionally pinned to
    # statpack.METRIC_TOL by tests/test_stats_pack.py.  A candidate outside the band — or not
    # beating f32 by probe_margin() — is disqualified.
    tol=(0.02, 0.05),
))
