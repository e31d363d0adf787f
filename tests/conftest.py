"""Test harness: a virtual 8-device CPU mesh.

The reference tests multi-node semantics by launching 4 extra local JVMs to
form a real 5-node cloud on loopback (multiNodeUtils.sh:21-27, SURVEY §4).
The TPU-native analog: force the host platform to expose 8 virtual CPU
devices, so every sharding/collective path compiles and executes exactly as
it would on an 8-chip slice — multi-host semantics tested on one box.

Must run before jax is imported anywhere.
"""

import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
# small row alignment so tiny test frames still spread over all 8 devices
os.environ.setdefault("H2O_TPU_ROW_ALIGN", "8")
# persistent XLA compile cache (core/cloud.py _enable_compile_cache):
# explicit CPU opt-in — the tree/GLM suites compile hundreds of programs
# and the cache keeps repeat tier-1 runs inside the time budget
os.environ.setdefault("H2O_TPU_COMPILE_CACHE", "1")
# runtime lock witness (core/lockwitness.py): on for the whole suite so
# every lock the package creates is wrapped and the mid-suite graftlint
# run (test_lint_resilience.test_graftlint_clean) checks the REAL
# witnessed acquisition graph for GL8xx findings.  Must be set before
# any h2o_tpu module creates a lock — the factory decides at creation.
os.environ.setdefault("H2O_TPU_LOCK_WITNESS", "1")

# The suite runs on the virtual CPU mesh on every machine, one with a chip
# included — must happen before any backend is initialized.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cl():
    from h2o_tpu.core.cloud import Cloud
    return Cloud.boot()


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "shared_dkv: module keeps DKV state across tests "
        "(module-scoped fixtures); per-test leak purge disabled")
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy suite (multi-minute on the 1-core CPU "
        "mesh).  Fast tier: pytest -m 'not slow' (~minutes); the full "
        "default run stays the release gate")
    config.addinivalue_line(
        "markers",
        "soak: randomized multi-fault chaos soak (tools/soak.py; "
        "seeded, minute-scale).  Soak tests are ALSO marked slow, so "
        "the tier-1 fast run (-m 'not slow') excludes them by the "
        "existing convention; run explicitly with -m soak or via "
        "tools/soak.py --seed N --duration S")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Print the dispatch-cache hit/miss totals at session end so a
    compile-count regression (misses growing with dispatches instead of
    staying flat) is visible in every tier-1 log without a dedicated
    run."""
    try:
        from h2o_tpu.core.diag import DispatchStats
        from h2o_tpu.core.mrtask import dispatch_cache
        s = dispatch_cache().stats()
        snap = DispatchStats.snapshot()
        terminalreporter.write_line(
            f"[dispatch-cache] hits={s['hits']} misses={s['misses']} "
            f"entries={s['entries']}/{s['capacity']} "
            f"xla_compiles={snap['xla_compiles']} "
            f"dispatches={sum(snap['dispatches'].values())}")
        pulls = snap.get("host_pulls", {})
        pbytes = snap.get("host_pull_bytes", {})
        terminalreporter.write_line(
            "[host-pulls] total={} bytes={} munge={} munge_bytes={}"
            .format(sum(pulls.values()), sum(pbytes.values()),
                    pulls.get("munge", 0), pbytes.get("munge", 0)))
        from h2o_tpu.core import oom, resilience
        from h2o_tpu.core.chaos import chaos
        from h2o_tpu.core.memory import manager
        rs, os_, ms = resilience.stats(), oom.stats(), manager().stats()
        terminalreporter.write_line(
            "[resilience] retries={} recoveries={} giveups={} | "
            "oom_events={} sweeps={} degradations={} terminal={} | "
            "spills={} reloads={} | chaos_injected={}".format(
                rs["retries"], rs["recoveries"], rs["giveups"],
                os_["oom_events"], os_["sweeps"], os_["degradations"],
                os_["terminal_failures"], ms["spills"], ms["reloads"],
                chaos().injected))
        hits, misses = ms["prefetch_hits"], ms["prefetch_misses"]
        rate = hits / (hits + misses) if (hits + misses) else 1.0
        terminalreporter.write_line(
            "[tier] pages_in={} pages_out={} persists={} "
            "persist_reloads={} | prefetch_hits={} misses={} "
            "hit_rate={:.2f} stalls={} | host_bytes={} persist_bytes={} "
            "peak_hbm={}".format(
                ms["pages_in"], ms["pages_out"], ms["persists"],
                ms["persist_reloads"], hits, misses, rate,
                ms["demand_page_stalls"], ms["tiers"]["host"],
                ms["tiers"]["persist"], ms["peak_hbm_bytes"]))
        from h2o_tpu.rapids.plan import PlanStats
        ps = PlanStats.snapshot()
        terminalreporter.write_line(
            "[plan] considered={} fused={} verbs={} repacks_elided={} "
            "syncs_elided={} unfused_fallbacks={} errors={} | "
            "lever fused={} per_verb={}".format(
                ps["regions_considered"], ps["regions_fused"],
                ps["verbs_fused"], ps["repacks_elided"],
                ps["host_syncs_elided"], ps["fallbacks_unfused"],
                ps["planner_errors"], ps["lever_fused"],
                ps["lever_per_verb"]))
        coll = snap.get("collectives", {})
        ici = sum(d["ici_bytes"] for ph in coll.values()
                  for d in ph.values())
        dcn = sum(d["dcn_bytes"] for ph in coll.values()
                  for d in ph.values())
        per_phase = " ".join(
            "{}={}/{}".format(
                p,
                sum(d["ici_bytes"] for d in coll[p].values()),
                sum(d["dcn_bytes"] for d in coll[p].values()))
            for p in ("munge", "rapids.fuse", "tree") if p in coll)
        terminalreporter.write_line(
            "[collectives] ici_bytes={} dcn_bytes={}{}".format(
                ici, dcn, (" | " + per_phase) if per_phase else ""))
        from h2o_tpu.ops import statpack
        sps = statpack.stats()
        terminalreporter.write_line(
            "[stats-pack] quantized_trains={} f32_trains={} "
            "bytes_saved_est={}".format(
                sps["quantized_trains"], sps["f32_trains"],
                sps["bytes_saved_est"]))
        from h2o_tpu.lint import last_summary
        ls = last_summary()
        if ls is not None:
            extra = ""
            if "new" in ls or "stale" in ls:
                extra = " new={} stale={}".format(ls.get("new", 0),
                                                  ls.get("stale", 0))
            terminalreporter.write_line(
                "[graftlint] rules={} modules={} findings={} "
                "suppressed={}{}".format(ls["rules_run"], ls["modules"],
                                         ls["findings"], ls["suppressed"],
                                         extra))
        from h2o_tpu.core import lockwitness
        if lockwitness.enabled():
            ws = lockwitness.registry().stats()
            terminalreporter.write_line(
                "[lock-witness] locks={} acquisitions={} edges={} "
                "cycles={} held_dispatches={}".format(
                    ws["locks_created"], ws["acquisitions"], ws["edges"],
                    len(lockwitness.registry().find_cycles()),
                    ws["held_dispatches"]))
    except Exception:  # noqa: BLE001 — reporting must never fail a run
        pass


_TEST_COUNTER = {"n": 0}


@pytest.fixture(autouse=True)
def _xla_cache_hygiene():
    """Periodically drop jitted-executable caches.  A full-suite run
    compiles many hundreds of XLA:CPU programs in one process; the
    accumulated native state has produced intermittent segfaults in
    late-suite compiles (observed at the uplift forest build).  Bounding
    the live-executable population keeps the compiler's working set in
    the regime every smaller run exercises."""
    yield
    _TEST_COUNTER["n"] += 1
    # 25 (was 40): with the shard_map/cummin compat fixes the suite now
    # exercises ~150 more compiling tests, and the larger live-executable
    # population reproduced the late-suite stall at the concurrent-
    # compile grid test; the persistent compile cache (H2O_TPU_COMPILE_
    # CACHE above) keeps the post-clear recompiles cheap
    if _TEST_COUNTER["n"] % 25 == 0:
        jax.clear_caches()


@pytest.fixture(autouse=True)
def _dkv_leak_check(request):
    """Per-test key-leak enforcement (water/runner/CheckKeysTask analog:
    H2ORunner checks for leaked keys after EVERY test, SURVEY §4).

    Keys a test adds to the DKV and does not remove are leaks: they are
    reported, purged (so tests stay isolated), and — with
    H2O_TPU_STRICT_LEAKS=1 — fail the test.  Modules whose tests share
    DKV state through module-scoped fixtures opt out with the
    ``shared_dkv`` marker."""
    if request.node.get_closest_marker("shared_dkv") is not None:
        yield
        return
    from h2o_tpu.core.cloud import Cloud
    inst = Cloud._instance
    before = set(map(str, inst.dkv.keys())) if inst is not None else set()
    yield
    inst = Cloud._instance
    if inst is None:
        return
    leaked = sorted(set(map(str, inst.dkv.keys())) - before)
    for k in leaked:
        inst.dkv.remove(k, force=True)   # purge even locked leftovers
    if leaked and os.environ.get("H2O_TPU_STRICT_LEAKS") == "1":
        pytest.fail(f"leaked {len(leaked)} DKV keys: {leaked[:20]}")
