"""The three set-up readers (``setup_programs``, ``setup_programs_compiled``,
``setup_trace_s``) on synthetic tables and rings, and on the program's own
table: the split at the window's root ``job.run``, and None where the
program keeps no table or the ring holds no window."""

import pytest

from benchmark import harness

READERS = ("setup_programs", "setup_programs_compiled", "setup_trace_s")


def _read(name, events, table):
    return harness.load_module("metrics", name).read({}, events, table)


def _span(sid, kind, what, ns, job, parent=None, dur_ns=100):
    return {"ns": ns, "kind": kind, "what": what, "thread": 1,
            "dur_ns": dur_ns, "id": sid, "parent": parent, "job": job}


def _ring():
    """A warm-up job at t 100, then the window's job at t 10,000."""
    return [_span(2, "train", "block.launch", 150, "job_warm", 1),
            _span(1, "job", "run", 100, "job_warm", dur_ns=1000),
            _span(4, "train", "block.launch", 10_050, "job_window", 3),
            _span(3, "job", "run", 10_000, "job_window", dur_ns=5000)]


def _program(ns, cache, trace_s=0.5, lower_s=0.25, compile_s=2.0):
    return {"ns": ns, "kind": "exec", "what": "ready", "fun": "jit(f)",
            "trace_s": trace_s, "lower_s": lower_s, "compile_s": compile_s,
            "cache": cache, "job": None, "parent": None}


def test_records_split_at_the_window_root():
    table = [_program(120, "compiled"), _program(160, "hit"),
             _program(170, "uncached", trace_s=0.125),
             _program(180, "traced", lower_s=0.0, compile_s=0.0),
             _program(9_999, "compiled"),        # warm_final_scoring
             _program(10_001, "compiled"),       # inside the window
             _program(20_000, "compiled")]       # a probe after it
    assert _read("setup_programs", _ring(), table) == 4
    assert _read("setup_programs_compiled", _ring(), table) == 2
    assert _read("setup_trace_s", _ring(), table) == pytest.approx(
        0.75 + 0.75 + 0.375 + 0.5 + 0.75)


def test_a_warm_setup_reads_no_compile():
    table = [_program(120, "hit"), _program(130, "uncached"),
             _program(20_000, "compiled")]
    assert _read("setup_programs_compiled", _ring(), table) == 0
    assert _read("setup_programs", _ring(), table) == 2


@pytest.mark.parametrize("name", READERS)
def test_no_window_root_no_reading(name):
    ring = [e for e in _ring() if e["job"] == "job_warm"
            and e["what"] != "block.launch"]      # no trained job at all
    assert _read(name, ring, [_program(120, "compiled")]) is None
    assert _read(name, [], [_program(120, "compiled")]) is None


@pytest.mark.parametrize("name", READERS)
def test_a_parent_without_the_table_reads_none(name, monkeypatch):
    from h2o_tpu.core import diag
    monkeypatch.delattr(diag.DispatchStats, "programs")
    assert _read(name, _ring(), None) is None


def test_setup_trace_never_exceeds_program_ready(monkeypatch):
    """On the program's own table: a warm-up job compiles, the window's
    job compiles nothing; ``setup_trace_s`` is a part of
    ``program_ready_s``, the three metrics stand in the result line."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from h2o_tpu.core.diag import DispatchStats, TimeLine
    DispatchStats.install_xla_listener()
    TimeLine.clear()
    x = jax.device_put(np.arange(23, dtype=np.float32))
    with TimeLine.span("job", "run", job="job_warm"):
        with TimeLine.span("train", "block.launch"):
            jax.jit(lambda v: jnp.exp(v) * 0.4375)(x).block_until_ready()
    with TimeLine.span("job", "run", job="job_window"):
        with TimeLine.span("train", "block.launch"):
            pass
    ready = _read("setup_trace_s", None, None)
    assert 0 < ready <= harness.load_module(
        "metrics", "program_ready_s").read({})
    assert _read("setup_programs", None, None) >= 1
    table = DispatchStats.programs()
    assert sum(p["trace_s"] + p["lower_s"] + p["compile_s"]
               for p in table) == pytest.approx(
        harness.load_module("metrics", "program_ready_s").read({}),
        abs=1e-9)
    bench = harness.load_benchmark()
    cell = bench["workloads"][0]
    job = harness.Job(cell=cell, config={}, traffic={}, seed=1, seconds=1.0,
                      trace=True, t_start=0.0)
    monkeypatch.setattr(harness, "metrics_for", lambda b, g, w: [
        m for m in b[g] if m["name"] in READERS])
    ctx = {"correct": True, "attempted": 1, "failed": 0, "compared": {}}
    line = harness.result_line(bench, job, ctx)
    assert set(line["metrics"]) == set(READERS)
    assert line["metrics"]["setup_trace_s"]["unit"] == "s"
