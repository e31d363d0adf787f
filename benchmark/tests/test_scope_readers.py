"""The seven readers this PR adds, through ``harness.result_line`` on a
canned ``ctx``: with a program that names its scopes and keeps its
spans, and with one that does neither (a parent commit), where each
metric is left out and nothing raises."""

import shutil
from pathlib import Path

import pytest

from benchmark import harness, scopes, spans

PLAIN = Path(__file__).with_name("fixture_trace.xplane.pb")
NEW = ("hist_pct", "route_pct", "rescore_pct", "unscoped_pct",
       "final_score_s", "program_ready_s", "safety_net_events")

# operation name -> (self seconds, events), as trace.reduce_xplane gives
OPS = {"%hist": (2.0, 10), "%onehot": (0.5, 10), "%psum": (0.5, 1),
       "%route": (3.0, 8), "%predict": (1.0, 1), "%descent": (2.0, 1),
       "%metrics": (0.5, 1), "%split": (0.25, 8), "%stats": (0.125, 1),
       "%copy": (0.125, 1)}
PATHS = {"%hist": "jit(t)/while/body/h2o.tree.hist.contract/dot_general:",
         "%onehot": "jit(t)/h2o.tree.hist.contract/h2o.tree.hist.onehot/eq:",
         "%psum": "jit(t)/h2o.tree.hist.contract/h2o.coll.hist.table/psum:",
         "%route": "jit(t)/while/body/h2o.tree.route/gather:",
         "%predict": "jit(t)/while/body/h2o.tree.predict/gather:",
         "%descent": "jit(forest_score)/h2o.score.descent/while/body/gather:",
         "%metrics": "jit(_accum)/h2o.score.metrics/add:",
         "%split": "jit(t)/h2o.tree.split/jit(find_splits)/argmax:",
         "%stats": "jit(t)/h2o.tree.stats/mul:"}      # %copy: no scope


def _job(bench):
    cell = bench["workloads"][0]
    return harness.Job(cell=cell, config={}, traffic={}, seed=1,
                       seconds=1.0, trace=True, t_start=0.0,
                       device={"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1})


def _ctx(ops):
    tr = {"busy_s": 10.0, "window_s": 10.0, "ops": ops,
          "device_ops": [], "idle_gaps": []}
    return {"end_to_end": {}, "clocks": {"first_train_s": 1.0,
                                         "landing_s": 1.0,
                                         "window_s": 20.0},
            "counters": {"window_compiles": 0, "dispatches": 2,
                         "trees": 2, "rows": 1000},
            "shapes": {"rows": 1000, "cols": 28, "nbins": 255,
                       "max_depth": 8, "fine_nbins": 0, "chips": 1},
            "device_kind": "TPU v5 lite", "trace": tr,
            "memory_peak_bytes": 1, "notes": {}, "attempted": 2,
            "failed": 0, "compared": {}, "correct": True}


@pytest.fixture()
def out_dir(tmp_path, monkeypatch):
    """A harness output directory that holds one trace file."""
    d = tmp_path / "out" / "trace-cell"
    d.mkdir(parents=True)
    shutil.copy(PLAIN, d / "t.xplane.pb")
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "out")
    return tmp_path / "out"


def test_readers_on_a_program_with_scopes_and_spans(out_dir, monkeypatch):
    from h2o_tpu.core.diag import DispatchStats, TimeLine
    monkeypatch.setattr(scopes, "op_paths", lambda xp: PATHS)
    DispatchStats.install_xla_listener()
    import jax.numpy as jnp
    (jnp.arange(5.0) * 2.5).block_until_ready()      # something compiled
    TimeLine.clear()
    # the warm-up job, then a scoring under no job, then the window
    for job in ("job_warm", "job_window"):
        with TimeLine.span("job", "run", job=job):
            with TimeLine.span("train", "block.launch"):
                pass
            with TimeLine.span("train", "final_metrics"):
                pass
    with TimeLine.span("train", "final_metrics"):
        pass
    window = spans.window_spans()
    assert {e["job"] for e in window} == {"job_window"}
    assert [e["what"] for e in window] == ["block.launch", "final_metrics",
                                           "run"]
    bench = harness.load_benchmark()
    line = harness.result_line(bench, _job(bench), _ctx(OPS))
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(m)
    assert m["hist_pct"] == pytest.approx(30.0)         # 2 + .5 + .5 of 10
    assert m["route_pct"] == pytest.approx(30.0)
    assert m["rescore_pct"] == pytest.approx(35.0)      # 1 + 2 + .5
    assert m["unscoped_pct"] == pytest.approx(1.25)
    rest = 100 * (0.25 + 0.125) / 10                    # split + stats
    assert m["hist_pct"] + m["route_pct"] + m["rescore_pct"] + \
        m["unscoped_pct"] + rest == pytest.approx(100.0)
    want = next(e for e in window if e["what"] == "final_metrics")
    assert m["final_score_s"] == want["dur_ns"] / 1e9
    assert m["program_ready_s"] > 0
    assert m["safety_net_events"] == 0
    units = {k: v["unit"] for k, v in line["metrics"].items()}
    assert units["hist_pct"] == "%" and units["final_score_s"] == "s"
    # the metrics that were there still are
    assert {"window_compiles", "dispatches_per_tree", "train_mfu",
            "device_idle_pct", "landing_s", "first_train_s"} <= set(m)


def test_readers_on_a_program_without_them(out_dir, monkeypatch):
    """A parent commit: a trace whose operations carry no ``h2o.`` scope,
    a ring with no span, a listener that keeps no duration."""
    from h2o_tpu.core import diag
    diag.TimeLine.clear()
    monkeypatch.delattr(diag.DispatchStats, "compile_seconds")
    bench = harness.load_benchmark()
    from benchmark import trace
    ops = trace.reduce_xplane(PLAIN)["ops"]     # the real file's own ops
    line = harness.result_line(bench, _job(bench), _ctx(ops))
    for name in NEW[:-1]:
        assert name not in line["metrics"], name
    # the counters of the safety nets exist on a parent too
    assert line["metrics"]["safety_net_events"]["value"] == 0
    assert "device_idle_pct" in line["metrics"]


def test_no_trace_no_share(out_dir):
    ctx = _ctx(OPS)
    ctx["trace"] = None
    assert scopes.window_scopes(ctx) is None
    assert scopes.share_pct(ctx, "h2o.tree.route") is None
