"""A tiny end-to-end rehearsal of ``train_budgeted`` on the CPU (2,000
rows, depth 3), and the same run with the timed path broken underneath:
``correct`` has to come out false for each fault the cells can have.

The harness has no option that lets a CPU run through: the test itself
stands in for the look for a chip.
"""

import copy
import json
import time

import numpy as np
import pytest

from benchmark import harness

CELLS = ["gbm-higgs-xgbhist.train", "gbm-higgs-h2odefault.train"]


def tiny_job(workload, tmp_path, seed=2 ** 31 + 17, trace=False):
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, workload)
    config = copy.deepcopy(config)
    config["rows"] = 2000
    config["params"]["max_depth"] = 3
    config["params"]["min_rows"] = 10
    traffic = dict(traffic, trees_per_second=1.0, trace_start_s=0.0,
                   trace_seconds=0.5)
    job = harness.Job(cell=cell, config=config, traffic=traffic, seed=seed,
                      seconds=6.0, trace=trace, t_start=time.monotonic(),
                      out_dir=tmp_path)
    job.device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    return bench, job


def drive(workload, tmp_path, **kw):
    bench, job = tiny_job(workload, tmp_path, **kw)
    kind = harness.load_module("kinds", job.traffic["kind"])
    ctx = kind.run(job)
    return harness.result_line(bench, job, ctx), ctx


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, tmp_path):
    line, ctx = drive(workload, tmp_path)
    assert line["correct"] is True, line["compared"]
    assert list(line)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert set(line["metrics"]) == {"train_rate", "setup_s"}
    assert line["metrics"]["train_rate"]["unit"] == "row-trees/s"
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert ctx["counters"]["window_compiles"] == 0
    json.dumps(line)


def test_traced_run_reports_the_per_layer_metrics(tmp_path):
    line, _ = drive(CELLS[0], tmp_path, trace=True)
    # no device plane in a CPU trace: the device_trace reader returns
    # nothing and its metric is left out, never reported as 0
    assert set(line["metrics"]) == {
        "landing_s", "first_train_s", "window_compiles",
        "dispatches_per_tree", "train_mfu"}
    assert line["metrics"]["dispatches_per_tree"]["value"] == 1.0
    assert 0 < line["metrics"]["train_mfu"]["value"] < 100


def _break(monkeypatch, fault):
    """Plant ``fault`` under the timed path: the driver's train_forest."""
    from h2o_tpu.models.tree import jit_engine
    real = jit_engine.train_forest

    def broken(*args, **kw):
        import jax.numpy as jnp
        if fault == "half_batch":
            keep = jnp.arange(kw["active"].shape[0]) % 2 == 0
            kw = dict(kw, active=kw["active"] & keep)
        tf = real(*args, **kw)
        if fault == "state_unchanged":
            tf = tf._replace(f_final=kw["F0"])
        if fault == "answer_altered":
            tf = tf._replace(value=tf.value * 1.01)
        return tf

    monkeypatch.setattr(jit_engine, "train_forest", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(workload, fault, tmp_path,
                                          monkeypatch):
    _break(monkeypatch, fault)
    line, _ = drive(workload, tmp_path)
    assert line["correct"] is False, (fault, line["compared"])
    over = [k for k, c in line["compared"].items()
            if not c["value"] <= c["limit"]]
    assert over, line["compared"]
