"""The cross-validated cell's own pieces, by hand on the CPU: the cell
found by the names ``BENCHMARK.json`` gives (the case ``test_discovery.py``
would hold, kept here because a PR may only ADD files to the benchmark),
the cell through its kind at a tiny size (plain and traced), the refusal of a
program without the orchestrator's hook, the planted faults against
tolerances of this size, and the three readers this cell adds, on canned
spans."""

import json

import numpy as np
import pytest

from benchmark import harness
from benchmark.data import higgs_like
from benchmark.kinds.train_budgeted import spec_of
from benchmark.reference.gbm_cv import (GbmCvReference, auc_of,
                                        modulo_folds)
from benchmark.tests import readings_cv
from benchmark.tests.test_rehearsal import tiny_job

CELL = "gbm-higgs-xgbhist-cv5.train"


def drive(tmp_path, **kw):
    bench, job = tiny_job(CELL, tmp_path, **kw)
    job.config["rows"] = 3000
    # two trees a model at the rehearsal's 6 s
    job.traffic["trees_per_second"] = 1.0 / 3
    ctx = harness.load_module("kinds", job.traffic["kind"]).run(job)
    return harness.result_line(bench, job, ctx), ctx


def test_sound_run_is_correct(tmp_path):
    line, ctx = drive(tmp_path)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_rate", "setup_s"}
    assert line["attempted"] == 12 and line["failed"] == 0
    c = ctx["counters"]
    assert c["window_compiles"] == 0 and c["models"] == 6
    # (5 x 2,400 + 3,000) rows x 2 trees
    assert c["row_trees"] == 30_000
    assert ctx["end_to_end"]["train_rate"] == pytest.approx(
        30_000 / ctx["clocks"]["window_s"])
    json.dumps(line)


def test_traced_run_reports_the_cells_own_metrics(tmp_path):
    line, _ = drive(tmp_path, trace=True)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["cv_bin_calls"] == 1
    assert m["dispatches_per_tree"] == 1.0 and m["window_compiles"] == 0
    assert 0 < m["cv_holdout_s"] < m["cv_outside_models_s"]
    assert 0 < m["train_mfu"] < 100 and m["final_score_s"] > 0


def test_program_without_the_hook_is_refused_at_once(tmp_path, monkeypatch):
    from h2o_tpu.models.model import ModelBuilder
    monkeypatch.delattr(ModelBuilder, "_cv_shared")
    with pytest.raises(SystemExit) as e:
        drive(tmp_path)
    assert e.value.code != 0


def test_cross_validated_cell_is_found_by_name():
    """The cell PR 37 added: a new kind, a reference of its own and three
    readers, each found by the name ``BENCHMARK.json`` gives."""
    bench = harness.load_benchmark()
    name = "gbm-higgs-xgbhist-cv5.train"
    cell, config, traffic = harness.load_cell(bench, name)
    assert (cell["chips"], traffic["kind"]) == (1, "train_cv")
    assert callable(harness.load_module("kinds", "train_cv").run)
    assert (harness.HERE / "reference" / "gbm_cv.py").is_file()
    assert config["params"]["nfolds"] == 5
    listed = {m["name"] for m in harness.metrics_for(bench, "per_layer",
                                                     name)}
    assert {"cv_bin_calls", "cv_holdout_s", "cv_outside_models_s",
            "train_mfu", "hist_roofline"} <= listed
    assert not listed & {"valid_descent_pct", "valid_final_s",
                         "split_order_pct", "cat_split_pct"}
    # every limit stands between two readings, written beside it
    for k in ("holdout_pred_gap", "holdout_logloss_gap", "cv_logloss_gap",
              "cv_fold_gap"):
        r = traffic["readings"][k]
        assert r["sound"] < traffic["limits"][k] < r["faulty"]


def _ring(job="j1"):
    def ev(what, ns, dur, kind="train", **f):
        return dict(kind=kind, what=what, ns=ns, dur_ns=dur, job=job, **f)
    s = 10 ** 9
    return [ev("run", 0, 40 * s, kind="job"), ev("bin", 0, 14 * s),
            ev("cv.folds", 0, s // 100),
            *[ev("cv.model", (14 + 4 * i) * s, 4 * s, fold=i + 1)
              for i in range(5)],
            *[ev("cv.holdout", (18 + 4 * i) * s, s // 10) for i in range(5)],
            ev("block.score", 15 * s, s), ev("cv.model", 34 * s, 5 * s),
            ev("cv.metrics", 39 * s, s // 2)]


def test_readers_on_canned_spans():
    ring = _ring()
    read = {n: harness.load_module("metrics", n).read
            for n in ("cv_bin_calls", "cv_holdout_s", "cv_outside_models_s")}
    assert read["cv_bin_calls"]({}, ring) == 1
    assert read["cv_holdout_s"]({}, ring) == pytest.approx(1.0)
    assert read["cv_outside_models_s"]({}, ring) == pytest.approx(1.0)
    # the parent's job: eleven binnings, no cv span, two readers silent
    parent = [e for e in ring if not e["what"].startswith("cv.")] + [
        dict(kind="train", what=w, ns=0, dur_ns=1, job="j1")
        for w in ["bin"] * 5 + ["valid.prepare"] * 5]
    assert read["cv_bin_calls"]({}, parent) == 11
    assert read["cv_holdout_s"]({}, parent) is None
    assert read["cv_outside_models_s"]({}, parent) is None
    assert read["cv_bin_calls"]({}, []) is None


def test_reference_pieces():
    assert modulo_folds(7, 3).tolist() == [0, 1, 2, 0, 1, 2, 0]
    p = np.array([0.1, 0.4, 0.35, 0.8, 0.4])
    y = np.array([0, 0, 1, 1, 1])
    # pairs (pos, neg): 0.35>0.1, 0.35<0.4, 0.8>both, 0.4>0.1, 0.4=0.4
    assert auc_of(p, y) == pytest.approx((1 + 0 + 2 + 1 + 0.5) / 6)


@pytest.fixture(scope="module")
def fault_numbers():
    X, y = higgs_like(3000, 6, 23)
    bench = harness.load_benchmark()
    _, config, _ = harness.load_cell(bench, CELL)
    config["params"].update(max_depth=3, min_rows=10, nbins=64)
    ref = GbmCvReference(X, y, spec_of(config), 5)
    ref.prepare()
    return dict(readings_cv.readings(ref, 2, 23 % 5, 2))


@pytest.mark.parametrize("mode,number", [
    ("leak", "root_cover_gap"), ("main_model", "holdout_pred_gap"),
    ("next_fold", "holdout_pred_gap"), ("stale", "holdout_pred_gap"),
    ("in_fold", "cv_logloss_gap")])
def test_each_fault_moves_its_number(fault_numbers, mode, number):
    sound, faulty = fault_numbers["sound"], fault_numbers[mode]
    assert sound[number] <= 1e-9
    assert faulty[number] > (0 if number == "root_cover_gap" else 1e-4)
