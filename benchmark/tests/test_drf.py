"""The DRF cell's own pieces, by hand on the CPU: the cell found by the
names ``BENCHMARK.json`` gives, the cell through its kind at a tiny size
(plain and traced), the refusal of a program without the window form of
the histogram, the planted faults of ``readings_drf.py`` failing against
the cell's limits, and the three readers this cell adds."""

import copy
import time

import pytest

from benchmark import harness
from benchmark.data_higgs_dense import higgs_like_dense
from benchmark.kinds.train_bagged import spec_of
from benchmark.tests import readings_drf

CELL = "drf-higgs-h2odefault.train"


def tiny_job(tmp_path, seed=2 ** 31 + 57, trace=False):
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    config = copy.deepcopy(config)
    config["rows"] = 3000
    config["max_live_leaves"] = 64
    # two trees at the rehearsal's 6 s, the slice opened by the first pull
    traffic = dict(traffic, trees_per_second=1.0 / 3, trace_seconds=0.5)
    job = harness.Job(cell=cell, config=config, traffic=traffic, seed=seed,
                      seconds=6.0, trace=trace, t_start=time.monotonic(),
                      out_dir=tmp_path)
    job.device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    return bench, job


def drive(tmp_path, monkeypatch, **kw):
    bench, job = tiny_job(tmp_path, **kw)
    for k, v in job.config["env"].items():
        monkeypatch.setenv(k, v)
    # restored after the test: the kind sets the cap from the config
    monkeypatch.setenv("H2O_TPU_MAX_LIVE_LEAVES", "64")
    ctx = harness.load_module("kinds", job.traffic["kind"]).run(job)
    return harness.result_line(bench, job, ctx), ctx


def test_the_cell_is_found():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and traffic["kind"] == "train_bagged"
    assert config["builder"] == "h2o_tpu.models.tree.drf:DRF"
    assert config["params"]["max_depth"] == 20
    assert config["env"] == {"H2O_TPU_AUTOTUNE": "0"}
    assert config["max_live_leaves"] == 65536
    assert "max_live_leaves" in config["reduced"]
    names = {m["name"] for m in harness.metrics_for(bench, "per_layer",
                                                     CELL)}
    assert {"window_hist_roofline", "partition_pct", "frontier_cut_pct",
            "hist_pct", "train_mfu"} <= names
    assert "hist_roofline" not in names
    for n in names:
        harness.load_module("metrics", n)


def test_sound_run_is_correct(tmp_path, monkeypatch):
    line, ctx = drive(tmp_path, monkeypatch)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_rate", "setup_s"}
    assert line["attempted"] == 2 and line["failed"] == 0
    assert ctx["counters"]["window_compiles"] == 0
    notes = line["notes"]
    assert notes["final_metrics_source"] == "carried_oob"
    # every block's pull carries the cap's counters
    assert len(notes["frontier"]) == 2
    assert all(f[2] > 0 and f[3] > f[2] for f in notes["frontier"])
    assert line["compared"]["mtries_gap"]["value"] == 0
    assert line["compared"]["frontier_gap"]["value"] == 0


def test_traced_run_reports_the_spans_readers(tmp_path, monkeypatch):
    line, _ = drive(tmp_path, monkeypatch, trace=True)
    # a CPU trace has no device plane: the device_trace readers are
    # silent, the ring's readers are not
    assert "frontier_cut_pct" in line["metrics"]
    assert 0 < line["metrics"]["frontier_cut_pct"]["value"] < 100
    assert "final_score_s" in line["metrics"]
    assert "window_hist_roofline" not in line["metrics"]


def test_a_program_without_the_window_form_is_refused(tmp_path,
                                                      monkeypatch):
    from h2o_tpu.ops import histogram
    monkeypatch.delattr(histogram, "histogram_window_traced")
    _, job = tiny_job(tmp_path)
    with pytest.raises(harness.Refused):
        harness.load_module("kinds", job.traffic["kind"]).run(job)


def test_every_planted_fault_fails_the_cells_limits():
    bench = harness.load_benchmark()
    _, config, traffic = harness.load_cell(bench, CELL)
    config = copy.deepcopy(config)
    config["max_live_leaves"] = 256
    spec = spec_of(config, 28)
    X, y = higgs_like_dense(20000, 28, 103)
    limits = traffic["limits"]
    exact = ("bag_gap", "mtries_gap", "frontier_gap", "oob_rows_gap",
             "oob_points_missing")
    for mode, nums in readings_drf.readings(X, y, spec, 103, 3):
        bad = [k for k, v in nums.items()
               if (k in limits and not v <= limits[k]) or
               (k in exact and v != 0)]
        assert bool(bad) == (mode != "sound"), (mode, bad, nums)


def test_the_readers_on_canned_spans():
    from benchmark.metrics import frontier_cut_pct
    ev = [{"kind": "job", "what": "run", "job": "j", "ns": 0, "dur_ns": 9},
          {"kind": "train", "what": "block.pull", "job": "j", "ns": 1,
           "dur_ns": 1, "frontier_cut": 30, "frontier_split_children": 120,
           "frontier_levels": 3},
          {"kind": "train", "what": "block.pull", "job": "j", "ns": 2,
           "dur_ns": 1, "frontier_cut": 10, "frontier_split_children": 80,
           "frontier_levels": 2}]
    assert frontier_cut_pct.read({}, ev) == pytest.approx(20.0)
    # a parent's spans carry no counters: silent
    for e in ev[1:]:
        for k in ("frontier_cut", "frontier_split_children"):
            e.pop(k)
    assert frontier_cut_pct.read({}, ev) is None
    for name in ("partition_pct", "window_hist_roofline"):
        assert harness.load_module("metrics", name).read(
            {"trace": None}) is None
