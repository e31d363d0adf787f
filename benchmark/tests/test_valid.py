"""The validated cell's own pieces, by hand on the CPU: the split
generator, the cell through ``run.main`` at a tiny size, the refusal of a
program that scores a second frame by its own codes, the planted faults
of the second frame against the traffic file's limits, and the two
readers this cell adds, on canned fixtures."""

import copy
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import data_airline_split as das
from benchmark import harness, run, scopes
from benchmark.kinds.train_mixed import spec_of
from benchmark.kinds.train_validated import probe_of
from benchmark.reference.gbm_valid import GbmValidReference
from benchmark.tests.readings_valid import MODES, readings

CELL = "gbm-airline-xgbhist-valid.train"


def _domains_differ(split):
    """(levels only training holds, levels only validation holds)."""
    one, other = 0, 0
    for j, n in enumerate(split.names):
        if n in split.enum:
            a, b = split.train.domain_ids(j), split.valid.domain_ids(j)
            one += len(np.setdiff1d(a, b))
            other += len(np.setdiff1d(b, a))
    return one, other


def test_split_is_a_pure_function_of_the_seed(monkeypatch):
    a = das.airline_split(40_000, 10_000, 2 ** 31 + 5)
    b = das.airline_split(40_000, 10_000, 2 ** 31 + 5)
    c = das.airline_split(40_000, 10_000, 2 ** 31 + 6)
    for x, y in zip(a.train.cols + a.valid.cols, b.train.cols + b.valid.cols):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.train.cols[0], c.train.cols[0])
    assert len(a.train.y) == 40_000 and len(a.valid.y) == 10_000
    assert abs(a.valid.y.mean() - 0.45) < 0.02
    # the tail is the population's rarest levels, and it is thinned
    tail = das.tail_ids()
    assert {len(v) for v in tail.values()} == {das.TAIL_LEVELS}
    assert 0.03 < das.tail_mass() < 0.0325
    j = a.names.index("Origin")
    assert not np.isin(a.train.cols[j], tail["Origin"]).any()
    # a frame's codes are places in ITS file's sorted domain
    cols, domains = das.as_frame_columns(a, a.valid)
    ids = a.valid.domain_ids(j)
    assert domains["Origin"] == [f"O{i:03d}" for i in ids]
    np.testing.assert_array_equal(ids[cols[j]], a.valid.cols[j])
    # with a fatter tail than the cell's the files differ both ways, as
    # they do at the cell's size (expected rows a tail level: 0.9)
    monkeypatch.setattr(das, "TAIL_FACTOR", 0.02)
    only_train, only_valid = _domains_differ(
        das.airline_split(60_000, 15_000, 7))
    assert only_train > 5 and only_valid > 0


@pytest.fixture()
def tiny(monkeypatch, tmp_path):
    """The cell cut to 20,000 + 5,000 rows and depth 4, the chip stood in
    for; the level tail fattened so that both files hold levels the other
    lacks, as at the cell's size."""
    real = harness.load_cell

    def load_cell(bench, workload):
        cell, config, traffic = real(bench, workload)
        config = copy.deepcopy(config)
        config.update(rows=20_000, valid_rows=5_000)
        config["params"].update(max_depth=4, min_rows=10)
        return cell, config, dict(traffic, trees_per_second=1.0,
                                  trace_start_s=0.0, trace_seconds=0.5)
    monkeypatch.setattr(harness, "load_cell", load_cell)
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    monkeypatch.setattr(das, "TAIL_FACTOR", 0.05)
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})


def _main(capsys, trace):
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 19),
                     "--seconds", "2", "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cell_runs_through_main(tiny, capsys):
    line = _main(capsys, 0)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_rate", "setup_s"}
    assert line["attempted"] == 2 and line["failed"] == 0
    limits = harness.load_json(
        harness.HERE / "traffic" / "train_deep_validated.json")["limits"]
    assert set(limits) | {"valid_points_missing", "unseen_rows_gap",
                          "unseen_rows_unprobed"} <= set(line["compared"])
    prepared = line["notes"]["valid_prepare"]
    assert prepared["rows"] == 5_000 and prepared["cat_cols"] == 3
    assert prepared["remapped_cols"] >= 2
    assert prepared["unseen_rows"] == \
        line["notes"]["read_not_compared"]["unseen_rows"] > 0
    traced = _main(capsys, 1)
    # no device plane in a CPU trace: the device_trace readers return
    # nothing; the host clock's and the counters' read the ring
    assert 0 < traced["metrics"]["valid_final_s"]["value"] < \
        traced["metrics"]["final_score_s"]["value"]
    assert "valid_descent_pct" not in traced["metrics"]
    assert 0 < traced["metrics"]["cat_split_pct"]["value"] <= 100
    assert traced["notes"]["forest_sha1"] == line["notes"]["forest_sha1"]


@pytest.mark.parametrize("fault", ["unmapped", "unseen_last", "stale"])
def test_broken_second_frame_is_not_correct(tiny, capsys, monkeypatch,
                                            fault):
    from h2o_tpu.core import frame as frame_mod
    from h2o_tpu.models.tree import driver
    if fault == "unmapped":
        # the parent: a frame's own codes are taken for training's
        monkeypatch.setattr(frame_mod, "domain_table", lambda f, t: None)
        from h2o_tpu.models import model as model_mod
        monkeypatch.setattr(model_mod, "domain_table", lambda f, t: None)
    elif fault == "unseen_last":
        real = frame_mod.domain_table

        def last_level(f, t):
            table = real(f, t)
            if table is not None:
                table[:len(f)][np.isnan(table[:len(f)])] = len(t) - 1
            return table
        from h2o_tpu.models import model as model_mod
        monkeypatch.setattr(model_mod, "domain_table", last_level)
    else:
        real_score = driver.IncrementalScorer.score

        def stale(self, tf, n):
            before = self.F
            out = real_score(self, tf, n)
            return [out[0], ("validation_", self.valid_metrics(before, n))]
        monkeypatch.setattr(driver.IncrementalScorer, "score", stale)
        monkeypatch.setenv("H2O_TPU_DONATE", "0")
    line = _main(capsys, 0)
    assert line["correct"] is False, (fault, line["compared"])


def test_a_program_that_scores_by_its_own_codes_is_refused_at_once(
        tiny, monkeypatch):
    from h2o_tpu.models import model as model_mod
    from benchmark.kinds import train_validated
    monkeypatch.delattr(model_mod, "adapt_frame")
    made = []
    monkeypatch.setitem(train_validated.GENERATORS, "airline_like_split",
                        lambda *a: made.append(a))
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "2"])
    assert e.value.code == 3 and not made


# ---- the reference in the program's place --------------------------------

@pytest.fixture(scope="module")
def faults():
    """mode -> numbers, at 60,000 + 15,000 rows with a fatter tail."""
    bench = harness.load_benchmark()
    _, config, traffic = harness.load_cell(bench, CELL)
    mp = pytest.MonkeyPatch()
    mp.setattr(das, "TAIL_FACTOR", 0.05)
    try:
        split = das.airline_split(60_000, 15_000, 2 ** 31 + 21)
    finally:
        mp.undo()
    ref = GbmValidReference(
        split.train.cols, split.valid.cols,
        [n in split.enum for n in split.names], split.train.y,
        split.valid.y, spec_of(config))
    ref.prepare()
    ids = {j: split.valid.cols[j] for j in ref.domains}
    assert ref.unseen_rows > 0
    return traffic["limits"], dict(readings(
        ref, ids, 2, probe_of(split, int(traffic["probe_rows"]))))


def _over(nums, limits):
    exact = ("valid_points_missing", "unseen_rows_gap",
             "unseen_rows_unprobed")
    return sorted(k for k, v in nums.items()
                  if (k in exact and v > 0) or v > limits.get(k, np.inf))


@pytest.mark.parametrize("mode", MODES)
def test_second_frames_faults_are_not_correct(faults, mode):
    limits, nums = faults
    if mode == "sound":
        assert not _over(nums[mode], limits), nums[mode]
    else:
        assert _over(nums[mode], limits), (mode, nums[mode])


# ---- the two readers this cell adds --------------------------------------

OPS = {"%hist": (5.0, 10), "%adapt": (0.25, 3), "%bin": (1.5, 1),
       "%descent": (0.75, 2), "%metrics": (0.5, 4), "%assign": (2.0, 1)}
PATHS = {"%hist": "jit(t)/while/body/h2o.tree.hist.contract/dot_general:",
         "%adapt": "jit(_codes_in_domain)/h2o.score.adapt/gather:",
         "%bin": "jit(_bin_all)/h2o.score.bin/while/body/select_n:",
         "%descent": "jit(forest_score)/h2o.score.descent/while/gather:",
         "%metrics": "jit(_accum)/h2o.score.metrics/add:",
         "%assign": "jit(_bin_all)/h2o.bin.assign/while/body/select_n:"}


def _reader(name):
    return harness.load_module("metrics", name)


def test_valid_descent_pct_on_a_canned_trace(tmp_path, monkeypatch):
    d = tmp_path / "trace-cell"
    d.mkdir()
    (d / "t.xplane.pb").write_bytes(
        Path(__file__).with_name("fixture_trace.xplane.pb").read_bytes())
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    ctx = {"trace": {"ops": OPS, "busy_s": 10.0, "window_s": 10.0}}
    monkeypatch.setattr(scopes, "op_paths", lambda xp: PATHS)
    read = _reader("valid_descent_pct").read
    assert read(ctx) == pytest.approx(25.0)
    # the training frame's binning and the metric kernels are not in it
    assert scopes.share_pct(ctx, "h2o.score.") == pytest.approx(30.0)
    # a job with one frame, a parent commit, no trace: left out
    one = {k: v for k, v in PATHS.items()
           if k in ("%hist", "%metrics", "%assign")}
    monkeypatch.setattr(scopes, "op_paths", lambda xp: one)
    assert read(ctx) is None
    assert read({"trace": None}) is None


def _span(kind, what, job, dur_ns=1, **info):
    return dict(kind=kind, what=what, job=job, dur_ns=dur_ns, ns=1, **info)


def test_valid_final_s_on_a_canned_ring():
    read = _reader("valid_final_s").read
    ring = [
        _span("train", "block.pull", "warm"),
        _span("train", "final_metrics.valid", "warm", 9_000_000_000),
        _span("job", "run", "warm"),
        _span("train", "block.pull", "win"),
        _span("train", "final_metrics.valid", "win", 250_000_000,
              source="carried_F"),
        _span("train", "final_metrics", "win", 400_000_000),
        _span("job", "run", "win")]
    assert read({}, ring) == pytest.approx(0.25)
    # a program with no such span (a parent commit, a job with one
    # frame), an empty ring: left out
    assert read({}, [e for e in ring
                     if e["what"] != "final_metrics.valid"]) is None
    assert read({}, []) is None
