"""The files of the mixed-type cell ``gbm-airline-xgbhist.train``, run by
hand as the rest of ``benchmark/tests``:

- its kind, generator, reference and metrics are found by name through
  the command's own ``main`` (a tiny CPU run; the test stands in for the
  look for a chip), and a broken timed path comes out as not correct;
- a program without ``col_nbins`` (a parent commit) is refused at once;
- the reference against itself reads nought, and not correct with each
  planted fault, under the cell's own limits;
- the two readers the cell adds, on a canned trace and a canned ring.
"""

import copy
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness, run, scopes
from benchmark.data_airline import LEVELS, NAMES, airline_like
from benchmark.kinds.train_mixed import spec_of
from benchmark.reference.gbm_mixed import GbmMixedReference
from benchmark.tests.readings_mixed import MODES, reading

CELL = "gbm-airline-xgbhist.train"


def test_generator_is_a_pure_function_of_the_seed():
    a, b = airline_like(5000, 2 ** 31 + 9), airline_like(5000, 2 ** 31 + 9)
    c = airline_like(5000, 2 ** 31 + 10)
    assert a.names == list(NAMES) and len(a.cols) == 13
    for x, y in zip(a.cols, b.cols):
        assert np.array_equal(x, y, equal_nan=True)
    assert np.array_equal(a.y, b.y) and not np.array_equal(a.y, c.y)
    assert a.card == [0] * 6 + [29, 0, 0, 347, 352, 0, 0]
    for n, k in LEVELS.items():
        code = a.cols[a.names.index(n)]
        assert code.dtype == np.int32 and 0 <= code.min() and code.max() < k
        assert a.domains[n] == sorted(a.domains[n]) and len(a.domains[n]) == k
    big = airline_like(400_000, 7)
    assert abs(big.y.mean() - 0.45) < 0.01
    elapsed = big.cols[big.names.index("ActualElapsedTime")]
    assert abs(np.isnan(elapsed).mean() - 0.02) < 0.002
    assert not any(np.isnan(c).any() for n, c in zip(big.names, big.cols)
                   if c.dtype == np.float32 and n != "ActualElapsedTime")
    origin = np.bincount(big.cols[big.names.index("Origin")], minlength=347)
    assert 0.04 < origin.max() / 400_000 < 0.06 and origin.min() > 0


@pytest.fixture()
def tiny(monkeypatch, tmp_path):
    """The cell cut to 20,000 rows and depth 4, the chip stood in for."""
    real = harness.load_cell

    def load_cell(bench, workload):
        cell, config, traffic = real(bench, workload)
        config = copy.deepcopy(config)
        config["rows"] = 20_000
        config["params"].update(max_depth=4, min_rows=10)
        return cell, config, dict(traffic, trees_per_second=1.0,
                                  trace_start_s=0.0, trace_seconds=0.5)
    monkeypatch.setattr(harness, "load_cell", load_cell)
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})


def _main(capsys, trace):
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 17),
                     "--seconds", "2", "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cell_runs_through_main(tiny, capsys):
    line = _main(capsys, 0)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_rate", "setup_s"}
    assert line["attempted"] == 2 and line["failed"] == 0
    assert set(harness.load_json(
        harness.HERE / "traffic" / "train_deep_mixed.json")["limits"]) <= \
        set(line["compared"])
    assert line["notes"]["col_nbins"] == [255] * 6 + [29, 255, 255, 347,
                                                      352, 255, 255]
    assert line["notes"]["enum_split_nodes"] > 0
    traced = _main(capsys, 1)
    # no device plane in a CPU trace: the device_trace readers return
    # nothing; the counter's reader reads the ring
    assert 0 < traced["metrics"]["cat_split_pct"]["value"] <= 100
    assert "split_order_pct" not in traced["metrics"]
    assert traced["notes"]["forest_sha1"] == line["notes"]["forest_sha1"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(tiny, capsys, monkeypatch, fault):
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
    line = _main(capsys, 0)
    assert line["correct"] is False, (fault, line["compared"])


def test_a_program_without_col_nbins_is_refused_at_once(tiny, monkeypatch):
    from h2o_tpu.models.tree import shared_tree
    from typing import NamedTuple

    class Old(NamedTuple):
        bins: object
        nbins: int

    monkeypatch.setattr(shared_tree, "BinnedData", Old)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "2"])
    assert e.value.code not in (0, None)


# ---- the reference in the program's place --------------------------------

@pytest.fixture(scope="module")
def cell():
    bench = harness.load_benchmark()
    _, config, traffic = harness.load_cell(bench, CELL)
    data = airline_like(200_000, 2 ** 31 + 5)
    ref = GbmMixedReference(data.cols, data.card, data.y, spec_of(config))
    ref.prepare()
    return ref, traffic


def over(nums, limits):
    return [k for k, v in nums.items() if k in limits and not v <= limits[k]]


def test_sound_reference_reads_nought(cell):
    ref, traffic = cell
    nums = reading(ref, "sound", int(traffic["check_trees"]),
                   int(traffic["search_trees"]))
    assert over(nums, traffic["limits"]) == []
    assert nums["leaf_value_gap"] == 0.0 and nums["logloss_gap"] == 0.0


@pytest.mark.parametrize("mode", [m for m in MODES if m != "sound"])
def test_control_and_faults_are_not_correct(cell, mode):
    ref, traffic = cell
    nums = reading(ref, mode, int(traffic["check_trees"]),
                   int(traffic["search_trees"]))
    assert over(nums, traffic["limits"]), (mode, nums)
    if mode == "cat_by_code":
        assert "split_gap" in over(nums, traffic["limits"])
    if mode == "na_flip":
        assert {"logloss_gap", "update_gap"} & set(
            over(nums, traffic["limits"]))


# ---- the readers ----------------------------------------------------------

OPS = {"%sort": (0.5, 8), "%take": (0.25, 8), "%cumsum": (0.25, 8),
       "%route": (6.0, 8), "%hist": (3.0, 8)}
PATHS = {
    "%sort": "jit(t)/h2o.tree.split/jit(find_splits)/h2o.tree.split.order/"
             "sort:",
    "%take": "jit(t)/h2o.tree.split/h2o.tree.split.order/gather:",
    "%cumsum": "jit(t)/h2o.tree.split/h2o.tree.split.scan/cumsum:",
    "%route": "jit(t)/h2o.tree.route/gather:",
    "%hist": "jit(t)/h2o.tree.hist.contract/dot_general:"}


def _reader(name):
    return harness.load_module("metrics", name)


def test_split_order_pct_on_a_canned_trace(tmp_path, monkeypatch):
    d = tmp_path / "trace-cell"
    d.mkdir()
    (d / "t.xplane.pb").write_bytes(
        Path(__file__).with_name("fixture_trace.xplane.pb").read_bytes())
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    ctx = {"trace": {"ops": OPS, "busy_s": 10.0, "window_s": 10.0}}
    monkeypatch.setattr(scopes, "op_paths", lambda xp: PATHS)
    assert _reader("split_order_pct").read(ctx) == pytest.approx(7.5)
    # the parent scope's readers see order + scan as one sum
    assert scopes.share_pct(ctx, "h2o.tree.split") == pytest.approx(10.0)
    # a program that names no such scope (a parent commit): left out
    old = {k: v.replace("/h2o.tree.split.order", "")
           .replace("/h2o.tree.split.scan", "") for k, v in PATHS.items()}
    monkeypatch.setattr(scopes, "op_paths", lambda xp: old)
    assert _reader("split_order_pct").read(ctx) is None
    assert _reader("split_order_pct").read({"trace": None}) is None


def _span(kind, what, job, **info):
    return dict(kind=kind, what=what, job=job, dur_ns=1, ns=1, **info)


def test_cat_split_pct_on_a_canned_ring():
    read = _reader("cat_split_pct").read
    ring = [
        # the warm-up job, then the window's two blocks
        _span("train", "block.pull", "warm", num_splits=9, cat_splits=9,
              na_left_splits=0),
        _span("job", "run", "warm"),
        _span("train", "block.pull", "win", num_splits=200, cat_splits=150,
              na_left_splits=3),
        _span("train", "block.pull", "win", num_splits=200, cat_splits=90,
              na_left_splits=1),
        _span("job", "run", "win")]
    assert read({}, ring) == pytest.approx(60.0)
    # a program whose spans carry no counters (a parent commit), a job
    # that split nothing, an empty ring: left out
    bare = [{k: v for k, v in e.items()
             if k not in ("num_splits", "cat_splits")} for e in ring]
    assert read({}, bare) is None
    assert read({}, [_span("train", "block.pull", "w", num_splits=0,
                           cat_splits=0), _span("job", "run", "w")]) is None
    assert read({}, []) is None
