"""The files of the four-chip cell ``gbm-airline-xgbhist-x4.train``, run by
hand as the rest of ``benchmark/tests``:

- the cell, its configuration, traffic, kind, generator, reference and
  metrics are found by name through the command's own ``main``: a tiny
  run on four virtual CPU devices (a child process: the devices are set
  before JAX starts; the test stands in for the look for a chip) comes
  out ``correct`` with every metric the cell lists;
- a program that cannot compute split points where the rows live (a
  parent commit) is refused at once, exit 3, before any data is made;
- the blocked generator is a pure function of the seed and draws the
  airline population;
- the collective readers and the metric kernel's roofline on a canned
  trace of four device planes.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness, run, scopes
from benchmark.data_airline import LEVELS, NAMES, airline_like
from benchmark.data_airline_blocked import airline_blocked

CELL = "gbm-airline-xgbhist-x4.train"
ROOT = Path(__file__).resolve().parents[2]
PLAIN = Path(__file__).with_name("fixture_trace.xplane.pb")


def test_generator_is_a_pure_function_of_the_seed():
    a = airline_blocked(5000, 2 ** 31 + 9, threads=3)
    b = airline_blocked(5000, 2 ** 31 + 9, threads=1)
    c = airline_blocked(5000, 2 ** 31 + 10)
    assert a.names == list(NAMES) and len(a.cols) == 13
    for x, y in zip(a.cols, b.cols):
        assert x.dtype == y.dtype and np.array_equal(x, y, equal_nan=True)
    assert np.array_equal(a.y, b.y) and not np.array_equal(a.y, c.y)
    assert a.card == [0] * 6 + [29, 0, 0, 347, 352, 0, 0]
    big = airline_blocked(400_000, 7)
    assert abs(big.y.mean() - 0.45) < 0.01
    elapsed = big.cols[big.names.index("ActualElapsedTime")]
    assert abs(np.isnan(elapsed).mean() - 0.02) < 0.002
    # the same population as airline_like: the same busiest levels
    one = airline_like(400_000, 7)
    for n, k in LEVELS.items():
        j = NAMES.index(n)
        fa = np.bincount(one.cols[j], minlength=k)
        fb = np.bincount(big.cols[j], minlength=k)
        assert np.argmax(fa) == np.argmax(fb)
        assert np.corrcoef(fa, fb)[0, 1] > 0.999


_CHILD = r"""
import copy, os, sys
from benchmark import harness, run
real = harness.load_cell

def load_cell(bench, workload):
    cell, config, traffic = real(bench, workload)
    config = copy.deepcopy(config)
    config["rows"] = 20_011
    config["params"].update(max_depth=4, min_rows=10)
    return cell, config, dict(traffic, trees_per_second=1.0,
                              trace_start_s=0.0, trace_seconds=0.5,
                              processes=2)

harness.load_cell = load_cell
harness.OUT_DIR = harness.Path(sys.argv[1])
harness.require_accelerator = lambda chips: {
    "platform": "cpu", "kind": "TPU v5 lite", "count": chips}
sys.exit(run.main(["--workload", "%s", "--seed", str(2 ** 31 + 17),
                   "--seconds", "2", "--trace", "0"]))
""" % CELL


def test_cell_runs_through_main_on_four_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", H2O_TPU_ROW_ALIGN="8",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT))
    p = subprocess.run([sys.executable, "-c", _CHILD, str(tmp_path)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_rate", "setup_s"}
    assert line["attempted"] == 2 and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert line["compared"]["split_point_gap"]["value"] == 0.0
    assert line["notes"]["gspmd_collectives"] == {}


def test_program_without_sharded_split_points_is_refused(monkeypatch):
    from benchmark.kinds import train_sharded
    from h2o_tpu.models.tree import shared_tree
    monkeypatch.delattr(shared_tree, "quantile_split_points")
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    made = []
    monkeypatch.setitem(train_sharded.GENERATORS, "airline_blocked",
                        lambda *a: made.append(a))
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "50",
                  "--trace", "0"])
    assert e.value.code == 3 and not made


# ---------------------------------------------------------------- readers

_AR = "%all-reduce.{} = f32[{}]{{0}} all-reduce(f32[{}]{{0}} %p), " \
      "replica_groups={{{{0,1,2,3}}}}, to_apply=%add"
OPS = {
    _AR.format(1, "64,13,353,4", "64,13,353,4"): (0.8, 40),   # hist.table
    _AR.format(2, "32,64", "32,64"): (0.04, 8),               # score.hist
    _AR.format(3, "4", "4"): (0.02, 8),                       # score.sums
    "%all-gather.4 = f32[1024,13]{1,0} all-gather(f32[256,13]{1,0} %x)":
        (0.14, 4),                                            # unowned
    "%fusion.5 = f32[64,13,353,4]{3,2,1,0} fusion(%all-reduce.1)":
        (7.0, 40),                                            # histogram
    "%fusion.6 = f32[32,64]{1,0} fusion(f32[8192] %a)": (1.0, 8),
    "%copy.7 = f32[8]{0} copy(f32[8]{0} %b)": (1.0, 4)}
PATHS = {
    list(OPS)[0]: "jit(t)/h2o.tree.hist.contract/h2o.coll.hist.table/psum:",
    list(OPS)[1]: "jit(k)/h2o.score.metrics/h2o.coll.score.hist/psum:",
    list(OPS)[2]: "jit(k)/h2o.score.metrics/h2o.coll.score.sums/psum:",
    list(OPS)[3]: "jit(s)/h2o.bin.quantile/sort:",
    list(OPS)[4]: "jit(t)/h2o.tree.hist.contract/dot_general:",
    list(OPS)[5]: "jit(k)/h2o.score.metrics/dot_general:"}


def _ctx():
    tr = {"busy_s": 10.0, "window_s": 10.0, "ops": OPS, "device_ops": [],
          "idle_gaps": []}
    return {"end_to_end": {}, "clocks": {"window_s": 20.0},
            "counters": {"trees": 2, "rows": 4000},
            "shapes": {"rows": 4000, "cols": 13, "nbins": 353,
                       "max_depth": 8, "fine_nbins": 0, "chips": 4,
                       "rows_per_chip": 1_000_000},
            "device_kind": "TPU v5 lite", "trace": tr}


@pytest.fixture()
def out_dir(tmp_path, monkeypatch):
    d = tmp_path / "out" / "trace-cell"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(PLAIN.read_bytes())
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "out")
    monkeypatch.setattr(scopes, "op_paths", lambda xp: PATHS)


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def test_collective_readers_on_four_planes(out_dir):
    ctx = _ctx()
    total = sum(s for s, _ in OPS.values())
    assert _read("coll_pct", ctx) == pytest.approx(100 * 1.0 / total)
    assert _read("unowned_coll_pct", ctx) == pytest.approx(14.0)
    # 40 all-reduces of a 64 x 13 x 353 x 4 float32 table over 4 chips
    nbytes = 64 * 13 * 353 * 4 * 4
    least = 40 * 2 * 3 / 4 * nbytes / 200e9
    assert _read("hist_psum_roofline", ctx) == pytest.approx(
        100 * least / 0.8)


def test_score_hist_roofline_counts_a_pass_a_plane(out_dir, monkeypatch):
    """8 passes over the planes, each a chip's 1M rows at 13 B over the
    HBM peak, against the kernel's own operations and reductions; the
    same where the chip's compiler combined the two reductions into one
    tuple all-reduce under one of the two scopes."""
    least = 8 * 1_000_000 * 13 / 819e9
    assert _read("score_hist_roofline", _ctx()) == pytest.approx(
        100 * least / (0.04 + 0.02 + 1.0))
    combined = "%all-reduce.8 = (f32[4]{0}, f32[32,64]{1,0}) all-reduce(" \
        "f32[4]{0} %s, f32[32,64]{1,0} %t), replica_groups={{0,1,2,3}}"
    ops = {k: v for k, v in OPS.items() if k not in list(OPS)[1:3]}
    ops[combined] = (0.05, 8)
    paths = dict(PATHS, **{
        combined: "jit(k)/h2o.score.metrics/shard_map/"
                  "h2o.coll.score.sums/psum:"})
    monkeypatch.setattr(scopes, "op_paths", lambda xp: paths)
    ctx = _ctx()
    ctx["trace"]["ops"] = ops
    assert _read("score_hist_roofline", ctx) == pytest.approx(
        100 * least / (0.05 + 1.0))


def test_collective_readers_leave_a_parent_out(out_dir, monkeypatch):
    """A parent commit: no h2o. scope in the trace, and one chip."""
    monkeypatch.setattr(scopes, "op_paths", lambda xp: {})
    for name in ("coll_pct", "unowned_coll_pct", "hist_psum_roofline",
                 "score_hist_roofline"):
        assert _read(name, _ctx()) is None
    monkeypatch.setattr(scopes, "op_paths", lambda xp: PATHS)
    one = _ctx()
    one["shapes"]["chips"] = 1
    assert _read("hist_psum_roofline", one) is None
