"""The airline GBM with its rows sharded over the mesh, against one
device and against the plain reference:

- the split points computed where the rows live (a local sort a shard,
  an exact search of counts reduced over the shards) equal one device's
  bit for bit, and the exact order statistics of the rank rule, on
  frames with missing values, ties, constant columns, a shard whose
  column is all missing and rows that no shard count divides;
- the binomial metric kernel's tables, each shard's rows reduced by one
  ``hpsum``, equal one device's for integer weights;
- a small airline-shaped GBM on the mesh grows one device's forest
  (split columns and left sets exact, leaves to float32 rounding) and is
  ``correct`` under ``benchmark/reference/gbm_mixed_blocked.py``;
- the compiled quantile and metric programs on the mesh hold no
  collective the partitioner inserted, and no all-gather of a row-length
  operand; the ``exec.ready`` records count such collectives;
- the blocked reference equals the single-pass one on a frame whose row
  count no block size divides;
- host rows land shard by shard, the padding made on the last shard
  only.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _frames import CARD, frame_of, mixed_columns
from benchmark.data_airline import RESPONSE, airline_like
from benchmark.kinds.train_mixed import land
from benchmark.kinds.train_sharded import compare, model_numbers
from benchmark.reference.gbm_mixed import GbmMixedReference, Spec
from benchmark.reference.gbm_mixed_blocked import (GbmMixedBlockedReference,
                                                   program_rank_rule)
from h2o_tpu.core.cloud import Cloud, cloud
from h2o_tpu.core.diag import DispatchStats, TimeLine, unowned_collectives
from h2o_tpu.models import metrics as mm
from h2o_tpu.models.tree import shared_tree as st
from h2o_tpu.models.tree.gbm import GBM

TRAFFIC = json.loads((Path(__file__).resolve().parents[1] / "benchmark" /
                      "traffic" / "train_deep_mixed_x4.json").read_text())
LIMITS = TRAFFIC["limits"]


@pytest.fixture()
def reboot(cl):
    """Boot meshes of other sizes, restoring the session cloud after."""
    saved = Cloud._instance
    yield lambda n: Cloud.boot(nodes=n)
    with Cloud._lock:
        Cloud._instance = saved


# ------------------------------------------------------------ split points

def _oracle(m: np.ndarray, nrows: int, nbins: int) -> np.ndarray:
    """The order statistics of the program's rank rule, column by column
    (a zero of either sign comes back as +0.0)."""
    out = np.full((m.shape[1], nbins - 1), np.nan, np.float32)
    for j in range(m.shape[1]):
        x = m[:nrows, j]
        x = np.sort(x[~np.isnan(x)]) + np.float32(0.0)
        if x.size:
            out[j] = x[program_rank_rule(x.size, nbins)]
    return out


def _frame_case(case: str):
    rng = np.random.default_rng(len(case))
    R = 3001                              # no shard count divides it
    m = rng.normal(size=(R, 5)).astype(np.float32)
    m[:, 1] = np.round(m[:, 1] * 3)                       # ties
    if case == "nan":
        m[rng.random(R) < 0.3, 0] = np.nan
        m[:, 3] = np.nan                                  # all missing
    elif case == "constant":
        m[:, 2] = 7.25
        m[:, 4] = 0.0
    elif case == "nan_shard":
        m[: R // 2, 2] = np.nan           # the leading shards hold none
        m[R // 3:, 4] = np.nan
    elif case == "extremes":
        m[5, 0], m[6, 0], m[7, 0] = np.inf, -np.inf, -0.0
    return m, R - 7


@pytest.mark.parametrize("case", ["nan", "constant", "nan_shard",
                                  "extremes"])
def test_split_points_sharded_equal_one_device(reboot, case):
    m, nrows = _frame_case(case)
    got = {}
    for n in (8, 3, 1):
        reboot(n)
        got[n] = st.quantile_split_points(jnp.asarray(m), nrows, 32)
    want = _oracle(m, nrows, 32)
    for n in (8, 3):
        np.testing.assert_array_equal(got[n].view(np.uint32),
                                      got[1].view(np.uint32))
    np.testing.assert_array_equal(got[1], want)


def test_quantile_span_and_its_ici_bytes(reboot):
    reboot(4)
    m, nrows = _frame_case("nan")
    TimeLine.clear()
    st.quantile_split_points(jnp.asarray(m), nrows, 16)
    st.quantile_split_points(jnp.asarray(m), nrows, 16)   # replayed
    evs = [e for e in TimeLine.snapshot() if e.get("what") == "bin.quantile"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["shards"] == 4 and ev["rows_per_shard"] == 3004 // 4
        assert 1 <= ev["rounds"] <= 32
        # counts and ranges once, the search's counts once a round
        C, B1 = 5, 15
        assert ev["ici_bytes"] == 3 * C * 4 + ev["rounds"] * B1 * C * 4


# ------------------------------------------------------------ metric kernel

@pytest.mark.parametrize("weights", ["unit", "fold01"])
def test_metric_kernel_sharded_equals_one_device(reboot, weights):
    rng = np.random.default_rng(3)
    R = 3 * mm._HIST_BLOCK + 501
    p = rng.random(R, dtype=np.float32)
    y = (rng.random(R) < p).astype(np.float32)
    w = (np.ones(R, np.float32) if weights == "unit" else
         (np.arange(R) % 5 != 3).astype(np.float32))
    valid = np.arange(R) < R - 37
    p[~valid] = np.nan
    out = {}
    for n in (8, 1):
        reboot(n)
        out[n] = jax.tree.map(np.asarray, mm.binomial_kernel(p, y, w, valid))
    for k in ("pos", "neg", "wsum"):
        np.testing.assert_array_equal(out[8][k], out[1][k])
    for k in ("logloss", "mse", "ymean"):
        assert out[8][k] == pytest.approx(out[1][k], rel=1e-6)
    assert out[1]["pos"].sum() + out[1]["neg"].sum() == w[valid].sum()


# ------------------------------------------------------------------ forest

PARAMS = dict(ntrees=2, max_depth=4, nbins=64, learn_rate=0.1,
              histogram_type="QuantilesGlobal", min_rows=10,
              min_split_improvement=1e-5, nbins_cats=1024,
              score_tree_interval=1, max_runtime_secs=600, seed=3)


def _forest(n, data):
    Cloud.boot(nodes=n)
    model = GBM(**PARAMS).train(y=RESPONSE, training_frame=land(data))
    return model.output


def test_airline_gbm_on_the_mesh_grows_one_device_forest(reboot):
    reboot(1)
    data = airline_like(6007, 2 ** 31 + 77)
    one = _forest(1, data)
    DispatchStats.install_xla_listener()
    n0 = len(DispatchStats.programs())
    TimeLine.clear()
    mesh = _forest(4, data)
    for k in ("split_col", "bitset", "is_cat", "col_nbins"):
        np.testing.assert_array_equal(np.asarray(mesh[k]),
                                      np.asarray(one[k]), err_msg=k)
    np.testing.assert_array_equal(mesh["split_points"], one["split_points"])
    np.testing.assert_allclose(np.asarray(mesh["value"]),
                               np.asarray(one["value"]), rtol=2e-5,
                               atol=1e-7)
    # no program of the mesh's train holds a collective the partitioner
    # put in
    made = DispatchStats.programs()[n0:]
    assert made and all(not p["gspmd_collectives"] for p in made), [
        (p["fun"], p["gspmd_collectives"]) for p in made]
    launches = [e for e in TimeLine.snapshot()
                if e.get("what") == "block.launch"]
    assert launches and all(e["ici_bytes"] > 0 for e in launches)
    bins = [e for e in TimeLine.snapshot() if e.get("what") == "bin"]
    assert bins and bins[-1]["shards"] == 4
    # correct under the blocked reference, at the cell's limits
    verdict = _compare(data, mesh)
    assert verdict["correct"], verdict["compared"]
    assert verdict["compared"]["split_point_gap"][0] == 0.0
    assert verdict["compared"]["metric_rows_gap"][0] == 0.0


def _compare(data, out):
    """The cell's comparison (``train_sharded.compare``) of a model of
    ``PARAMS`` on ``data``."""
    return compare({"params": PARAMS}, dict(TRAFFIC, processes=2), data,
                   model_numbers(out), PARAMS["ntrees"])


@pytest.mark.parametrize("seed", [2 ** 31 + 5, 11, 2 ** 33 + 1])
def test_metric_shard_left_out_fails_metric_rows_gap(reboot, seed):
    """The metric kernel with the last shard's rows left out of its sums
    and table (``readings_x4.py``'s planted fault, through the program's
    own path): the training metric counts three quarters of the rows, on
    every seed, where the log-loss alone would move by sampling noise."""
    from benchmark.tests.readings_x4 import planted
    data = airline_like(4003, seed)
    reboot(4)
    frame = land(data)
    keep = 3 * (int(frame.padded_rows) // 4)
    with planted("metric_shard_out", keep):
        out = GBM(**PARAMS).train(y=RESPONSE, training_frame=frame).output
    verdict = _compare(data, out)
    gap, limit = verdict["compared"]["metric_rows_gap"]
    assert gap == pytest.approx(1 - keep / len(data.y))
    assert gap > limit and not verdict["correct"]


# ---------------------------------------------------- compiled programs

_GATHER = re.compile(r"= \(?\w+\[([\d,]*)\][^=]*\sall-gather(?:-start)?\(")


def _row_gathers(text: str, rows: int):
    return [m.group(0) for m in _GATHER.finditer(text)
            if any(int(d) >= rows for d in filter(None,
                                                  m.group(1).split(",")))]


def test_compiled_programs_gather_no_rows(cl):
    R = 8 * 1024
    mat = jax.ShapeDtypeStruct((R, 5), jnp.float32,
                               sharding=cl.matrix_sharding())
    vec = jax.ShapeDtypeStruct((R,), jnp.float32, sharding=cl.row_sharding)
    flag = jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=cl.row_sharding)
    texts = [
        st._quantile_split_points.lower(mat, jnp.int32(R), nbins=16,
                                        mesh=cl.mesh).compile().as_text(),
        mm._binomial_kernel.lower(vec, vec, vec, flag,
                                  mesh=cl.mesh).compile().as_text()]
    for text in texts:
        assert unowned_collectives(text) == 0
        assert not _row_gathers(text, R // 8)
    # the partitioner's own reduction of a sharded sum is counted
    plain = jax.jit(lambda x: jnp.sum(x)).lower(vec).compile().as_text()
    assert unowned_collectives(plain) >= 1


def test_exec_ready_records_count_unowned_collectives(cl):
    DispatchStats.install_xla_listener()
    x = jax.device_put(jnp.arange(8 * 64, dtype=jnp.float32),
                       cl.row_sharding)
    n0 = len(DispatchStats.programs())
    jax.jit(lambda v: jnp.sum(v * 1.75 + 0.5))(x).block_until_ready()
    made = [p for p in DispatchStats.programs()[n0:] if "lambda" in p["fun"]]
    assert made and made[-1]["gspmd_collectives"] >= 1
    n1 = len(DispatchStats.programs())
    from h2o_tpu.core.cloud import hsum_rows
    hsum_rows(x * 2.25, "test.sum").block_until_ready()
    owned = [p for p in DispatchStats.programs()[n1:]
             if "sum_rows" in p["fun"]]
    assert owned and owned[-1]["gspmd_collectives"] == 0


# ------------------------------------------------------ blocked reference

def test_blocked_reference_equals_single_pass(cl):
    cols, y = mixed_columns(2 ** 31 + 5, 0.05, rows=5003)
    spec = Spec(5, 64, 1024, 0.1, 10.0, 1e-5)
    one = GbmMixedReference(cols, CARD, y, spec)
    one.prepare()
    trees, f0, history = one.build_forest(2)
    with GbmMixedBlockedReference(cols, CARD, y, spec, processes=3,
                                  block_rows=997) as blk:
        blk.prepare()
        for a, b in zip(one.split_points, blk.split_points):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(one.bins, blk.bins):
            np.testing.assert_array_equal(a, b)
        trees2, f02, history2 = blk.build_forest(2)
        followed = (one.check_forest(trees, f0, history, 2),
                    blk.check_forest(trees, f0, history, 2))
    assert f0 == f02
    for a, b in zip(trees, trees2):
        for k in ("col", "left", "na_left"):
            np.testing.assert_array_equal(getattr(a, k), getattr(b, k))
        np.testing.assert_array_equal(a.thr, b.thr)
        np.testing.assert_allclose(a.value, b.value, rtol=1e-12, atol=0)
    for k in history:
        assert history2[k] == pytest.approx(history[k], rel=1e-13)
    for k, v in followed[0].items():
        if isinstance(v, float):
            assert followed[1][k] == pytest.approx(v, rel=1e-9, abs=1e-12)


def test_blocked_reference_reads_split_points_of_a_shard_left_out(cl):
    """Split points from three of four shards' rows are no order
    statistics of the whole column: ``split_point_gap`` reads them."""
    data = airline_like(8000, 2 ** 31 + 3)
    spec = Spec(3, 255, 1024, 0.1, 10.0, 1e-5)
    R = len(data.y)
    part = np.arange(R) < 3 * R // 4
    with GbmMixedBlockedReference(data.cols, data.card, data.y, spec,
                                  processes=2) as ref:
        ref.prepare(rows=part)
        prog = np.full((13, 254), np.nan, np.float32)
        for c, sp in enumerate(ref.split_points):
            prog[c, :len(sp)] = sp
        nums = ref.prepare(prog)
    assert nums["split_point_gap"] > 0
    assert nums["rank_gap"] > LIMITS["rank_gap"]


# ---------------------------------------------------------------- landing

def test_rows_land_shard_by_shard(reboot):
    from h2o_tpu.core import landing
    reboot(4)
    q = cloud().row_multiple()
    host = np.arange(3 * q + 5, dtype=np.float32)
    landing.reset_stats()
    arr = landing.land_rows(host)
    got = np.asarray(arr)
    assert got.shape[0] % q == 0 and got.shape[0] >= host.shape[0]
    np.testing.assert_array_equal(got[:host.shape[0]], host)
    assert np.isnan(got[host.shape[0]:]).all()
    assert landing.stats()["max_transfer_bytes"] == got.nbytes // 4
    codes = landing.land_rows(np.arange(2 * q + 3, dtype=np.int32))
    assert np.asarray(codes)[2 * q + 3:].tolist() == [0] * (q - 3)
    fr = frame_of(*mixed_columns(2, 0.1, rows=901))
    assert fr.padded_rows % 4 == 0
