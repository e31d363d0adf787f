"""The histogram contraction's two forms (``ops/histogram.hist_form``).

- ``statpack.bf16_parts`` splits f32 statistics into three bfloat16
  parts whose f32 sum is the statistics bit for bit, and the split
  survives compilation (the compiled program still rounds by
  ``reduce-precision``);
- each form gives today's HIGHEST table: bit for bit on dyadic
  statistics (every sum exact), within 2**-20 of the entry's absolute
  sum on random f32 statistics; on the global grid, the adaptive arm and
  the window arm, with sibling subtraction, the NA bucket hit and a
  padded last block;
- the form is picked from the contraction's static shape at the
  crossover read standalone on the chip;
- ``hist_narrow_levels`` (the ``train.block.launch`` span's field)
  counts the levels the traced program contracts in the narrow form.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame, Vec, T_CAT
from h2o_tpu.models.tree import jit_engine as je
from h2o_tpu.ops import histogram as H
from h2o_tpu.ops import statpack

C, S, BLOCK = 3, 4, 16
FORMS = ("highest", "bf16x3")


# ------------------------------------------------------------ the split

def test_three_parts_sum_to_the_statistics_bit_for_bit():
    """Down to 2**-103: below it a value's last bits lie under float32's
    smallest normal, and the third part, subnormal, is flushed to zero."""
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.normal(size=5000) * 10.0 ** rng.integers(-26, 37, 5000),
        rng.uniform(-1, 1, 5000), [0.0, -0.0, 1.0, -1.0, 3.0e38, 2.0 ** -103,
                                   -(2.0 ** -103) * 1.999],
        (rng.integers(-2 ** 23, 2 ** 23, 1000) * 2.0 ** -23),
    ]).astype(np.float32)
    parts = [np.asarray(p) for p in jax.jit(statpack.bf16_parts)(x)]
    for p in parts:
        # each part is a bfloat16 value
        np.testing.assert_array_equal(
            p.astype(jnp.bfloat16).astype(np.float32), p)
    total = parts[0] + (parts[1] + parts[2])
    np.testing.assert_array_equal(total.view(np.int32) & 0x7FFFFFFF,
                                  x.view(np.int32) & 0x7FFFFFFF)
    np.testing.assert_array_equal(parts[0] + parts[1] + parts[2], x)
    # the split is not trivial: the third part carries bits
    assert (parts[1] != 0).mean() > 0.9 and (parts[2] != 0).mean() > 0.5


def test_the_split_survives_compilation():
    x = jax.ShapeDtypeStruct((64, S), jnp.float32)
    text = jax.jit(statpack.bf16_parts).lower(x).compile().as_text()
    assert text.count("reduce-precision(") >= 2

    def block(b, l, s):
        return H._block_hist(b, l, s, 2, 20)
    args = (jax.ShapeDtypeStruct((64, C), jnp.int32),
            jax.ShapeDtypeStruct((64,), jnp.int32), x)
    assert H.hist_form(2 * S) == "bf16x3"
    text = jax.jit(block).lower(*args).compile().as_text()
    assert text.count("reduce-precision(") >= 2
    assert "bf16[" in text


# ------------------------------------------------- the forms' tables

def _with_form(monkeypatch, form):
    monkeypatch.setattr(H, "hist_form", lambda LS: form)


def _stats(rng, rows, dyadic):
    """Dyadic: 18 significant bits at 2**-20, so a sum of fewer than 128
    terms is exact (an entry here sums about 80 rows, 110 at most);
    else random f32 over ten decades."""
    if dyadic:
        k = rng.integers(-2 ** 17 + 1, 2 ** 17, size=(rows, S))
        return (k * 2.0 ** -20).astype(np.float32)
    return (rng.normal(size=(rows, S)) *
            10.0 ** rng.uniform(-5, 5, size=(1, S))).astype(np.float32)


def _case(cl, codes, L, seed, dyadic, rows=1597):
    """Rows on the 8-node mesh, a tenth inactive, the NA code (the top
    one) drawn for one value in a hundred besides its own share, the
    frame's padding rows inactive with NaN payloads.  1,597 rows pad to
    200 a node: twelve blocks of 16 and 8 rows, which the build pads to
    a block of their own."""
    rng = np.random.default_rng(seed)
    bins_h = rng.integers(0, codes, size=(rows, C)).astype(np.int32)
    bins_h[rng.uniform(size=bins_h.shape) < 0.01] = codes - 1
    leaf_h = rng.integers(0, L, size=rows).astype(np.int32)
    leaf_h[rng.uniform(size=rows) < 0.1] = -1
    stats_h = _stats(rng, rows, dyadic)
    bins = cl.device_put_rows(bins_h)
    pad = bins.shape[0] - rows
    assert bins.shape[0] // cl.n_nodes % BLOCK == 8
    leaf = cl.device_put_rows(
        np.concatenate([leaf_h, np.full(pad, -1, np.int32)]))
    stats = cl.device_put_rows(
        np.concatenate([stats_h, np.full((pad, S), np.nan, np.float32)]))
    return bins, leaf, stats


def _fine_map(L, B1):
    """An adaptive map over a fine grid of ``F = 2 * (B1 - 1)`` bins and
    its NA code F: each node's range the whole grid, two fine bins a
    bucket."""
    F = 2 * (B1 - 1)
    return (jnp.zeros((L, C), jnp.int32), jnp.full((L, C), F - 1, jnp.int32),
            jnp.zeros((L, C), jnp.int32), jnp.zeros((C,), bool), F)


def _codes(arm, B1):
    """Codes a row's bin takes: the table's on the global grid, the fine
    grid's on the adaptive arm (``_fine_map``), NA the top one."""
    return B1 if arm == "global" else 2 * (B1 - 1) + 1


def _tables(monkeypatch, build, dev):
    out = {}
    for form in FORMS:
        _with_form(monkeypatch, form)
        out[form] = np.asarray(jax.jit(build)(*dev))
    return out


def _check(tables, abs_table, dyadic):
    want = tables["highest"]
    assert np.isfinite(want).all() and (want != 0).any()
    got = tables["bf16x3"]
    if dyadic:
        np.testing.assert_array_equal(got, want)
    else:
        assert (np.abs(got - want) <= 2.0 ** -20 * abs_table).all()


def _abs_table(monkeypatch, build, dev):
    """Each entry's sum of absolute terms: the table of |stats|."""
    _with_form(monkeypatch, "highest")
    return np.asarray(jax.jit(build)(dev[0], dev[1], jnp.abs(dev[2])))


@pytest.mark.parametrize("dyadic", [True, False], ids=["dyadic", "random"])
@pytest.mark.parametrize("arm", ["global", "adaptive"])
@pytest.mark.parametrize("L", [1, 8])
def test_the_forms_build_one_table(cl, monkeypatch, L, arm, dyadic):
    B1 = 21
    dev = _case(cl, _codes(arm, B1), L, seed=L * 7 + (arm == "global"),
                dyadic=dyadic)
    fine_map = _fine_map(L, B1) if arm == "adaptive" else None

    def build(b, l, s):
        return H.histogram_build_traced(b, l, s, L, B1 - 1, block_rows=BLOCK,
                                        fine_map=fine_map)
    tables = _tables(monkeypatch, build, dev)
    _check(tables, _abs_table(monkeypatch, build, dev), dyadic)


@pytest.mark.parametrize("dyadic", [True, False], ids=["dyadic", "random"])
def test_the_forms_build_one_table_by_sibling_subtraction(cl, monkeypatch,
                                                          dyadic):
    L, B1 = 8, 256
    bins, leaf, stats = _case(cl, B1, L, seed=11, dyadic=dyadic)
    cfg = {"block_rows": BLOCK, "bf16": False, "pallas": False}

    def build(b, l, s):
        parent_slot = jnp.where(l >= 0, l // 2, -1)
        parent = H.histogram_build_traced(b, parent_slot, s, L // 2, B1 - 1,
                                          block_rows=BLOCK)
        split = jnp.arange(L // 2) != 1          # one parent did not split
        return je._hist_level_with_sibling(b, l, s, L, B1 - 1, cfg, parent,
                                           split)
    dev = (bins, leaf, stats)
    tables = _tables(monkeypatch, build, dev)
    assert not tables["highest"][3].any()        # the unsplit's right
    _check(tables, _abs_table(monkeypatch, build, dev), dyadic)


@pytest.mark.parametrize("dyadic", [True, False], ids=["dyadic", "random"])
@pytest.mark.parametrize("arm", ["global", "adaptive"])
def test_the_forms_build_one_table_on_the_window_arm(cl, monkeypatch, arm,
                                                     dyadic):
    """The window arm's levels are 64 nodes and more, past the rule's
    narrow widths: the form is held to each answer here, as a moved
    crossover would hold it."""
    L, B1 = 64, 21
    dev = _case(cl, _codes(arm, B1), L, seed=5 + (arm == "global"),
                dyadic=dyadic)
    fine_map = _fine_map(L, B1) if arm == "adaptive" else None
    assert H.hist_form(min(H.WINDOW_LEAVES, L) * S) == "highest"

    def build(b, l, s):
        return H.histogram_window_traced(b, l, s, L, B1 - 1,
                                         fine_map=fine_map)
    tables = _tables(monkeypatch, build, dev)
    _check(tables, _abs_table(monkeypatch, build, dev), dyadic)


def test_the_quantized_and_bf16_tables_keep_their_one_form(cl, monkeypatch):
    """The narrow rule is the f32 path's: an integer carrier and the
    bfloat16 control contract as before whatever the shape."""
    seen = []
    rule = H.hist_form

    def spy(LS):
        seen.append(LS)
        return rule(LS)
    monkeypatch.setattr(H, "hist_form", spy)
    b = jnp.zeros((64, C), jnp.int32)
    lf = jnp.zeros((64,), jnp.int32)
    for s, mm in ((jnp.ones((64, S), jnp.int16), jnp.float32),
                  (jnp.ones((64, S), jnp.float32), jnp.bfloat16)):
        out = H._block_hist(b, lf, s, 2, 20, mm)
        assert out.shape == (C * 21, 2 * S)
    assert H.hist_form(2 * S) == "bf16x3"
    assert seen == [2 * S]                     # the assert's own call


# --------------------------------------------------------- the crossover

# one level of the block scan standalone on a v5e chip
# (tools/hist_level_on_chip.py; PERF.md section 6): (C*B1p, L*S)
# -> ms at HIGHEST, ms in the bf16x3 form, medians of 7 calls; 7,168 =
# 5.25M x 28 x 256, 4,680 = 11.5M x 13 x 360, 28,700 = 5.25M x 28 x
# 1,025 on the adaptive arm
CHIP_READING = {
    (7168, 4): (116.135, 67.049), (7168, 8): (115.999, 67.153),
    (7168, 16): (117.180, 68.139), (7168, 32): (118.720, 88.351),
    (7168, 64): (125.815, 122.368), (7168, 128): (197.049, 202.281),
    (7168, 256): (349.223, 371.137), (7168, 512): (661.505, 725.090),
    (4680, 4): (167.120, 100.060), (4680, 8): (168.455, 99.786),
    (4680, 16): (169.753, 101.943), (4680, 32): (172.101, 122.694),
    (4680, 64): (178.952, 182.527), (4680, 128): (287.865, 299.459),
    (4680, 256): (499.762, 544.011), (4680, 512): (951.801, 1038.355),
    (28700, 4): (1488.133, 1374.724), (28700, 8): (1635.908, 1448.551),
    (28700, 16): (1638.257, 1450.936),
}


@pytest.mark.parametrize("CB, LS", sorted(CHIP_READING))
def test_the_rule_takes_the_form_the_chip_read_faster(CB, LS):
    """The narrow form where it read more than 5 % faster; at 64 columns
    the two read within 3 % of each other, and the rule keeps today's
    contraction there."""
    highest_ms, split_ms = CHIP_READING[(CB, LS)]
    assert H.hist_form(LS) == ("bf16x3" if split_ms < 0.95 * highest_ms
                               else "highest")


def test_the_crossover_is_the_same_at_every_one_hot_width_read():
    for LS in (4, 8, 16, 32, 64, 128, 256, 512):
        fast = {split_ms < 0.95 * highest_ms
                for (CB, ls), (highest_ms, split_ms) in CHIP_READING.items()
                if ls == LS}
        assert len(fast) == 1, LS


# ------------------------------------------------ the counter and the trace

def _traced_narrow(monkeypatch, build, cfg, R=64, Cn=5):
    """For each histogram level the build traced: whether its block
    contraction took the narrow form."""
    levels = []
    rule = H.hist_form
    state = {"on": None}

    def spy(LS):
        form = rule(LS)
        if state["on"] is not None:
            state["on"] |= form == "bf16x3"
        return form

    def level(fn):
        def wrapped(*a, **k):
            state["on"] = False
            out = fn(*a, **k)
            levels.append(state["on"])
            state["on"] = None
            return out
        return wrapped
    monkeypatch.setattr(H, "hist_form", spy)
    monkeypatch.setattr(je, "_shard_histogram", level(je._shard_histogram))
    monkeypatch.setattr(je, "histogram_window_traced",
                        level(je.histogram_window_traced))
    bins = jnp.zeros((R, Cn), jnp.int32)
    stats = jnp.ones((R, 4), jnp.float32)
    leaf0 = jnp.zeros((R,), jnp.int32)
    jax.make_jaxpr(lambda b, s, l: build(
        b, s, l, jax.random.PRNGKey(0), jnp.zeros((Cn,), bool), cfg))(
            bins, stats, leaf0)
    return levels


@pytest.mark.parametrize("engine, D, B, adaptive, F, cap, want", [
    ("dense", 8, 255, False, 0, 0, 5),        # cells 1, 3-5, 7
    ("dense", 5, 20, True, 1024, 0, 4),       # cell 2
    ("frontier", 9, 20, True, 1024, 64, 4),   # cell 6's top levels
    ("frontier", 12, 255, False, 0, 128, 5),
])
def test_the_counter_counts_what_the_trace_does(monkeypatch, engine, D, B,
                                                adaptive, F, cap, want):
    cfg = dict(max_depth=D, nbins=B, k_cols=5, newton=True, min_rows=1.0,
               min_split_improvement=0.0, block_rows=64, bf16=False,
               max_live_leaves=cap, sibling=True, adaptive=adaptive,
               fine_nbins=F, hist_random=False, pallas=False,
               mm_route=False)
    build = je.build_tree_frontier if engine == "frontier" else \
        je.build_tree_traced
    seen = _traced_narrow(monkeypatch, build, cfg)
    if cap:
        # the levels from d0 on are one traced loop body
        d0 = je.frontier_loop_start(D, cap, B, F, adaptive)
        assert 0 < d0 < D
        seen = seen[:d0] + seen[d0:] * (D - d0)
    assert len(seen) == D
    kw = dict(max_depth=D, nbins=B, kleaves=cap, adaptive=adaptive,
              fine_nbins=F, bins=jnp.zeros((64, 5), jnp.int32),
              sibling=True, bf16=False, stats_dtype="f32",
              hist_pallas=False)
    assert je.hist_narrow_levels(kw) == sum(seen) == want
    assert je.hist_narrow_levels(dict(kw, bf16=True)) == 0
    assert je.hist_narrow_levels(dict(kw, stats_dtype="int16")) == 0


@pytest.mark.parametrize("histogram_type, want", [
    # built L*S 4, 4, 8, 16, 32, 64, 128, 256: sibling subtraction
    ("QuantilesGlobal", 5),
    # UniformAdaptive builds every node: 4, 8, 16, 32, 64, ...
    ("AUTO", 4),
])
def test_a_depth_8_job_says_how_many_levels_are_narrow(cl, rng,
                                                       histogram_type, want):
    from h2o_tpu.models.tree.gbm import GBM
    n = 800
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + 0.5 * rng.normal(size=n) > 0).astype(np.int32)
    fr = Frame([f"x{j}" for j in range(4)] + ["y"],
               [Vec(X[:, j]) for j in range(4)] +
               [Vec(y, T_CAT, domain=["no", "yes"])])
    TimeLine.clear()
    GBM(ntrees=2, max_depth=8, nbins=20, min_rows=1.0, seed=3,
        histogram_type=histogram_type,
        score_tree_interval=1).train(y="y", training_frame=fr)
    launches = [e for e in TimeLine.snapshot()
                if "dur_ns" in e and (e["kind"], e["what"]) ==
                ("train", "block.launch")]
    assert len(launches) == 2
    assert {e["hist_narrow_levels"] for e in launches} == {want}
