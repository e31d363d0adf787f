"""Growth's routing in its two forms (``jit_engine._route_level``).

- the select form (each row's record picked from the level's L nodes by
  compare-select-sums over packed words) returns the gather form's
  ``(go_left, do_lf)`` bit for bit on every row, and both follow the rule
  written out in numpy: the row's node is ``max(leaf, 0)`` (an inactive
  row reads node 0), its bin the node's column's, ``go_left`` the bin's
  bit in the node's left set (the NA slot set and unset, enum sets no
  prefix of the levels) or, on the adaptive arm, the node's threshold
  and NA side where ``cat_choice`` says so; ``do_lf`` the node's
  ``do_split`` (false on some nodes);
- the form is picked from the level's static shape: every level of the
  cells' trees selects, a frontier past the crossover gathers;
- ``route_plan`` (the ``train.block.launch`` span's fields) counts what
  the engine's trace does, and a depth-8 job's span reads 8 of 8.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame, Vec, T_CAT
from h2o_tpu.models.tree import jit_engine as je

ROWS, COLS = 3001, 6
NODES = (1, 2, 7, 64, 128)
SLOTS = (21, 256, 354, 1025)


def _level(L, S, seed):
    """A level's record over random rows: every node's left set a random
    subset of the S slots (no prefix), the NA slot (``S - 1``) set on half
    the nodes, ``do_split`` false on a fifth; a tenth of the rows
    inactive (``leaf`` -1); bins over every slot, the NA one included."""
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, S, size=(ROWS, COLS)).astype(np.int32)
    bins[rng.uniform(size=bins.shape) < 0.05] = S - 1
    leaf = rng.integers(0, L, size=ROWS)
    leaf[rng.uniform(size=ROWS) < 0.1] = -1
    bitset = rng.uniform(size=(L, S)) < 0.5
    bitset[:, S - 1] = np.arange(L) % 2 == 0
    return dict(bins=bins, leaf=leaf, col=rng.integers(0, COLS, size=L),
                bitset=bitset, do=rng.uniform(size=L) < 0.8,
                na_left=rng.uniform(size=L) < 0.5,
                cat=rng.uniform(size=L) < 0.5)


def _route(monkeypatch, lv, Bd, adaptive, thr, F, select):
    """The level routed in one form: the crossover moved so that this
    shape selects (or gathers), traced afresh."""
    monkeypatch.setattr(je, "ROUTE_SELECT_MAX", 1 << 30 if select else 0)

    @jax.jit
    def f(bins, lf, col, bitset, do, na_left, cat, thr):
        s = {"col": col, "bitset": bitset, "na_left": na_left}
        return je._route_level(bins, lf, s, do, Bd, cat, adaptive, thr, F)
    out = f(jnp.asarray(lv["bins"]),
            jnp.asarray(np.maximum(lv["leaf"], 0), jnp.int32),
            jnp.asarray(lv["col"], jnp.int32), jnp.asarray(lv["bitset"]),
            jnp.asarray(lv["do"]), jnp.asarray(lv["na_left"]),
            jnp.asarray(lv["cat"]),
            None if thr is None else jnp.asarray(thr, jnp.int32))
    return [np.asarray(a) for a in out]


def _assert_forms_agree(monkeypatch, lv, want_go, Bd, adaptive=False,
                        thr=None, F=-1):
    lf = np.maximum(lv["leaf"], 0)
    sel = _route(monkeypatch, lv, Bd, adaptive, thr, F, True)
    gat = _route(monkeypatch, lv, Bd, adaptive, thr, F, False)
    for got in (sel, gat):
        np.testing.assert_array_equal(got[0], want_go)
        np.testing.assert_array_equal(got[1], lv["do"][lf])
    np.testing.assert_array_equal(sel[0], gat[0])
    np.testing.assert_array_equal(sel[1], gat[1])
    # what the case holds: both directions, nodes that do not split,
    # inactive rows, which read node 0
    assert 0 < want_go.sum() < want_go.size
    assert (~lv["do"][lf]).any() or not (~lv["do"]).any()
    off = lv["leaf"] < 0
    assert off.any()
    np.testing.assert_array_equal(sel[1][off], lv["do"][0])


@pytest.mark.parametrize("S", SLOTS)
@pytest.mark.parametrize("L", NODES)
def test_select_form_is_the_gather_form_bit_for_bit(monkeypatch, L, S):
    lv = _level(L, S, seed=L * 10_000 + S)
    lf = np.maximum(lv["leaf"], 0)
    b = lv["bins"][np.arange(ROWS), lv["col"][lf]]
    want = lv["bitset"][lf, b]
    # NA rows went both ways, and some sets are no prefix of the slots
    na = b == S - 1
    assert na.any() and 0 < want[na].sum() < na.sum() or L == 1
    body = lv["bitset"][:, :S - 1]
    assert any(not r[:int(r.sum())].all() for r in body)
    _assert_forms_agree(monkeypatch, lv, want, S - 1)


@pytest.mark.parametrize("L, S, F", [(1, 1025, 1024), (7, 1025, 1024),
                                     (128, 1025, 1024), (1, 21, 64),
                                     (7, 21, 64), (128, 21, 1024)])
def test_adaptive_arm_select_is_gather(monkeypatch, L, S, F):
    """The adaptive level: slot ``Bd = S - 1`` is the NA bucket of an enum
    set, a numeric node compares the fine bin with its threshold and
    sends bin ``F`` by ``na_left``; ``cat_choice`` picks the rule per
    node.  ``F > Bd`` is a level below the root's grid."""
    Bd = S - 1
    lv = _level(L, S, seed=7 * L + S + F)
    rng = np.random.default_rng(F + L)
    lv["bins"] = rng.integers(0, F + 1, size=(ROWS, COLS)).astype(np.int32)
    lv["bins"][rng.uniform(size=lv["bins"].shape) < 0.05] = F
    thr = rng.integers(0, F, size=L)
    lf = np.maximum(lv["leaf"], 0)
    b = lv["bins"][np.arange(ROWS), lv["col"][lf]]
    gset = lv["bitset"][lf, np.minimum(b, Bd)]
    gthr = np.where(b == F, lv["na_left"][lf], b < thr[lf])
    want = np.where(lv["cat"][lf], gset, gthr)
    assert lv["cat"][lf].any() and (~lv["cat"][lf]).any() or L == 1
    _assert_forms_agree(monkeypatch, lv, want, Bd, adaptive=True, thr=thr,
                        F=F)


# ---------------------------------------------------------- the crossover

CELL_TREES = {
    # (max_depth, nbins, adaptive, fine_nbins): the cells' trees
    "higgs_255_bins": (8, 255, False, 255),
    "airline_353_bins": (8, 353, False, 353),
    "h2o_default_adaptive": (5, 20, True, 1024),
}


@pytest.mark.parametrize("tree", sorted(CELL_TREES))
def test_every_level_of_a_cell_tree_selects(tree):
    D, B, adaptive, F = CELL_TREES[tree]
    for d in range(D):
        slots = (max(B, F >> d) if adaptive else B) + 1
        assert je.route_selects(2 ** d, slots), (d, slots)
    kw = dict(max_depth=D, nbins=B, kleaves=0, adaptive=adaptive,
              fine_nbins=F, mm_route=False)
    assert je.route_plan(kw) == (D, D)


@pytest.mark.parametrize("slots", [256, 354])
def test_a_frontier_past_the_crossover_gathers(slots):
    W = je.route_words(slots)
    assert W == {256: 8, 354: 12}[slots]
    wide = [L for L in (512, 1024, 2048, 4096)
            if L * (W + 1) > je.ROUTE_SELECT_MAX]
    assert 4096 in wide
    for L in wide:
        assert not je.route_selects(L, slots)
    # a deep forest on the frontier engine: its narrow levels select,
    # its wide ones gather
    kw = dict(max_depth=14, nbins=slots - 1, kleaves=4096, adaptive=False,
              fine_nbins=0, mm_route=False)
    levels, selects = je.route_plan(kw)
    # the levels from the loop's start on run at the cap's width
    d0 = je.frontier_loop_start(14, 4096, slots - 1, 0, False)
    widths = je.frontier_plan(14, 4096)[:d0] + [4096] * (14 - d0)
    assert levels == 14 and 0 < selects < 14
    assert selects == sum(je.route_selects(L, slots) for L in widths)


# one level standalone on a v5e chip, int32 bins (PERF.md section 6, PR
# 41): (L, slots) -> ms of the select form, ms of the gather form
CHIP_READING = {(128, 256): (11.1, 146.3), (1024, 256): (40.8, 148.1),
                (2048, 256): (81.1, 148.0), (4096, 256): (159.1, 147.9),
                (128, 354): (41.6, 295.0), (1024, 354): (145.9, 303.3),
                (2048, 354): (349.7, 303.2), (4096, 354): (693.4, 303.4)}


@pytest.mark.parametrize("L, slots", sorted(CHIP_READING))
def test_the_rule_takes_the_form_the_chip_read_faster(L, slots):
    select_ms, gather_ms = CHIP_READING[(L, slots)]
    assert je.route_selects(L, slots) == (select_ms < gather_ms)


def _traced_levels(monkeypatch, build, cfg, R=64, C=5):
    """The (L, slots) of every level ``_route_level`` saw while ``build``
    was traced, with the form it took."""
    seen = []
    rule = je.route_selects

    def spy(L, S):
        seen.append((int(L), int(S), rule(L, S)))
        return rule(L, S)
    monkeypatch.setattr(je, "route_selects", spy)
    bins = jnp.zeros((R, C), jnp.int32)
    stats = jnp.ones((R, 4), jnp.float32)
    leaf0 = jnp.zeros((R,), jnp.int32)
    jax.make_jaxpr(lambda b, s, l: build(
        b, s, l, jax.random.PRNGKey(0), jnp.zeros((C,), bool), cfg))(
            bins, stats, leaf0)
    monkeypatch.setattr(je, "route_selects", rule)
    return seen


@pytest.mark.parametrize("engine, D, B, adaptive, F, cap", [
    ("dense", 8, 255, False, 0, 0),
    ("dense", 5, 20, True, 1024, 0),
    ("frontier", 13, 255, False, 0, 4096),
])
def test_the_plan_counts_what_the_trace_does(monkeypatch, engine, D, B,
                                             adaptive, F, cap):
    cfg = dict(max_depth=D, nbins=B, k_cols=5, newton=True, min_rows=1.0,
               min_split_improvement=0.0, block_rows=64, bf16=False,
               max_live_leaves=cap, sibling=True, adaptive=adaptive,
               fine_nbins=F, hist_random=False, pallas=False,
               mm_route=False)
    build = je.build_tree_frontier if engine == "frontier" else \
        je.build_tree_traced
    seen = _traced_levels(monkeypatch, build, cfg)
    widths = je.frontier_plan(D, cap) if cap else [2 ** d for d in range(D)]
    if cap:
        # the frontier engine's levels from d0 on are one traced loop
        # body at the cap's width
        d0 = je.frontier_loop_start(D, cap, B, F, adaptive)
        assert 0 < d0 < D
        seen = seen[:d0] + seen[d0:] * (D - d0)
        widths = widths[:d0] + [cap] * (D - d0)
    assert len(seen) == D
    kw = dict(max_depth=D, nbins=B, kleaves=cap, adaptive=adaptive,
              fine_nbins=F, mm_route=False)
    assert je.route_plan(kw) == (D, sum(s for _, _, s in seen))
    assert [L for L, _, _ in seen] == widths


def test_a_depth_8_job_says_8_of_8_levels_select(cl, rng):
    from h2o_tpu.models.tree.gbm import GBM
    n = 800
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + 0.5 * rng.normal(size=n) > 0).astype(np.int32)
    fr = Frame([f"x{j}" for j in range(4)] + ["y"],
               [Vec(X[:, j]) for j in range(4)] +
               [Vec(y, T_CAT, domain=["no", "yes"])])
    TimeLine.clear()
    GBM(ntrees=2, max_depth=8, nbins=20, min_rows=1.0, seed=3,
        score_tree_interval=1).train(y="y", training_frame=fr)
    launches = [e for e in TimeLine.snapshot()
                if "dur_ns" in e and (e["kind"], e["what"]) ==
                ("train", "block.launch")]
    assert len(launches) == 2
    assert {(e["route_levels"], e["route_select_levels"])
            for e in launches} == {(8, 8)}
