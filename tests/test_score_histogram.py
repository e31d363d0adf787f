"""The binomial metric kernel's score histogram (PR 39): one two-level
one-hot contraction a row block (``metrics._score_histogram``) against the
two scatter-adds it replaced, kept here as the oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from h2o_tpu.models import metrics as mm

NB = mm._NBINS_AUC
BLK = mm._HIST_BLOCK


def _scatter_oracle(p, y, w, valid, nbins=NB):
    """The parent's histogram, written plainly: two scatter-adds."""
    w = jnp.where(valid, w, 0.0)
    y = jnp.where(valid, y, 0.0)
    p = jnp.where(valid, p, 0.5)
    b = jnp.clip((p * nbins).astype(jnp.int32), 0, nbins - 1)
    pos = jnp.zeros((nbins,), jnp.float32).at[b].add(w * y)
    neg = jnp.zeros((nbins,), jnp.float32).at[b].add(w * (1 - y))
    return np.asarray(pos), np.asarray(neg)


def _frame(rows, weights="unit", seed=0, invalid=0, edges=False):
    rng = np.random.default_rng(seed)
    p = rng.random(rows, dtype=np.float32)
    if edges:
        special = np.concatenate([
            [0.0, 1.0, 1 - 2.0 ** -24],
            np.arange(NB + 1) / NB]).astype(np.float32)  # exact bin edges
        p[:len(special)] = special
    y = (rng.random(rows) < p).astype(np.float32)
    w = {"unit": np.ones(rows, np.float32),
         "fold01": (np.arange(rows) % 5 != 3).astype(np.float32),
         "real": rng.random(rows, dtype=np.float32) * 3}[weights]
    valid = np.ones(rows, bool)
    if invalid:
        # padded rows as a frame carries them: NaN payloads, not valid
        valid[-invalid:] = False
        p[-invalid:] = np.nan
        y[-invalid:] = np.nan
    return p, y, w, valid


def _kernel_tables(p, y, w, valid):
    r = mm.binomial_kernel(p, y, w, valid)
    return np.asarray(r["pos"]), np.asarray(r["neg"])


def _bit_equal(rows, **kw):
    p, y, w, valid = _frame(rows, **kw)
    pos, neg = _kernel_tables(p, y, w, valid)
    opos, oneg = _scatter_oracle(p, y, w, valid)
    assert pos.shape == neg.shape == (NB,)
    np.testing.assert_array_equal(pos, opos)
    np.testing.assert_array_equal(neg, oneg)
    return opos, oneg


def _real_weights():
    p, y, w, valid = _frame(3 * BLK + 501, weights="real", invalid=40)
    pos, neg = _kernel_tables(p, y, w, valid)
    for got, want in zip((pos, neg), _scatter_oracle(p, y, w, valid)):
        # float32 summation order, nothing more
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * want.max())


def _edges():
    opos, oneg = _bit_equal(2 * NB + 3, edges=True)
    # p = k / 1024 lands in bin k; p = 1 and 1 - 2**-24 in the last
    assert opos.sum() + oneg.sum() == 2 * NB + 3


def _metrics_equal(weights):
    p, y, w, valid = _frame(2 * BLK + 77, weights=weights, seed=3,
                            invalid=13)
    m = mm.binomial_metrics(p, y, w=w, valid=valid)
    opos, oneg = _scatter_oracle(p, y, w, valid)
    sweep = mm._auc_from_hist(opos, oneg)
    for k in ("AUC", "pr_auc", "gini", "max_f1", "max_f1_threshold", "cm"):
        assert m[k] == sweep[k], k
    thresh, maxc = mm._threshold_tables(opos, oneg)
    assert m["thresholds_and_metric_scores"] == thresh
    assert m["max_criteria_and_metric_scores"] == maxc


def _no_scatter():
    from h2o_tpu.core.cloud import cloud
    p = jnp.zeros((3 * BLK + 8 * 5,))
    text = mm._binomial_kernel.lower(p, p, p, p > 0,
                                     mesh=cloud().mesh).as_text()
    assert "scatter" not in text


CASES = {
    # (i) bit-equal on integer weights, padded invalid rows, bin edges
    "unit": lambda: _bit_equal(BLK, invalid=17),
    "fold01": lambda: _bit_equal(BLK, weights="fold01", invalid=17),
    "edges": _edges,
    "real_weights": _real_weights,
    # (ii) row counts off the block: under one block, a block and a rest
    "rows_under_block": lambda: _bit_equal(1001, weights="fold01"),
    "rows_off_block": lambda: _bit_equal(2 * BLK + 1, invalid=1),
    "rows_one": lambda: _bit_equal(1),
    "rows_zero": lambda: _bit_equal(0),
    # (iii) AUC, PR-AUC, max-F1 and both threshold tables equal
    "metrics_unit": lambda: _metrics_equal("unit"),
    "metrics_fold01": lambda: _metrics_equal("fold01"),
    # (iv) the lowered program holds no scatter
    "no_scatter": _no_scatter,
}


@pytest.mark.parametrize("case", list(CASES))
def test_score_histogram_matches_the_scatter(case):
    CASES[case]()


@pytest.mark.parametrize("rows", [1, 1023, 1024, 70001])
def test_row_sums_in_partials_equal_the_exact_sum(rows):
    """``_sum_rows``: partial sums of ``_SUM_BLOCK`` rows, the rows left
    over padded with zeros, then the sum of the partials; terms that many
    rows share (a forest of pure leaves) are what a single chained
    reduction on the chip rounds alike at every add."""
    rng = np.random.default_rng(rows)
    x = np.where(rng.random(rows) < 0.3, np.float32(34.538776),
                 rng.random(rows, dtype=np.float32)).astype(np.float32)
    got = float(mm._sum_rows(jnp.asarray(x)))
    want = float(np.sum(x.astype(np.float64)))
    assert got == pytest.approx(want, rel=2e-7, abs=1e-6)
