"""Model metrics — the ModelMetrics* hierarchy, TPU-native.

Reference: 30+ ModelMetrics classes plus the streaming 400-bin AUC builder
(h2o-core hex/ModelMetrics*.java, hex/AUC2.java:24,362 — AUC is computed from
a fixed-size histogram of scores so it reduces across nodes in O(bins), not
O(rows)).

Here each metric set is ONE fused jit reduction over the row-sharded
prediction/actual arrays; the score histogram (1024 bins) gives AUC, PR-AUC,
Gini, and the threshold-indexed confusion counts exactly like AUC2's bin
sweep.  All reductions ride ICI psum via the arrays' sharding.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

_NBINS_AUC = 1024
EPS = 1e-15
_HIST_BLOCK = 8192      # rows a contraction, as ops/histogram.py's block
_LO_BITS = 5            # a bin is (hi, lo): hi = b >> 5, lo = b & 31


def _score_histogram(b, wy, wn, nbins: int):
    """Weighted score histograms ``pos[k] = sum(wy[b == k])`` and ``neg``
    likewise, as one (nhi, 2 * nlo) table (``pos`` its first ``nlo``
    columns, ``neg`` the rest, row-major), by one two-level one-hot
    contraction a row block, not two scatter-adds (a scatter-add of 5.25M
    rows into 1,024 slots is serialised work on the chip: 46 ms a table,
    PERF.md §6).

    ``onehot(hi)`` (R, nhi) meets ``[onehot(lo) * wy, onehot(lo) * wn]``
    (R, 2 * nlo) over the rows.  The weight side stays float32 (HIGHEST;
    the one-hot side is exact in any dtype), so integer weights give the
    scatter's tables bit for bit.
    One contraction a block of ``_HIST_BLOCK`` rows: no whole-frame
    one-hot lands in HBM; the per-block partials are summed in float32."""
    nlo = 1 << _LO_BITS
    nhi = -(-nbins // nlo)
    R = b.shape[0]
    blk = max(min(_HIST_BLOCK, R), 1)
    nblk = R // blk

    def part(bb, yy, nn):
        hi = (bb >> _LO_BITS)[:, None] == jnp.arange(nhi)[None, :]
        lo = ((bb & (nlo - 1))[:, None] ==
              jnp.arange(nlo)[None, :]).astype(jnp.float32)
        rhs = jnp.concatenate([lo * yy[:, None], lo * nn[:, None]], axis=1)
        return jax.lax.dot_general(
            hi.astype(jnp.float32), rhs,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)        # (nhi, 2 * nlo)

    acc, _ = jax.lax.scan(
        lambda acc, xs: (acc + part(*xs), None),
        jnp.zeros((nhi, 2 * nlo), jnp.float32),
        tuple(v[: nblk * blk].reshape(nblk, blk) for v in (b, wy, wn)))
    rem = R - nblk * blk
    if rem:
        # the rows left over padded to a whole block at weight 0: one
        # contraction shape, and no copy of the whole frame (PERF.md §6,
        # PR 34: a contraction cut at its own last-block shape once came
        # back all zero on the chip)
        acc = acc + part(*(jnp.pad(v[nblk * blk:], (0, blk - rem))
                           for v in (b, wy, wn)))
    return acc


_SUM_BLOCK = 1024      # rows of one partial sum (one (8, 128) tile)


def _sum_rows(x):
    """Sum of an (R,) float32 vector as partial sums of ``_SUM_BLOCK``
    rows, then the sum of the partials.  One reduction over millions of
    rows adds each lane's rows one after another on the chip, and a term
    many rows share (a forest of pure leaves: ``-log(EPS)`` on every row
    it gets wrong) rounds the same way at every add: the sum drifts by
    1e-5 to 1e-4 of itself at 5M rows.  The barrier keeps the compiler
    from folding the two reductions back into one."""
    pad = (-x.shape[0]) % _SUM_BLOCK
    parts = jnp.sum(jnp.pad(x, (0, pad)).reshape(-1, _SUM_BLOCK), axis=1)
    return jnp.sum(jax.lax.optimization_barrier(parts))


@functools.partial(jax.jit, static_argnames=("nbins", "mesh"))
@jax.named_scope("h2o.score.metrics")
def _binomial_kernel(p, y, w, valid, *, mesh, nbins: int = _NBINS_AUC):
    """p: P(class 1); y: {0,1}; returns scalars + per-bin pos/neg counts.

    Computed where the rows live: each shard of ``mesh``'s data axis
    scans its own rows (its score table and its partial sums), then one
    ``hpsum`` of the (nhi, 2 * nlo) table and one of the four sums; no
    row-length operand crosses between shards.  One device is the same
    program over one shard.  The row count is a multiple of the shard
    count (``binomial_kernel`` pads)."""
    from h2o_tpu.core.cloud import cloud, hpsum, shard_map_compat
    dp = cloud().data_pspec

    @functools.partial(shard_map_compat, mesh=mesh,
                       in_specs=(dp(),) * 4, out_specs=PartitionSpec(),
                       check_vma=False)
    def run(p, y, w, valid):
        w = jnp.where(valid, w, 0.0)
        y = jnp.where(valid, y, 0.0)
        p = jnp.where(valid, p, 0.5)   # NaN-proof padded rows (0*NaN)
        # where-form, not y*log(p)+(1-y)*log(1-p): p can round to
        # exactly 0/1 in f32 and 0*log(0) would poison the sum with NaN
        sums = jnp.stack([
            _sum_rows(-w * jnp.where(y > 0.5,
                                     jnp.log(jnp.maximum(p, EPS)),
                                     jnp.log(jnp.maximum(1.0 - p, EPS)))),
            _sum_rows(w * (y - p) ** 2), jnp.sum(w), jnp.sum(w * y)])
        b = jnp.clip((p * nbins).astype(jnp.int32), 0, nbins - 1)
        table = _score_histogram(b, w * y, w * (1 - y), nbins)
        return hpsum(table, "score.hist"), hpsum(sums, "score.sums")

    table, sums = run(p, y, w, valid)
    nlo = 1 << _LO_BITS
    pos = table[:, :nlo].reshape(-1)[:nbins]
    neg = table[:, nlo:].reshape(-1)[:nbins]
    wsum = jnp.maximum(sums[2], EPS)
    return dict(logloss=sums[0] / wsum, mse=sums[1] / wsum, pos=pos,
                neg=neg, wsum=wsum, ymean=sums[3] / wsum)


def binomial_kernel(p, y, w, valid, nbins: int = _NBINS_AUC):
    """``_binomial_kernel`` over the cloud's mesh, the rows padded at
    ``valid`` False by ``pad_rows``."""
    from h2o_tpu.core.cloud import cloud, pad_rows
    p, y, w = (pad_rows(jnp.asarray(v)) for v in (p, y, w))
    return _binomial_kernel(p, y, w, pad_rows(jnp.asarray(valid), False),
                            nbins=nbins, mesh=cloud().mesh)


def _auc_from_hist(pos: np.ndarray, neg: np.ndarray) -> Dict[str, float]:
    """Exact bin-sweep AUC/PR-AUC/max-F1 from score histograms (AUC2 analog:
    thresholds descend bin edges; trapezoids between)."""
    # sweep thresholds from high to low: cumulative TP/FP
    tp = np.cumsum(pos[::-1])
    fp = np.cumsum(neg[::-1])
    P, N = max(tp[-1], EPS), max(fp[-1], EPS)
    tpr = np.concatenate([[0.0], tp / P])
    fpr = np.concatenate([[0.0], fp / N])
    auc = float(np.trapezoid(tpr, fpr))
    prec = tp / np.maximum(tp + fp, EPS)
    rec = tp / P
    # PR-AUC via step interpolation (reference pr_auc)
    pr_auc = float(np.sum(np.diff(np.concatenate([[0.0], rec])) * prec))
    f1 = 2 * prec * rec / np.maximum(prec + rec, EPS)
    k = int(np.argmax(f1))
    nb = len(pos)
    thr = 1.0 - (k + 1) / nb  # threshold under the kth-from-top bin
    cm = dict(tp=float(tp[k]), fp=float(fp[k]),
              fn=float(P - tp[k]), tn=float(N - fp[k]))
    return dict(AUC=auc, pr_auc=pr_auc, gini=2 * auc - 1,
                max_f1=float(f1[k]), max_f1_threshold=thr, cm=cm)


@jax.jit
@jax.named_scope("h2o.score.metrics")
def _regression_kernel(pred, y, w, valid, dev):
    w = jnp.where(valid, w, 0.0)
    # NaN-proof the payloads too: invalid rows carry NaN and 0*NaN = NaN
    y = jnp.where(valid, y, 0.0)
    pred = jnp.where(valid, pred, 0.0)
    wsum = jnp.maximum(jnp.sum(w), EPS)
    err = y - pred
    mse = jnp.sum(w * err ** 2) / wsum
    mae = jnp.sum(w * jnp.abs(err)) / wsum
    ymean = jnp.sum(w * y) / wsum
    sstot = jnp.sum(w * (y - ymean) ** 2) / wsum
    ok_log = (y > -1) & (pred > -1)
    rmsle2 = jnp.sum(jnp.where(ok_log, w, 0.0) *
                     (jnp.log1p(jnp.maximum(y, -1 + EPS)) -
                      jnp.log1p(jnp.maximum(pred, -1 + EPS))) ** 2)
    rmsle_ok = jnp.all(jnp.where(valid, ok_log, True))
    mean_dev = jnp.sum(jnp.where(valid, dev, 0.0)) / wsum
    return dict(mse=mse, mae=mae, r2=1 - mse / jnp.maximum(sstot, EPS),
                rmsle2=rmsle2 / wsum, rmsle_ok=rmsle_ok,
                mean_residual_deviance=mean_dev, wsum=wsum)


@functools.partial(jax.jit, static_argnames=("nclass",))
@jax.named_scope("h2o.score.metrics")
def _multinomial_kernel(probs, y, w, valid, nclass: int):
    """probs: (rows, K); y: int class; confusion + logloss + hit ratios."""
    w = jnp.where(valid, w, 0.0)
    y = jnp.where(valid, y, 0.0)
    probs = jnp.where(valid[:, None], probs, 1.0 / nclass)
    wsum = jnp.maximum(jnp.sum(w), EPS)
    yi = jnp.clip(y.astype(jnp.int32), 0, nclass - 1)
    py = jnp.take_along_axis(probs, yi[:, None], axis=1)[:, 0]
    logloss = jnp.sum(-w * jnp.log(jnp.clip(py, EPS, 1.0))) / wsum
    pred = jnp.argmax(probs, axis=1).astype(jnp.int32)
    err = jnp.sum(w * (pred != yi)) / wsum
    cm = jnp.zeros((nclass, nclass), jnp.float32).at[yi, pred].add(w)
    # hit ratios: rank of true class (top-k accuracy, k=1..min(10,K))
    rank = jnp.sum(probs > py[:, None], axis=1)
    ks = min(10, nclass)
    hits = jnp.stack([jnp.sum(w * (rank <= k)) / wsum
                      for k in range(ks)])
    mse = jnp.sum(w * (1.0 - py) ** 2) / wsum
    return dict(logloss=logloss, err=err, cm=cm, hit_ratios=hits, mse=mse,
                wsum=wsum)


class ModelMetrics:
    """Host-side metrics bundle; shaped for the REST ModelMetrics schemas."""

    def __init__(self, kind: str, data: Dict):
        self.kind = kind  # regression | binomial | multinomial | clustering
        self.data = data

    def __getitem__(self, k):
        return self.data[k]

    def get(self, k, default=None):
        return self.data.get(k, default)

    def __repr__(self):
        keys = ("mse rmse mae rmsle r2 mean_residual_deviance logloss AUC "
                "pr_auc gini err tot_withinss").split()
        parts = [f"{k}={self.data[k]:.5g}" for k in keys
                 if isinstance(self.data.get(k), (int, float))]
        return f"<ModelMetrics{self.kind.capitalize()} {' '.join(parts)}>"

    def to_dict(self) -> Dict:
        out = {"model_category": self.kind.capitalize()}
        for k, v in self.data.items():
            out[k] = v.tolist() if isinstance(v, np.ndarray) else v
        return out


def regression_metrics(pred, y, w=None, valid=None, distribution=None,
                       nrows: Optional[int] = None) -> ModelMetrics:
    pred = jnp.asarray(pred)
    y = jnp.asarray(y)
    if valid is None:
        valid = (jnp.arange(pred.shape[0]) < nrows) if nrows is not None \
            else jnp.ones(pred.shape, bool)
    valid = valid & ~jnp.isnan(y) & ~jnp.isnan(pred)
    w = jnp.ones_like(pred) if w is None else w
    if distribution is not None:
        dev = distribution.deviance(w, y, distribution.link_fn(
            jnp.maximum(pred, EPS)) if distribution.link == "log" else pred)
    else:
        dev = w * (y - pred) ** 2
    r = jax.tree.map(np.asarray, _regression_kernel(pred, y, w, valid, dev))
    data = dict(mse=float(r["mse"]), rmse=float(np.sqrt(r["mse"])),
                mae=float(r["mae"]), r2=float(r["r2"]),
                mean_residual_deviance=float(r["mean_residual_deviance"]),
                nobs=float(r["wsum"]))
    data["rmsle"] = float(np.sqrt(r["rmsle2"])) if bool(r["rmsle_ok"]) \
        else float("nan")
    return ModelMetrics("regression", data)


def twodim_json(name, col_header, col_types, rows, description=""):
    """TwoDimTableV3 wire JSON (h2o-py/h2o/two_dim_table.py parses
    columns[].name/type + column-major data)."""
    ncol = len(col_header)
    data = [[r[j] for r in rows] for j in range(ncol)]
    return {
        "__meta": {"schema_version": 3, "schema_name": "TwoDimTableV3",
                   "schema_type": "TwoDimTable"},
        "name": name, "description": description,
        "columns": [{"__meta": {"schema_version": -1,
                                "schema_name": "ColumnSpecsBase",
                                "schema_type": "Iced"},
                     "name": n, "type": t, "format": "%s", "description": n}
                    for n, t in zip(col_header, col_types)],
        "rowcount": len(rows),
        "data": data,
    }


# AUC2.ThresholdCriterion.VALUES order (hex/AUC2.java:43-95) — the client
# indexes thresholds_and_metric_scores rows positionally (row[11]=tns ..
# row[14]=tps, h2o-py/h2o/model/metrics/binomial.py:783-786)
_THRESHOLD_CRITERIA = (
    "f1", "f2", "f0point5", "accuracy", "precision", "recall",
    "specificity", "absolute_mcc", "min_per_class_accuracy",
    "mean_per_class_accuracy", "tns", "fns", "fps", "tps",
    "tnr", "fnr", "fpr", "tpr")


def _threshold_tables(pos: np.ndarray, neg: np.ndarray):
    """thresholds_and_metric_scores + max_criteria_and_metric_scores from
    the AUC score histograms (ModelMetricsBinomialV3.java:70-120)."""
    nb = len(pos)
    pos_d, neg_d = pos[::-1], neg[::-1]          # descending thresholds
    keep = (pos_d + neg_d) > 0                   # real thresholds only
    tp = np.cumsum(pos_d)[keep]
    fp = np.cumsum(neg_d)[keep]
    ths = (1.0 - (np.arange(nb) + 1.0) / nb)[keep]
    n = len(tp)
    if n == 0:
        return None, None
    P = max(tp[-1], EPS)
    N = max(fp[-1], EPS)
    fn, tn = P - tp, N - fp
    with np.errstate(divide="ignore", invalid="ignore"):
        prec = tp / np.maximum(tp + fp, EPS)
        tpr = tp / P
        tnr = tn / N
        vals = {
            "f1": 2 * prec * tpr / np.maximum(prec + tpr, EPS),
            "f2": 5 * prec * tpr / np.maximum(4 * prec + tpr, EPS),
            "f0point5": 1.25 * prec * tpr / np.maximum(
                0.25 * prec + tpr, EPS),
            "accuracy": (tp + tn) / (P + N),
            "precision": prec, "recall": tpr, "specificity": tnr,
            "absolute_mcc": np.abs(
                (tp * tn - fp * fn) / np.sqrt(np.maximum(
                    (tp + fp) * (tp + fn) * (tn + fp) * (tn + fn), EPS))),
            "min_per_class_accuracy": np.minimum(tpr, tnr),
            "mean_per_class_accuracy": 0.5 * (tpr + tnr),
            "tns": tn, "fns": fn, "fps": fp, "tps": tp,
            "tnr": tnr, "fnr": fn / P, "fpr": fp / N, "tpr": tpr,
        }
    int_crits = {"tns", "fns", "fps", "tps"}
    rows = []
    for i in range(n):
        row = [float(ths[i])]
        for c in _THRESHOLD_CRITERIA:
            v = vals[c][i]
            row.append(int(v) if c in int_crits else float(v))
        row.append(i)
        rows.append(row)
    thresh_tbl = twodim_json(
        "Metrics for Thresholds",
        ["threshold"] + list(_THRESHOLD_CRITERIA) + ["idx"],
        ["double"] + ["long" if c in int_crits else "double"
                      for c in _THRESHOLD_CRITERIA] + ["int"],
        rows, "Binomial metrics as a function of classification thresholds")
    max_rows = []
    for c in _THRESHOLD_CRITERIA:
        k = int(np.argmax(vals[c]))
        max_rows.append([f"max {c}", float(ths[k]), float(vals[c][k]), k])
    max_tbl = twodim_json(
        "Maximum Metrics", ["metric", "threshold", "value", "idx"],
        ["string", "double", "double", "long"], max_rows,
        "Maximum metrics at their respective thresholds")
    return thresh_tbl, max_tbl


def binomial_metrics(p1, y, w=None, valid=None,
                     domain=None, nrows: Optional[int] = None) -> ModelMetrics:
    p1 = jnp.asarray(p1)
    y = jnp.asarray(y, jnp.float32)
    if valid is None:
        valid = (jnp.arange(p1.shape[0]) < nrows) if nrows is not None \
            else jnp.ones(p1.shape, bool)
    valid = valid & ~jnp.isnan(y)
    w = jnp.ones_like(p1) if w is None else w
    r = jax.tree.map(np.asarray, binomial_kernel(p1, y, w, valid))
    sweep = _auc_from_hist(r["pos"], r["neg"])
    data = dict(mse=float(r["mse"]), rmse=float(np.sqrt(r["mse"])),
                logloss=float(r["logloss"]), nobs=float(r["wsum"]),
                mean_per_class_error=float(
                    0.5 * (sweep["cm"]["fn"] / max(sweep["cm"]["fn"] +
                                                   sweep["cm"]["tp"], EPS) +
                           sweep["cm"]["fp"] / max(sweep["cm"]["fp"] +
                                                   sweep["cm"]["tn"], EPS))),
                domain=list(domain) if domain else ["0", "1"], **sweep)
    thresh_tbl, max_tbl = _threshold_tables(r["pos"], r["neg"])
    data["thresholds_and_metric_scores"] = thresh_tbl
    data["max_criteria_and_metric_scores"] = max_tbl
    return ModelMetrics("binomial", data)


def multinomial_metrics(probs, y, w=None, valid=None, domain=None,
                        nrows: Optional[int] = None) -> ModelMetrics:
    probs = jnp.asarray(probs)
    y = jnp.asarray(y)
    if valid is None:
        valid = (jnp.arange(probs.shape[0]) < nrows) if nrows is not None \
            else jnp.ones(probs.shape[:1], bool)
    valid = valid & ~jnp.isnan(y)
    w = jnp.ones(probs.shape[:1]) if w is None else w
    K = probs.shape[1]
    r = jax.tree.map(np.asarray,
                     _multinomial_kernel(probs, y, w, valid, K))
    cmat = r["cm"]
    row_tot = cmat.sum(axis=1)
    per_class_err = np.where(row_tot > 0,
                             1.0 - np.diagonal(cmat) /
                             np.maximum(row_tot, 1e-12), 0.0)
    data = dict(logloss=float(r["logloss"]), err=float(r["err"]),
                mse=float(r["mse"]), rmse=float(np.sqrt(r["mse"])),
                mean_per_class_error=float(per_class_err.mean()),
                cm=r["cm"], hit_ratios=r["hit_ratios"].tolist(),
                nobs=float(r["wsum"]),
                domain=list(domain) if domain else
                [str(i) for i in range(K)])
    return ModelMetrics("multinomial", data)
