"""Isolation Forest + Extended Isolation Forest — anomaly detection.

Reference:
- hex/tree/isofor/IsolationForest.java — trees isolate rows on a per-tree
  random sub-sample (``sample_size``, default 256, depth 8); each leaf's
  prediction is its DEPTH (IsolationForest.java:289 ``ln._pred = depths``);
  a row's raw score is the total path length over all trees, normalized
  against the min/max total path observed on the training frame
  (IsolationForestModel.java:162-168: ``(max - len) / (max - min)``); the
  prediction frame is ``[predict, mean_length]``.
- hex/tree/isoforextended/ExtendedIsolationForest.java — splits are random
  hyperplanes (``extension_level`` controls how many coordinates are
  non-zero); the anomaly score is the classic Liu formula
  ``2^(-E[h]/c(sample_size))`` with the unsuccessful-BST-search adjustment
  ``c(n)`` added at leaves (ExtendedIsolationForestModel.java:45-59).

TPU-native: each tree trains on a fixed-size gathered sample (S, C) — small
enough that per-level node min/max reductions are a single broadcast masked
reduce, no histograms needed.  The whole forest is one ``lax.scan`` over
per-tree RNG keys (same fused-XLA-loop design as jit_engine.py); scoring is
a fixed-depth vectorized heap descent over all rows (forest_score analog).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.frame import Frame, Vec
from h2o_tpu.models.metrics import ModelMetrics
from h2o_tpu.models.model import DataInfo, Model, ModelBuilder

EULER = 0.5772156649015329
INF = jnp.inf


def avg_path_length(n):
    """c(n): average unsuccessful-search path length of a BST of n nodes."""
    n = jnp.asarray(n, jnp.float32)
    h = jnp.log(jnp.maximum(n - 1.0, 1.0)) + EULER
    c = 2.0 * h - 2.0 * (n - 1.0) / jnp.maximum(n, 1.0)
    return jnp.where(n > 2.0, c, jnp.where(n == 2.0, 1.0, 0.0))


# ---------------------------------------------------------------------------
# axis-parallel Isolation Forest
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("S", "D", "nrows"))
def _build_if_trees(X, keys, S: int, D: int, nrows: int):
    """lax.scan over trees: per tree, sample S rows, grow a depth-D tree of
    uniform-random axis-parallel splits.  Returns (T, H) heap arrays."""
    H = 2 ** (D + 1) - 1
    C = X.shape[1]

    def one_tree(carry, key):
        k_samp, k_tree = jax.random.split(key)
        idx = jax.random.choice(k_samp, nrows, (S,), replace=S > nrows)
        Xs = X[idx]                                     # (S, C)
        split_col = jnp.full((H,), -1, jnp.int32)
        thresh = jnp.zeros((H,), jnp.float32)
        leaf = jnp.zeros((S,), jnp.int32)               # level-local index
        alive = jnp.ones((S,), bool)
        for d in range(D):
            L = 2 ** d
            off = L - 1
            k_tree, kc, kt = jax.random.split(k_tree, 3)
            hot = (leaf[:, None] == jnp.arange(L)[None, :]) & \
                alive[:, None]                          # (S, L)
            cnt = jnp.sum(hot, axis=0)
            xm = jnp.where(hot[:, :, None], Xs[:, None, :], jnp.nan)
            vmin = jnp.nanmin(jnp.where(jnp.isnan(xm), INF, xm), axis=0)
            vmax = jnp.nanmax(jnp.where(jnp.isnan(xm), -INF, xm), axis=0)
            valid = (vmax > vmin) & jnp.isfinite(vmin)  # (L, C)
            can = (cnt > 1) & jnp.any(valid, axis=1)
            r = jax.random.uniform(kc, (L, C))
            col = jnp.argmax(jnp.where(valid, r, -1.0), axis=1) \
                .astype(jnp.int32)
            li = jnp.arange(L)
            lo, hi = vmin[li, col], vmax[li, col]
            u = jax.random.uniform(kt, (L,))
            th = lo + u * (hi - lo)
            split_col = jax.lax.dynamic_update_slice(
                split_col, jnp.where(can, col, -1), (off,))
            thresh = jax.lax.dynamic_update_slice(
                thresh, jnp.nan_to_num(th), (off,))
            # route: x < thresh -> left child (NaN compares false -> right)
            xv = jnp.take_along_axis(
                Xs, jnp.clip(col[leaf], 0, C - 1)[:, None], axis=1)[:, 0]
            go_left = xv < th[leaf]
            nxt = 2 * leaf + jnp.where(go_left, 0, 1)
            splits = can[leaf]
            leaf = jnp.where(alive & splits, nxt, leaf)
            alive = alive & splits
        return carry, (split_col, thresh)

    _, (sc, th) = jax.lax.scan(one_tree, 0, keys)
    return sc, th


@functools.partial(jax.jit, static_argnames=("D",))
def _if_path_lengths(X, split_col, thresh, D: int):
    """(R,) total path length over all trees (each tree adds its leaf depth,
    the reference's PathTracker total)."""
    R, C = X.shape

    def one_tree(total, tree):
        sc, th = tree
        node = jnp.zeros((R,), jnp.int32)
        depth = jnp.zeros((R,), jnp.int32)
        for _ in range(D):
            c = sc[node]
            term = c < 0
            xv = jnp.take_along_axis(
                X, jnp.clip(c, 0, C - 1)[:, None], axis=1)[:, 0]
            go_left = xv < th[node]
            nxt = 2 * node + jnp.where(go_left, 1, 2)
            node = jnp.where(term, node, nxt)
            depth = depth + jnp.where(term, 0, 1)
        return total + depth, None

    total, _ = jax.lax.scan(one_tree, jnp.zeros((R,), jnp.int32),
                            (split_col, thresh))
    return total


class AnomalyModel(Model):
    """Shared anomaly-model surface: [score, mean_length] predictions."""

    supervised = False
    pred_names = ("predict", "mean_length")

    def predict(self, frame: Frame) -> Frame:
        raw = self.predict_raw(frame)
        n = frame.nrows
        return Frame(list(self.pred_names),
                     [Vec(raw[:, 0], nrows=n), Vec(raw[:, 1], nrows=n)])

    def model_metrics(self, frame: Frame):
        raw = np.asarray(self.predict_raw(frame))[: frame.nrows]
        return self._metrics_from(raw)

    @staticmethod
    def _metrics_from(raw: np.ndarray) -> ModelMetrics:
        return ModelMetrics("anomaly", dict(
            mean_score=float(raw[:, 0].mean()),
            mean_length=float(raw[:, 1].mean())))


class IsolationForestModel(AnomalyModel):
    algo = "isolationforest"


    def _total_path(self, frame: Frame):
        out = self.output
        X = self.scoring_matrix(frame)
        return _if_path_lengths(X, jnp.asarray(out["split_col"]),
                                jnp.asarray(out["thresh"]),
                                int(out["max_depth"]))

    def predict_raw(self, frame: Frame):
        out = self.output
        total = self._total_path(frame).astype(jnp.float32)
        lo, hi = float(out["min_path_length"]), float(out["max_path_length"])
        score = (hi - total) / (hi - lo) if hi > lo else \
            jnp.ones_like(total)
        mean_len = total / max(int(out["ntrees_actual"]), 1)
        return jnp.stack([score, mean_len], axis=1)


class IsolationForest(ModelBuilder):
    ENGINE_FIXED = {"mtries": (-1, -2), "contamination": (-1.0,)}

    algo = "isolationforest"
    model_cls = IsolationForestModel
    supervised = False

    def default_params(self) -> Dict:
        p = super().default_params()
        p.update(ntrees=50, max_depth=8, sample_size=256, sample_rate=-1.0,
                 mtries=-1, contamination=-1.0,
                 score_each_iteration=False, score_tree_interval=0,
                 stopping_rounds=0, stopping_metric="AUTO",
                 stopping_tolerance=0.01)
        return p

    def _fit(self, job, x, y, train: Frame, valid: Optional[Frame]):
        p = self.params
        di = DataInfo(train, x, None, mode="tree")
        X = train.as_matrix(di.x)
        D = int(p["max_depth"])
        T = int(p["ntrees"])
        rate = float(p.get("sample_rate") or -1.0)
        S = int(round(rate * train.nrows)) if rate > 0 else \
            int(p["sample_size"])
        S = max(2, min(S, train.nrows))
        keys = jax.random.split(self.rng_key(), T)
        job.update(0.1, f"growing {T} isolation trees (sample={S})")
        sc, th = _build_if_trees(X, keys, S, D, train.nrows)
        total = np.asarray(_if_path_lengths(X, sc, th, D))[: train.nrows]
        lo, hi = int(total.min()), int(total.max())
        out = dict(x=list(di.x), split_col=np.asarray(sc),
                   thresh=np.asarray(th), max_depth=D, ntrees_actual=T,
                   sample_size=S,
                   min_path_length=lo, max_path_length=hi,
                   domains={c: list(train.vec(c).domain)
                            for c in di.cat_names})
        model = self.model_cls(self.model_id, dict(p), out)
        # training metrics from the path lengths already in hand (no second
        # full-frame scoring pass)
        score = (hi - total) / (hi - lo) if hi > lo else \
            np.ones_like(total, np.float32)
        raw = np.stack([score, total / max(T, 1)], axis=1)
        model.output["training_metrics"] = AnomalyModel._metrics_from(raw)
        return model


# ---------------------------------------------------------------------------
# Extended Isolation Forest (random hyperplane splits)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("S", "D", "nrows", "ext"))
def _build_eif_trees(X, keys, S: int, D: int, nrows: int, ext: int):
    """Per tree: random-hyperplane splits (n·(x-p) <= 0 goes left), leaf
    value = depth + c(leaf_count).  Returns (T,H,C) normals/intercepts and
    (T,H) values / terminal flags."""
    H = 2 ** (D + 1) - 1
    C = X.shape[1]

    def one_tree(carry, key):
        k_samp, k_tree = jax.random.split(key)
        idx = jax.random.choice(k_samp, nrows, (S,), replace=S > nrows)
        Xs = X[idx]
        normals = jnp.zeros((H, C), jnp.float32)
        points = jnp.zeros((H, C), jnp.float32)
        value = jnp.zeros((H,), jnp.float32)
        counts = jnp.zeros((H,), jnp.int32)   # rows reaching the node
        is_split = jnp.zeros((H,), bool)
        leaf = jnp.zeros((S,), jnp.int32)
        alive = jnp.ones((S,), bool)
        for d in range(D):
            L = 2 ** d
            off = L - 1
            k_tree, kn, kz, kp = jax.random.split(k_tree, 4)
            hot = (leaf[:, None] == jnp.arange(L)[None, :]) & \
                alive[:, None]
            cnt = jnp.sum(hot, axis=0)
            xm = jnp.where(hot[:, :, None], Xs[:, None, :], jnp.nan)
            vmin = jnp.nanmin(jnp.where(jnp.isnan(xm), INF, xm), axis=0)
            vmax = jnp.nanmax(jnp.where(jnp.isnan(xm), -INF, xm), axis=0)
            span = jnp.where(jnp.isfinite(vmin), vmax - vmin, 0.0)
            can = (cnt > 1) & jnp.any(span > 0, axis=1)
            # normal vector with ext+1 non-zero coordinates (EIF paper)
            nvec = jax.random.normal(kn, (L, C))
            r = jax.random.uniform(kz, (L, C))
            keep_k = min(ext + 1, C)
            kth = jnp.sort(r, axis=1)[:, keep_k - 1][:, None]
            nvec = jnp.where(r <= kth, nvec, 0.0)
            pvec = vmin + jax.random.uniform(kp, (L, C)) * \
                jnp.maximum(span, 0.0)
            normals = jax.lax.dynamic_update_slice(normals, nvec, (off, 0))
            points = jax.lax.dynamic_update_slice(
                points, jnp.nan_to_num(pvec), (off, 0))
            value = jax.lax.dynamic_update_slice(
                value, d + avg_path_length(cnt), (off,))
            counts = jax.lax.dynamic_update_slice(
                counts, cnt.astype(jnp.int32), (off,))
            is_split = jax.lax.dynamic_update_slice(is_split, can, (off,))
            proj = jnp.sum((jnp.nan_to_num(Xs)[:, None, :] - pvec[None]) *
                           nvec[None], axis=2)           # (S, L)
            go_left = jnp.take_along_axis(proj, leaf[:, None],
                                          axis=1)[:, 0] <= 0
            nxt = 2 * leaf + jnp.where(go_left, 0, 1)
            splits = can[leaf]
            leaf = jnp.where(alive & splits, nxt, leaf)
            alive = alive & splits
        # last level: value = D + c(cnt)
        L = 2 ** D
        hot = (leaf[:, None] == jnp.arange(L)[None, :]) & alive[:, None]
        cnt = jnp.sum(hot, axis=0)
        value = jax.lax.dynamic_update_slice(
            value, D + avg_path_length(cnt), (L - 1,))
        counts = jax.lax.dynamic_update_slice(
            counts, cnt.astype(jnp.int32), (L - 1,))
        return carry, (normals, points, value, is_split, counts)

    _, trees = jax.lax.scan(one_tree, 0, keys)
    return trees


@functools.partial(jax.jit, static_argnames=("D",))
def _eif_mean_path(X, normals, points, value, is_split, D: int):
    R, C = X.shape
    Xz = jnp.nan_to_num(X)

    def one_tree(total, tree):
        nv, pv, vl, sp = tree
        node = jnp.zeros((R,), jnp.int32)
        for _ in range(D):
            term = ~sp[node]
            proj = jnp.sum((Xz - pv[node]) * nv[node], axis=1)
            nxt = 2 * node + jnp.where(proj <= 0, 1, 2)
            node = jnp.where(term, node, nxt)
        return total + vl[node], None

    total, _ = jax.lax.scan(one_tree, jnp.zeros((R,), jnp.float32),
                            (normals, points, value, is_split))
    return total / normals.shape[0]


class ExtendedIsolationForestModel(AnomalyModel):
    algo = "extendedisolationforest"
    pred_names = ("anomaly_score", "mean_length")

    def predict_raw(self, frame: Frame):
        out = self.output
        X = self.scoring_matrix(frame)
        mean_len = _eif_mean_path(
            X, jnp.asarray(out["normals"]), jnp.asarray(out["points"]),
            jnp.asarray(out["value"]), jnp.asarray(out["is_split"]),
            int(out["max_depth"]))
        cn = float(np.asarray(avg_path_length(out["sample_size"])))
        score = jnp.power(2.0, -mean_len / max(cn, 1e-12))
        return jnp.stack([score, mean_len], axis=1)


class ExtendedIsolationForest(ModelBuilder):
    algo = "extendedisolationforest"
    model_cls = ExtendedIsolationForestModel
    supervised = False

    def default_params(self) -> Dict:
        p = super().default_params()
        p.update(ntrees=100, sample_size=256, extension_level=0,
                 score_each_iteration=False, score_tree_interval=0)
        return p

    def _fit(self, job, x, y, train: Frame, valid: Optional[Frame]):
        p = self.params
        di = DataInfo(train, x, None, mode="tree")
        X = train.as_matrix(di.x)
        C = len(di.x)
        ext = int(p["extension_level"])
        if not (0 <= ext <= C - 1):
            raise ValueError(
                f"extension_level must be in [0, {C - 1}], got {ext}")
        S = max(2, min(int(p["sample_size"]), train.nrows))
        D = max(1, int(np.ceil(np.log2(S))))
        T = int(p["ntrees"])
        keys = jax.random.split(self.rng_key(), T)
        job.update(0.1, f"growing {T} extended isolation trees")
        normals, points, value, is_split, counts = _build_eif_trees(
            X, keys, S, D, train.nrows, ext)
        out = dict(x=list(di.x), normals=np.asarray(normals),
                   points=np.asarray(points), value=np.asarray(value),
                   is_split=np.asarray(is_split),
                   counts=np.asarray(counts), max_depth=D,
                   ntrees_actual=T, sample_size=S,
                   domains={c: list(train.vec(c).domain)
                            for c in di.cat_names})
        model = self.model_cls(self.model_id, dict(p), out)
        model.output["training_metrics"] = model.model_metrics(train)
        return model
