"""XGBoost — parameter-compatible histogram gradient boosting.

Reference (h2o-extensions/xgboost, 17.1k Java glue + native libxgboost):
H2O frames convert to DMatrix, one native updater thread per node drives
``tree_method=hist/gpu_hist/approx`` boosters with Rabit allreduce
(RabitTrackerH2O.java:14).  SURVEY §2.3 marks this the ``gpu_hist`` → TPU
path: the same histogram engine as GBM, XGBoost-compatible params.

TPU-native: this builder IS the fused-XLA histogram engine (jit_engine.py)
— the Pallas/MXU histogram replaces gpu_hist's shared-memory bins and the
row-shard psum replaces Rabit's ring allreduce.  XGBoost naming is mapped
onto the engine (eta→learn_rate, subsample→sample_rate, colsample_bytree→
col_sample_rate_per_tree, min_child_weight→min_rows, max_bins→nbins);
``reg_lambda`` enters the Newton leaf denominator; ``min_split_loss``
(gamma) maps to the split-improvement threshold.

Booster coverage:
- ``gbtree``   — the fused engine (default);
- ``dart``     — host-driven per-tree loop with tree dropout
  (rate_drop/skip_drop; "tree" sample_type, "tree" normalize_type) —
  inherently sequential, so each tree is one engine dispatch;
- ``gblinear`` — delegates to the GLM elastic-net path (reg_alpha/
  reg_lambda map onto alpha/lambda), scored as a linear model.
``monotone_constraints`` flow into the split finder + child-value
clamping (shared_tree.find_splits / jit_engine monotone bounds).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from h2o_tpu.core.frame import Frame
from h2o_tpu.models.tree.gbm import GBM, GBMModel


class XGBoostModel(GBMModel):
    algo = "xgboost"


_PARAM_MAP = {
    "eta": "learn_rate",
    "learn_rate": "learn_rate",
    "subsample": "sample_rate",
    "sample_rate": "sample_rate",
    "colsample_bytree": "col_sample_rate_per_tree",
    "col_sample_rate_per_tree": "col_sample_rate_per_tree",
    "colsample_bylevel": "col_sample_rate",
    "col_sample_rate": "col_sample_rate",
    "min_child_weight": "min_rows",
    "min_rows": "min_rows",
    "max_bins": "nbins",
    "min_split_loss": "min_split_improvement",
    "gamma": "min_split_improvement",
}

_XGB_DEFAULTS = dict(
    ntrees=50, max_depth=6, eta=0.3, subsample=1.0, colsample_bytree=1.0,
    colsample_bylevel=1.0, min_child_weight=1.0, max_bins=256,
    reg_lambda=1.0, reg_alpha=0.0, min_split_loss=0.0,
    tree_method="hist", booster="gbtree", grow_policy="depthwise",
    backend="auto", force_newton=True,
    rate_drop=0.0, skip_drop=0.0, sample_type="uniform",
    normalize_type="tree")


class XGBoostLinearModel(XGBoostModel):
    """booster=gblinear result: scored via the GLM linear predictor."""

    def predict_raw(self, frame: Frame):
        from h2o_tpu.models.glm import GLMModel
        return GLMModel.predict_raw(self, frame)

    def predict_raw_array(self, X):
        from h2o_tpu.models.glm import GLMModel
        return GLMModel.predict_raw_array(self, X)

    def _raw_from_expanded(self, X):
        # the borrowed GLM scoring paths above resolve this on self
        from h2o_tpu.models.glm import GLMModel
        return GLMModel._raw_from_expanded(self, X)

    def model_metrics(self, frame: Frame = None):
        from h2o_tpu.models.glm import GLMModel
        return GLMModel.model_metrics(self, frame)


class XGBoost(GBM):
    algo = "xgboost"
    model_cls = XGBoostModel

    ENGINE_FIXED = {
        **GBM.ENGINE_FIXED,
        "tree_method": ("auto", "hist"),  # this engine IS hist
        "grow_policy": ("depthwise",),
        "booster": ("gbtree", "dart", "gblinear"),
        "sample_type": ("uniform",),
        "normalize_type": ("tree",),
    }

    def default_params(self) -> Dict:
        p = super().default_params()
        p.update(_XGB_DEFAULTS)
        # GBM defaults that differ under XGBoost naming
        p["learn_rate"] = 0.3
        p["min_rows"] = 1.0
        p["nbins"] = 256
        return p

    def __init__(self, **params):
        super().__init__(**params)
        # translate xgboost names onto the engine's (explicit user values
        # win over both defaults)
        for xgb_name, engine_name in _PARAM_MAP.items():
            if xgb_name in params and xgb_name != engine_name:
                self.params[engine_name] = params[xgb_name]
        booster = self.params.get("booster", "gbtree")
        if booster != "gblinear" and float(
                self.params.get("reg_alpha") or 0.0) != 0.0:
            raise ValueError(
                "reg_alpha (L1 leaf regularization) is only honored by "
                "booster='gblinear' on this engine; refusing to train "
                "with a silently-ignored setting")

    def _cv_shared(self, job, x, y, train: Frame):
        # gblinear and dart drive fits of their own: the generic path
        if self.params.get("booster", "gbtree") != "gbtree":
            return None
        return super()._cv_shared(job, x, y, train)

    def _fit(self, job, x, y, train: Frame, valid: Optional[Frame],
             cv=None):
        booster = self.params.get("booster", "gbtree")
        if booster == "gblinear":
            return self._fit_gblinear(job, x, y, train, valid)
        if booster == "dart":
            return self._fit_dart(job, x, y, train, valid)
        # gbtree: reg_lambda flows into the Newton denominator via the
        # engine's reg_lambda kwarg (jit_engine._node_val)
        return super()._fit(job, x, y, train, valid, cv=cv)

    # -- booster=gblinear --------------------------------------------------

    def _fit_gblinear(self, job, x, y, train, valid):
        """XGBoost gblinear == elastic-net linear model; delegate to the
        GLM coordinate-descent path (reg_alpha -> L1, reg_lambda -> L2;
        alpha = a/(a+l), lambda = (a+l)/n in GLM's per-row convention)."""
        from h2o_tpu.models.glm import GLM
        a = float(self.params.get("reg_alpha") or 0.0)
        l2 = float(self.params.get("reg_lambda") or 0.0)
        tot = a + l2
        fam = "binomial" if train.vec(y).is_categorical and \
            len(train.vec(y).domain or []) == 2 else \
            ("multinomial" if train.vec(y).is_categorical else "gaussian")
        g = GLM(family=fam,
                alpha=(a / tot) if tot > 0 else 0.0,
                lambda_=tot / max(train.nrows, 1),
                seed=self.params.get("seed", -1))
        g.model_id = self.model_id
        g.model_cls = XGBoostLinearModel
        m = g._fit(job, x, y, train, valid)
        m.params.update(booster="gblinear",
                        reg_alpha=a, reg_lambda=l2)
        return m

    # -- booster=dart ------------------------------------------------------

    def _fit_dart(self, job, x, y, train, valid):
        """DART (Dropouts meet Multiple Additive Regression Trees): each
        iteration drops a random subset of prior trees, fits the new tree
        against the remaining ensemble, and rescales (normalize_type=
        "tree": new tree 1/(k+1), dropped trees k/(k+1)).

        Sequential by construction, so each tree is one GBM._fit call with
        the running (minus-dropped) ensemble injected through the engine's
        existing offset-column path — F0 = f0 + offset is exactly the DART
        "score without the dropped trees" state.  f0 depends only on
        (y, w, distribution) so every per-tree model shares it and the
        final concatenated forest scores as f0 + sum of rescaled trees.
        """
        import jax.numpy as jnp
        from h2o_tpu.core.frame import Frame as _Frame, Vec as _Vec
        from h2o_tpu.models.tree import shared_tree as st

        yv = train.vec(y)
        if yv.is_categorical and len(yv.domain or []) > 2:
            raise ValueError(
                "booster='dart' supports regression/binomial on this "
                "engine (multinomial K>1 has no offset path); use "
                "booster='gbtree' for multinomial")
        if self.params.get("offset_column"):
            raise ValueError("booster='dart' uses the offset path "
                             "internally; offset_column is unsupported")
        if self.params.get("checkpoint"):
            raise ValueError("booster='dart' does not support checkpoint "
                             "resume (per-tree weights are rescaled "
                             "during training)")
        p_all = dict(self.params)
        ntrees = int(p_all["ntrees"])
        rate_drop = float(p_all.get("rate_drop") or 0.0)
        skip_drop = float(p_all.get("skip_drop") or 0.0)
        seed = int(p_all.get("seed") or -1)
        rng = np.random.default_rng(seed if seed >= 0 else None)

        x_cols = [c for c in (x or train.names)
                  if c != y and c != "__dart_offset__"]
        R = train.nrows
        scs, bss, vls, chs, preds, nws, thsl, nasl = \
            [], [], [], [], [], [], [], []
        scale: list = []
        base_out = None
        bins = None
        self.params["ntrees"] = 1
        self.params["score_tree_interval"] = 0
        self.params["stopping_rounds"] = 0
        # inner fits skip their (discarded) full-frame scoring pass; the
        # final concatenated forest is scored once below
        self.params["_skip_final_metrics"] = True
        try:
            for t in range(ntrees):
                k_idx = np.array([], np.int64)
                if t > 0 and rate_drop > 0 and rng.uniform() >= skip_drop:
                    k_idx = np.flatnonzero(
                        rng.uniform(size=t) < rate_drop)
                keep = [i for i in range(t) if i not in set(k_idx)]
                off = np.zeros(R, np.float32)
                for i in keep:
                    off += preds[i] * np.float32(scale[i])
                work = _Frame(list(train.names) + ["__dart_offset__"],
                              list(train.vecs) + [_Vec(off)])
                self.params["offset_column"] = "__dart_offset__"
                m = super()._fit(job, x_cols, y, work, None)
                sc = np.asarray(m.output["split_col"])   # (1, K, N)
                bs = np.asarray(m.output["bitset"])
                vl = np.asarray(m.output["value"])
                ch = m.output.get("child")
                th = m.output.get("thr_bin")
                na = m.output.get("na_left")
                if base_out is None:
                    base_out = m.output
                    bins = st.bin_matrix_out(
                        train.as_matrix(m.output["x"]), m.output)
                Fnew = np.asarray(st.forest_score(
                    bins, jnp.asarray(sc), jnp.asarray(bs),
                    jnp.asarray(vl),
                    int(m.output["max_depth"]),
                    child=jnp.asarray(ch)
                    if ch is not None else None,
                    thr=jnp.asarray(th) if th is not None else None,
                    na_l=jnp.asarray(na) if na is not None else None,
                    fine_na=st.model_fine_na(m.output)))[: R, 0]
                k = len(k_idx)
                if k:
                    # normalize_type="tree": new tree 1/(k+1); dropped
                    # trees shrink to k/(k+1) of their current weight
                    vl = vl / (k + 1)
                    Fnew = Fnew / (k + 1)
                    for i in k_idx:
                        scale[i] *= k / (k + 1)
                scs.append(sc)
                bss.append(bs)
                vls.append(vl)
                if m.output.get("node_w") is not None:
                    nws.append(np.asarray(m.output["node_w"]))
                if th is not None:
                    thsl.append(np.asarray(th))
                    nasl.append(np.asarray(na))
                if ch is not None:
                    chs.append(np.asarray(ch))
                preds.append(Fnew)
                scale.append(1.0)
                job.update(0.05 + 0.9 * (t + 1) / ntrees,
                           f"dart tree {t + 1}/{ntrees} "
                           f"(dropped {k})")
        finally:
            self.params = p_all
        out = dict(base_out)
        out["split_col"] = np.concatenate(scs)
        out["bitset"] = np.concatenate(bss)
        out["value"] = np.concatenate(
            [v * np.float32(s) for v, s in zip(vls, scale)])
        out["child"] = np.concatenate(chs) if chs else None
        out["node_gain"] = None
        # per-fit covers concatenate cleanly (DART rescales leaf VALUES,
        # not row routing, so TreeSHAP stays exact on the scaled forest)
        out["node_w"] = np.concatenate(nws) \
            if len(nws) == len(scs) else None
        out["thr_bin"] = np.concatenate(thsl) \
            if len(thsl) == len(scs) else None
        out["na_left"] = np.concatenate(nasl) \
            if len(nasl) == len(scs) else None
        out["ntrees_actual"] = ntrees
        model = self.model_cls(self.model_id, dict(p_all), out)
        model.params["response_column"] = y
        model.output["training_metrics"] = model.model_metrics(train)
        if valid is not None:
            model.output["validation_metrics"] = model.model_metrics(valid)
        return model
