"""GBM — distributed Gradient Boosting Machine.

Reference: hex/tree/gbm/GBM.java (driver loop buildNextKTrees :464-528 —
per-iteration ComputePredAndRes gradient MRTask, K class trees, GammaPass
leaf values) over the SharedTree engine (SURVEY §3.3).

TPU-native: gradients/hessians are one fused jit over the row-sharded f
array; trees come from h2o_tpu.models.tree.shared_tree (MXU histogram +
vectorized split finding, leaf Newton values fused into the histogram);
the f update is a single-tree forest_score.  Multinomial builds K trees
per iteration on softmax gradients with the (K-1)/K scaling.  The
training metrics that end ``_fit`` are read from that carried f (the
build's own predictions, as the reference takes them), not from a
BigScore of the finished forest.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.cloud import hbroadcast_rows
from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame
from h2o_tpu.models.distributions import get_distribution
from h2o_tpu.models.model import CVFold, DataInfo, Model, ModelBuilder
from h2o_tpu.models.tree import shared_tree as st

EPS = 1e-10


@jax.named_scope("h2o.score.metrics")
def raw_from_F(F, dom, dist_name: str, tweedie_power: float = 1.5,
               threshold: float = 0.5, custom_link: str = None):
    """Link-scale forest sum -> raw predictions (shared by BigScore-style
    full scoring and the driver's incremental per-block scoring)."""
    if dom is None:
        if dist_name == "custom":
            from h2o_tpu.core.udf import custom_link_inv
            return custom_link_inv(custom_link, F[:, 0])
        dist = get_distribution(dist_name, tweedie_power=tweedie_power)
        return dist.link_inv(F[:, 0])
    if len(dom) == 2:
        p1 = jax.nn.sigmoid(F[:, 0])
        label = (p1 >= threshold).astype(jnp.float32)
        return jnp.stack([label, 1 - p1, p1], axis=1)
    P = jax.nn.softmax(F, axis=1)
    label = jnp.argmax(P, axis=1).astype(jnp.float32)
    return jnp.concatenate([label[:, None], P], axis=1)


class GBMModel(Model):
    algo = "gbm"

    def _forest_F(self, m) -> jax.Array:
        """(rows, C) raw-code matrix -> link-scale forest sum (shared by
        the Frame path and the online array fast path)."""
        out = self.output
        return st.forest_score_out(st.bin_matrix_out(m, out), out) + \
            jnp.asarray(out["f0"])[None, :]

    def _raw_from_F(self, F) -> jax.Array:
        out = self.output
        return raw_from_F(F, out.get("response_domain"),
                          out["distribution_resolved"],
                          self.params.get("tweedie_power", 1.5),
                          threshold=float(out.get("default_threshold",
                                                  0.5)),
                          custom_link=out.get("custom_link"))

    def predict_raw_array(self, X) -> jax.Array:
        """Online fast path (serve/engine.py): raw column matrix in
        output['x'] order, no Frame/DKV."""
        return self._raw_from_F(self._forest_F(
            jnp.asarray(X, jnp.float32)))

    def predict_raw(self, frame: Frame):
        F = self._forest_F(self.scoring_matrix(frame))
        off_col = self.params.get("offset_column")
        if off_col and off_col in frame:
            F = F + frame.vec(off_col).data[:, None]
        return self._raw_from_F(F)


class GBM(ModelBuilder):
    algo = "gbm"
    model_cls = GBMModel

    # engine-fixed params (ModelBuilder._validate_fixed: accepted values
    # only — anything else errors instead of silently no-opping)
    ENGINE_FIXED = {
        "histogram_type": ("AUTO", "UniformAdaptive", "QuantilesGlobal",
                           "Random"),
        "categorical_encoding": ("AUTO", "Enum"),
        "calibrate_model": (False,),
    }

    def default_params(self) -> Dict:
        p = super().default_params()
        p.update(ntrees=50, max_depth=5, min_rows=10.0, nbins=20,
                 nbins_cats=1024, learn_rate=0.1, learn_rate_annealing=1.0,
                 sample_rate=1.0, col_sample_rate=1.0,
                 col_sample_rate_per_tree=1.0, min_split_improvement=1e-5,
                 histogram_type="AUTO", nbins_top_level=1024,
                 categorical_encoding="AUTO",
                 score_each_iteration=False, score_tree_interval=0,
                 stopping_rounds=0, stopping_metric="AUTO",
                 stopping_tolerance=1e-3, build_tree_one_node=False,
                 calibrate_model=False, bf16_histograms=False,
                 monotone_constraints=None,
                 custom_distribution_func=None)
        return p

    @staticmethod
    def _mono_array(p, di):
        """monotone_constraints {'col': ±1} -> (C,) int array (reference
        hex/tree monotone handling; only numeric columns constrainable).
        Returns None when unconstrained."""
        mc = p.get("monotone_constraints")
        if not mc:
            return None
        if isinstance(mc, str):
            import json as _json
            try:
                mc = _json.loads(mc.replace("'", '"'))
            except _json.JSONDecodeError:
                raise ValueError(
                    f"bad monotone_constraints: {mc!r}")
        import numpy as _np
        mono = _np.zeros(len(di.x), _np.int32)
        for name, d in dict(mc).items():
            if name not in di.x:
                raise ValueError(f"monotone_constraints column {name!r} "
                                 "is not a predictor")
            if name in di.cat_names:
                raise ValueError(f"monotone_constraints on categorical "
                                 f"column {name!r} is not supported")
            d = int(d)
            if d not in (-1, 0, 1):
                raise ValueError(f"monotone_constraints[{name!r}]={d}; "
                                 "must be -1, 0 or 1")
            mono[di.x.index(name)] = d
        return mono if mono.any() else None

    def _cv_shared(self, job, x, y, train: Frame):
        return st.shared_bins(self.params, x, y, train,
                              offset=self.params.get("offset_column"))

    def _fit(self, job, x, y, train: Frame, valid: Optional[Frame],
             cv: Optional[CVFold] = None):
        """``cv``: this model is one of a cross-validated job's
        (``ModelBuilder._fit_cv``): it bins nothing (``cv.shared`` is the
        job's ``BinnedData``) and, a fold model, trains under
        ``cv.weights`` and reads its holdout metrics and predictions from
        the F it carries."""
        p = self.params
        ckpt = self.checkpoint_model()
        di = DataInfo(train, x, y, mode="tree",
                      weights=p.get("weights_column"),
                      offset=p.get("offset_column"))
        if ckpt is not None:
            # resume: reuse the checkpoint's feature list + binning so new
            # trees reference the same bin space (SharedTree.java:465-478)
            co = ckpt.output
            di.x = list(co["x"])
            di.cat_names = [c for c in di.x if train.vec(c).is_categorical]
            di.num_names = [c for c in di.x if c not in di.cat_names]
            dist_name = co["distribution_resolved"]
        else:
            dist_name = self.resolve_distribution(di)
        nclass = di.nclasses if dist_name in ("bernoulli", "multinomial") \
            else 1
        K = nclass if dist_name == "multinomial" else 1

        hist_type = st.resolve_histogram_type(p)
        if ckpt is not None:
            # resume MUST bin in the checkpoint's grid space
            hist_type = co.get("hist_type", "QuantilesGlobal")
            ck_fine = int(co.get("fine_nbins") or co["nbins"])
            sp_dev = jnp.asarray(co["split_points"])
            binned = st.BinnedData(
                st.bin_matrix(train.as_matrix(di.x), sp_dev,
                              co["is_cat"], ck_fine, co.get("col_nbins")),
                np.asarray(co["split_points"]), sp_dev,
                np.asarray(co["is_cat"]), int(co["nbins"]), ck_fine,
                hist_type, co.get("col_nbins"))
        elif cv is not None:
            binned = cv.shared
        else:
            binned = st.prepare_bins(
                di, int(p["nbins"]), int(p["nbins_cats"]), hist_type,
                int(p.get("nbins_top_level") or 1024))
        bins = binned.bins
        yv = di.response()
        fold_model = cv is not None and cv.weights is not None
        w = cv.weights if fold_model else di.weights()
        active = di.valid_mask()
        R = bins.shape[0]

        # custom distribution (water/udf CDistributionFunc; the stock
        # client's h2o.upload_custom_distribution flow)
        custom = None
        if dist_name == "custom":
            ref = p.get("custom_distribution_func")
            if not ref:
                raise ValueError("distribution='custom' requires "
                                 "custom_distribution_func")
            from h2o_tpu.core.udf import load_custom_distribution
            custom = load_custom_distribution(ref)

        # f0 on link scale
        wa = jnp.where(active, w, 0.0)
        if dist_name != "custom":
            dist = get_distribution(
                dist_name if dist_name != "multinomial" else "gaussian",
                tweedie_power=p["tweedie_power"],
                quantile_alpha=p["quantile_alpha"],
                huber_alpha=p["huber_alpha"])
        if dist_name == "multinomial":
            pri = jnp.stack([jnp.sum(wa * (yv == k)) for k in range(K)])
            pri = pri / jnp.maximum(jnp.sum(pri), EPS)
            f0 = jnp.log(jnp.maximum(pri, EPS))
        elif dist_name == "bernoulli":
            dist = get_distribution("bernoulli")
            f0 = dist.init_f0(jnp.where(active, yv, 0.0), wa)[None]
        elif dist_name == "custom":
            mask = np.asarray(active)
            f0 = jnp.asarray([custom.init_f0(
                np.nan_to_num(np.asarray(yv))[mask],
                np.asarray(w)[mask])], jnp.float32)
        else:
            f0 = dist.init_f0(jnp.where(active, jnp.nan_to_num(yv), 0.0),
                              wa)[None]
        if ckpt is not None:
            f0 = jnp.asarray(co["f0"]) if dist_name == "multinomial" \
                else jnp.asarray(co["f0"][:1])
        F = hbroadcast_rows(f0, R)
        offset = di.offset()
        if offset is not None:
            F = F + offset[:, None]

        prior = 0
        if ckpt is not None:
            prior = int(co["ntrees_actual"])
            if int(co["max_depth"]) != int(p["max_depth"]):
                raise ValueError("checkpoint max_depth mismatch")
            F = F + st.forest_score_out(bins, co, int(p["max_depth"]))

        C = len(di.x)
        from h2o_tpu.core.log import get_logger
        from h2o_tpu.models.tree.jit_engine import (clamp_depth,
                                                    plan_engine, pool_size)
        depth = clamp_depth(int(p["max_depth"]), get_logger("gbm"))
        if depth != int(p["max_depth"]):
            job.warn(f"max_depth={p['max_depth']} exceeds the engine "
                     f"depth limit; trees were built to depth {depth} "
                     "(H2O_TPU_MAX_TREE_DEPTH)")
        kleaves = plan_engine(depth)
        if ckpt is not None:
            if (co.get("child") is not None) != (kleaves > 0) or \
                    co["split_col"].shape[2] != pool_size(depth, kleaves):
                raise ValueError(
                    "checkpoint tree engine/pool mismatch (dense vs "
                    "sparse-frontier, or a different frontier width); "
                    "set H2O_TPU_MAX_LIVE_LEAVES to match the "
                    "checkpoint's engine")
        newton = dist_name not in ("gaussian", "laplace", "quantile",
                                   "huber")
        if custom is not None:
            newton = custom.newton
        if p.get("force_newton"):
            # XGBoost semantics: Newton leaf values for every objective
            # (squared error has unit hessian, so wg/(wh+reg_lambda))
            newton = True
        k_cols = max(1, min(C, int(round(float(p["col_sample_rate"]) * C))))
        f0_out = np.asarray(f0 if dist_name == "multinomial"
                            else jnp.broadcast_to(f0, (K,)))
        sp_np = np.asarray(binned.split_points)
        ic_np = np.asarray(binned.is_cat)

        # the driver hands make_model the F it carried through the last
        # kept block: f0 + offset + checkpoint forest + every new tree, on
        # every row of ``train`` — what _forest_F(train) would recompute
        F_train = None

        def make_model(sc, bs, vl, ch, n_new, F_final):
            nonlocal F_train
            F_train = F_final
            if ckpt is not None:
                sc = np.concatenate([co["split_col"], sc]) if n_new \
                    else np.asarray(co["split_col"])
                bs = np.concatenate([co["bitset"], bs]) if n_new \
                    else np.asarray(co["bitset"])
                vl = np.concatenate([co["value"], vl]) if n_new \
                    else np.asarray(co["value"])
                if ch is not None:
                    ch = np.concatenate([co["child"], ch]) if n_new \
                        else np.asarray(co["child"])
            out = dict(
                x=list(di.x), split_points=sp_np, is_cat=ic_np,
                nbins=binned.nbins, fine_nbins=binned.fine,
                col_nbins=binned.col_nbins, hist_type=binned.hist_type,
                split_col=sc, bitset=bs, value=vl,
                child=ch,
                max_depth=depth, f0=f0_out, effective_max_depth=depth,
                distribution_resolved=dist_name,
                custom_link=custom.link_name if custom else None,
                response_domain=di.response_domain if nclass >= 2 else None,
                domains={c: list(train.vec(c).domain)
                         for c in di.cat_names},
                ntrees_actual=prior + n_new)
            if ckpt is not None and co.get("varimp") is not None:
                # carry the checkpoint trees' importance; the driver adds
                # the new trees' gains on top
                out["varimp"] = np.asarray(co["varimp"])
            if ckpt is not None and co.get("node_gain") is not None:
                # checkpoint per-node gains; driver appends new trees'
                out["node_gain"] = np.asarray(co["node_gain"])
            if ckpt is not None and co.get("node_w") is not None:
                out["node_w"] = np.asarray(co["node_w"])
            if ckpt is not None and co.get("thr_bin") is not None:
                out["thr_bin"] = np.asarray(co["thr_bin"])
                out["na_left"] = np.asarray(co["na_left"])
            model = self.model_cls(self.model_id, dict(p), out)
            model.params["response_column"] = y
            return model

        train_kwargs = dict(
            bins=bins, yv=jnp.nan_to_num(yv), w=w, active=active,
            is_cat=jnp.asarray(binned.is_cat),
            dist_name=dist_name, K=K, max_depth=depth, nbins=binned.nbins,
            k_cols=k_cols, newton=newton,
            sample_rate=float(p["sample_rate"]),
            learn_rate=float(p["learn_rate"]),
            learn_rate_annealing=float(p["learn_rate_annealing"]),
            min_rows=float(p["min_rows"]),
            min_split_improvement=float(p["min_split_improvement"]),
            bf16=bool(p.get("bf16_histograms", False)), mode="gbm",
            tweedie_power=float(p["tweedie_power"]),
            quantile_alpha=float(p["quantile_alpha"]),
            reg_lambda=float(p.get("reg_lambda") or 0.0),
            col_sample_rate_per_tree=float(
                p.get("col_sample_rate_per_tree") or 1.0),
            huber_alpha=float(p["huber_alpha"]), kleaves=kleaves,
            custom_dist=custom,
            adaptive=binned.hist_type in ("UniformAdaptive", "Random"),
            fine_nbins=binned.fine,
            hist_random=binned.hist_type == "Random")
        mono = self._mono_array(p, di)
        if mono is not None:
            train_kwargs["mono"] = jnp.asarray(mono)
            train_kwargs["use_mono"] = True
        kind = "binomial" if nclass == 2 else (
            "multinomial" if nclass > 2 else "regression")
        from h2o_tpu.models.tree.driver import (IncrementalScorer,
                                                run_tree_driver)
        scorer = None
        want_scoring = int(p.get("stopping_rounds") or 0) > 0 or \
            int(p.get("score_tree_interval") or 0) > 0 or \
            p.get("score_each_iteration") or \
            float(p.get("max_runtime_secs") or 0) > 0
        if want_scoring:
            H = pool_size(depth, kleaves)
            proto = make_model(
                np.zeros((0, K, H), np.int32),
                np.zeros((0, K, H, binned.nbins + 1), bool),
                np.zeros((0, K, H), np.float32),
                np.zeros((0, K, H), np.int32) if kleaves else None,
                0, None)
            dom_sc = di.response_domain if nclass >= 2 else None

            def metrics_on(frame, w_sc=None):
                def to_metrics(Fv, ntot):
                    raw = raw_from_F(Fv, dom_sc, dist_name,
                                     float(p["tweedie_power"]),
                                     custom_link=custom.link_name
                                     if custom else None)
                    return proto.metrics_from_raw(raw, frame, w=w_sc)
                return to_metrics

            if fold_model:
                # both metrics of a scoring point from the one carried F:
                # the rows trained on, and the fold's rows (weight 0 in
                # every statistic, routed by growth like any row)
                scorer = IncrementalScorer(
                    metrics_on(train, w),
                    holdout_metrics=metrics_on(train, cv.holdout),
                    holdout_rows=cv.holdout_rows)
            elif valid is None:
                # the trainer's carried F is this frame's prediction: the
                # driver scores each block on it and descends nothing
                scorer = IncrementalScorer(metrics_on(train))
            else:
                bins_sc, prepared = st.bin_validation_frame(
                    job, valid, di.x, proto.output["domains"], binned)
                F_sc = jnp.broadcast_to(
                    f0[None, :], (bins_sc.shape[0], K)).astype(jnp.float32)
                off_col = p.get("offset_column")
                if off_col and off_col in valid:
                    F_sc = F_sc + valid.vec(off_col).data[:, None]
                if prior:
                    F_sc = F_sc + st.forest_score_out(bins_sc, co, depth)
                scorer = IncrementalScorer(
                    metrics_on(train), bins_sc, F_sc, depth,
                    fine_na=binned.fine, valid_metrics=metrics_on(valid),
                    prepared=prepared, ntrees=prior)
        job.update(0.05, f"training {int(p['ntrees']) - prior} trees")
        model = run_tree_driver(job, p, train_kwargs, F, self.rng_key(),
                                make_model, scorer, kind,
                                prior_trees=prior,
                                recovery=getattr(self, "_recovery", None),
                                data_frame=train)
        if p.get("_skip_final_metrics"):
            # per-tree inner fits (DART driver) discard these; the outer
            # loop scores the final concatenated forest once
            return model
        with TimeLine.span("train", "final_metrics", source="carried_F"):
            raw = model._raw_from_F(F_train)
            model.output["training_metrics"] = model.metrics_from_raw(
                raw, train, w=w if fold_model else None)
            if fold_model:
                cv.raw = raw
                model.output["validation_metrics"] = \
                    model.metrics_from_raw(raw, train, w=cv.holdout)
            elif valid is not None:
                model.output["validation_metrics"] = \
                    st.final_validation_metrics(model, valid, scorer)
        return model
