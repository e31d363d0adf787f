"""DRF — Distributed Random Forest (+ Isolation Forest / ExtraTrees flavors).

Reference: hex/tree/drf/DRF.java over SharedTree — bagged trees fit directly
on the response (no boosting), per-split mtries column subsampling,
sample_rate=0.632 row bagging, predictions averaged over trees; multinomial
builds one tree per class on one-vs-all indicators with normalized votes.

TPU-native: same engine as GBM (MXU histogram + bitset splits); leaf values
are plain means (no Newton), prediction = mean over trees.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import Frame
from h2o_tpu.models.model import CVFold, DataInfo, Model, ModelBuilder
from h2o_tpu.models.tree import shared_tree as st

EPS = 1e-10


def raw_from_votes(F, ntrees: int, dom, threshold: float = 0.5):
    """Accumulated per-tree votes -> raw predictions (mean over trees)."""
    F = F / max(int(ntrees), 1)
    if dom is None:
        return F[:, 0]
    if len(dom) == 2:
        p1 = jnp.clip(F[:, 0], 0.0, 1.0)
        label = (p1 >= threshold).astype(jnp.float32)
        return jnp.stack([label, 1 - p1, p1], axis=1)
    P = jnp.maximum(F, 0.0)
    P = P / jnp.maximum(jnp.sum(P, axis=1, keepdims=True), EPS)
    label = jnp.argmax(P, axis=1).astype(jnp.float32)
    return jnp.concatenate([label[:, None], P], axis=1)


def raw_from_oob(O, dom, threshold: float = 0.5):
    """Carried out-of-bag votes (``TrainedForest.oob``: K vote sums, then
    the trees a row was out of the bag of) -> raw predictions, each row
    the mean of the votes of the trees that did not see it."""
    K = O.shape[1] - 1
    votes, count = O[:, :K], jnp.maximum(O[:, K:], 1.0)
    # unanimous votes of 1 mean 1 exactly (the chip's division misses
    # x / x by up to two ulps, and a wrong vote's log-loss is most
    # sensitive near 1: ``jit_engine._node_val``)
    return raw_from_votes(jnp.where(votes == count, 1.0, votes / count), 1,
                          dom, threshold)


def oob_weights(O, w):
    """``w`` where a row was out of some tree's bag, else 0: a row every
    tree saw has no out-of-bag prediction and counts in no metric."""
    return jnp.where(O[:, -1] > 0, w, 0.0)


class DRFModel(Model):
    algo = "drf"

    def predict_raw_array(self, X) -> jax.Array:
        """Online fast path (serve/engine.py): raw column matrix in
        output['x'] order, no Frame/DKV."""
        out = self.output
        m = jnp.asarray(X, jnp.float32)
        F = st.forest_score_out(st.bin_matrix_out(m, out), out)
        return raw_from_votes(F, int(out["ntrees_actual"]),
                              out.get("response_domain"),
                              threshold=float(out.get(
                                  "default_threshold", 0.5)))

    def predict_raw(self, frame: Frame):
        # delegates to the array fast path — one scoring implementation
        return self.predict_raw_array(self.scoring_matrix(frame))


class DRF(ModelBuilder):
    algo = "drf"
    model_cls = DRFModel

    ENGINE_FIXED = {
        "histogram_type": ("AUTO", "UniformAdaptive", "QuantilesGlobal",
                           "Random"),
        "binomial_double_trees": (False,),
    }

    def default_params(self) -> Dict:
        p = super().default_params()
        p.update(ntrees=50, max_depth=20, min_rows=1.0, nbins=20,
                 nbins_cats=1024, mtries=-1, sample_rate=0.632,
                 col_sample_rate_per_tree=1.0, min_split_improvement=1e-5,
                 histogram_type="AUTO", nbins_top_level=1024,
                 binomial_double_trees=False,
                 score_each_iteration=False, score_tree_interval=0,
                 stopping_rounds=0, stopping_metric="AUTO",
                 stopping_tolerance=1e-3)
        return p

    def _cv_shared(self, job, x, y, train: Frame):
        return st.shared_bins(self.params, x, y, train)

    def _fit(self, job, x, y, train: Frame, valid: Optional[Frame],
             cv: Optional[CVFold] = None):
        """``cv``: as ``GBM._fit``'s: one of a cross-validated job's
        models, on the job's ``BinnedData``; a fold model's metrics and
        holdout predictions are read from the votes it carried."""
        p = self.params
        ckpt = self.checkpoint_model()
        di = DataInfo(train, x, y, mode="tree",
                      weights=p.get("weights_column"))
        if ckpt is not None:
            co = ckpt.output
            di.x = list(co["x"])
            di.cat_names = [c for c in di.x if train.vec(c).is_categorical]
            di.num_names = [c for c in di.x if c not in di.cat_names]
        nclass = di.nclasses
        K = nclass if nclass > 2 else 1

        hist_type = st.resolve_histogram_type(p)
        if ckpt is not None:
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
        C = len(di.x)

        # mtries default: sqrt(C) classification, C/3 regression (DRF.java)
        mtries = int(p["mtries"])
        if mtries <= 0:
            mtries = max(1, int(np.sqrt(C))) if nclass >= 2 \
                else max(1, C // 3)

        from h2o_tpu.core.log import get_logger
        from h2o_tpu.models.tree.jit_engine import (clamp_depth,
                                                    plan_engine, pool_size)
        depth = clamp_depth(int(p["max_depth"]), get_logger("drf"))
        if depth != int(p["max_depth"]):
            job.warn(f"max_depth={p['max_depth']} exceeds the engine "
                     f"depth limit; trees were built to depth {depth} "
                     "(H2O_TPU_MAX_TREE_DEPTH)")
        kleaves = plan_engine(depth)
        F0 = jnp.zeros((R, K), jnp.float32)
        prior = 0
        if ckpt is not None:
            prior = int(co["ntrees_actual"])
            if int(co["max_depth"]) != depth:
                raise ValueError("checkpoint max_depth mismatch")
            if (co.get("child") is not None) != (kleaves > 0) or \
                    co["split_col"].shape[2] != pool_size(depth, kleaves):
                raise ValueError(
                    "checkpoint tree engine/pool mismatch (dense vs "
                    "sparse-frontier, or a different frontier width); "
                    "set H2O_TPU_MAX_LIVE_LEAVES to match the "
                    "checkpoint's engine")
            F0 = F0 + st.forest_score_out(bins, co, depth)
        sp_np = np.asarray(binned.split_points)
        ic_np = np.asarray(binned.is_cat)

        F_train = None      # the votes the driver carried, on every row
        # H2O-3 scores a random forest's training frame on the rows each
        # tree left out of its bag: the tree driver carries those votes (with
        # no bag, sample_rate 1, every tree sees every row and the
        # training metrics are those of all the votes)
        bagged = float(p["sample_rate"]) < 1.0
        oob0 = None
        if bagged:
            # row-sharded as the trainer returns its carries (one program
            # for the first block and the next)
            from h2o_tpu.core.cloud import cloud
            from h2o_tpu.core.landing import reshard_rows
            rows = cloud().matrix_sharding()
            F0 = reshard_rows(F0, rows)
            oob0 = reshard_rows(jnp.zeros((R, K + 1), jnp.float32), rows)
        O_train = None      # the out-of-bag votes the tree driver carried

        def make_model(sc, bs, vl, ch, n_new, F_final, oob=None):
            nonlocal F_train, O_train
            F_train, O_train = F_final, oob
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
                max_depth=depth, effective_max_depth=depth,
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
            dist_name="gaussian", K=K, max_depth=depth, nbins=binned.nbins,
            k_cols=mtries, newton=False,
            sample_rate=float(p["sample_rate"]),
            learn_rate=1.0, learn_rate_annealing=1.0,
            min_rows=float(p["min_rows"]),
            min_split_improvement=float(p["min_split_improvement"]),
            col_sample_rate_per_tree=float(
                p.get("col_sample_rate_per_tree") or 1.0),
            mode="drf", kleaves=kleaves,
            adaptive=binned.hist_type in ("UniformAdaptive", "Random"),
            fine_nbins=binned.fine,
            hist_random=binned.hist_type == "Random")
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
                return lambda Fv, ntot: proto.metrics_from_raw(
                    raw_from_votes(Fv, ntot, dom_sc), frame, w=w_sc)

            def oob_metrics(O, _ntot):
                return proto.metrics_from_raw(raw_from_oob(O, dom_sc),
                                              train, w=oob_weights(O, w))

            train_metrics = oob_metrics if bagged else metrics_on(train, w)
            if fold_model:
                # the in-fold metrics from the out-of-bag votes, the
                # holdout's from every tree's (``GBM._fit``)
                scorer = IncrementalScorer(
                    train_metrics,
                    holdout_metrics=metrics_on(train, cv.holdout),
                    holdout_rows=cv.holdout_rows, oob=bagged)
            elif valid is None:
                # the trainer carries the out-of-bag votes on every row
                # of this frame: the tree driver scores each block on them
                scorer = IncrementalScorer(train_metrics, oob=bagged)
            else:
                bins_sc, prepared = st.bin_validation_frame(
                    job, valid, di.x, proto.output["domains"], binned)
                F_sc = jnp.zeros((bins_sc.shape[0], K), jnp.float32)
                if prior:
                    F_sc = F_sc + st.forest_score_out(bins_sc, co, depth)
                scorer = IncrementalScorer(
                    train_metrics, bins_sc, F_sc, depth,
                    fine_na=binned.fine, valid_metrics=metrics_on(valid),
                    prepared=prepared, ntrees=prior, oob=bagged)
        job.update(0.05, f"training {int(p['ntrees']) - prior} trees")
        model = run_tree_driver(job, p, train_kwargs, F0, self.rng_key(),
                                make_model, scorer, kind,
                                prior_trees=prior,
                                recovery=getattr(self, "_recovery", None),
                                data_frame=train, oob0=oob0)
        dom = model.output.get("response_domain")
        with TimeLine.span("train", "final_metrics",
                           source="carried_oob" if bagged else "carried_F"):
            if bagged:
                model.output["training_metrics"] = model.metrics_from_raw(
                    raw_from_oob(O_train, dom), train,
                    w=oob_weights(O_train, w))
            else:
                model.output["training_metrics"] = model.metrics_from_raw(
                    raw_from_votes(F_train,
                                   int(model.output["ntrees_actual"]), dom),
                    train, w=w)
            if fold_model:
                cv.raw = raw_from_votes(
                    F_train, int(model.output["ntrees_actual"]), dom)
                model.output["validation_metrics"] = \
                    model.metrics_from_raw(cv.raw, train, w=cv.holdout)
                return model
            if valid is not None:
                model.output["validation_metrics"] = \
                    st.final_validation_metrics(model, valid, scorer)
        return model
