"""UpliftDRF — uplift random forest for treatment-effect estimation.

Reference (hex/tree/uplift/UpliftDRF.java): DRF variant for binary response
+ binary ``treatment_column``; splits maximize the divergence gain between
the treatment and control response distributions (``uplift_metric``:
KL (default) / ChiSquared / Euclidean); leaf prediction is
(p(y=1|treatment) − p(y=1|control)); the prediction frame is
[uplift_predict, p_y1_ct1, p_y1_ct0].

TPU-native: the SAME 4-slot MXU histogram kernel as GBM/DRF, but the slots
carry (w_treat, w_treat·y, w_ctrl, w_ctrl·y) — the uplift divergence gain
is then a closed-form expression over bin cumsums, vectorized across every
(leaf, col, bin, na-direction) candidate at once; the whole forest is one
lax.scan XLA program on the sparse-frontier pool engine (jit_engine
pattern: live leaves capped per level, explicit child pointers), so deep
uplift trees train with bounded memory like GBM/DRF.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.frame import Frame, Vec
from h2o_tpu.models import metrics as mm
from h2o_tpu.models.model import DataInfo, Model, ModelBuilder
from h2o_tpu.core.autotune import hist_bucket
from h2o_tpu.models.tree import shared_tree as st
from h2o_tpu.models.tree.jit_engine import _route_level, frontier_plan
from h2o_tpu.ops.histogram import histogram_build_traced, pallas_env_enabled

EPS = 1e-6


def _divergence(pt, pc, metric: str):
    """D(P_treat || P_ctrl) for a binary outcome."""
    pt = jnp.clip(pt, EPS, 1 - EPS)
    pc = jnp.clip(pc, EPS, 1 - EPS)
    if metric == "kl":
        return pt * jnp.log(pt / pc) + \
            (1 - pt) * jnp.log((1 - pt) / (1 - pc))
    if metric == "chisquared":
        return (pt - pc) ** 2 / pc + (pt - pc) ** 2 / (1 - pc)
    return (pt - pc) ** 2 + ((1 - pt) - (1 - pc)) ** 2   # euclidean


def _find_uplift_splits(hist, col_allowed, metric: str, min_rows: float):
    """Best divergence-gain split per leaf from (L, C, B+1, 4) histograms
    with slots (w_t, w_t*y, w_c, w_c*y).  Prefix bitset splits in natural
    bin order; NA bucket tried on both sides."""
    L, C, B1, _ = hist.shape
    B = B1 - 1
    wt, wty, wc, wcy = (hist[..., k] for k in range(4))
    cwt, cwty, cwc, cwcy = (jnp.cumsum(x[..., :B], axis=2)
                            for x in (wt, wty, wc, wcy))
    nat = (wt[..., B], wty[..., B], wc[..., B], wcy[..., B])
    tot = (cwt[..., -1] + nat[0], cwty[..., -1] + nat[1],
           cwc[..., -1] + nat[2], cwcy[..., -1] + nat[3])

    def rate(n, s):
        return s / jnp.maximum(n, EPS)

    d_parent = _divergence(rate(tot[0], tot[1]), rate(tot[2], tot[3]),
                           metric)                          # (L, C)

    def side_gain(na_left):
        lwt = cwt + (nat[0][..., None] if na_left else 0.0)
        lwty = cwty + (nat[1][..., None] if na_left else 0.0)
        lwc = cwc + (nat[2][..., None] if na_left else 0.0)
        lwcy = cwcy + (nat[3][..., None] if na_left else 0.0)
        rwt = tot[0][..., None] - lwt
        rwty = tot[1][..., None] - lwty
        rwc = tot[2][..., None] - lwc
        rwcy = tot[3][..., None] - lwcy
        nl = lwt + lwc
        nr = rwt + rwc
        n = tot[0][..., None] + tot[2][..., None]
        dl = _divergence(rate(lwt, lwty), rate(lwc, lwcy), metric)
        dr = _divergence(rate(rwt, rwty), rate(rwc, rwcy), metric)
        gain = (nl / jnp.maximum(n, EPS)) * dl + \
            (nr / jnp.maximum(n, EPS)) * dr - d_parent[..., None]
        ok = (nl >= min_rows) & (nr >= min_rows) & \
            (lwt > 0) & (lwc > 0) & (rwt > 0) & (rwc > 0)
        return jnp.where(ok, gain, -jnp.inf)

    gains = jnp.stack([side_gain(False), side_gain(True)], axis=-1)
    gains = jnp.where(col_allowed[..., None, None], gains, -jnp.inf)
    flat = gains.reshape(L, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    col = (best // (B * 2)).astype(jnp.int32)
    rem = best % (B * 2)
    split_b = (rem // 2).astype(jnp.int32)
    na_left = (rem % 2).astype(jnp.bool_)
    do_split = jnp.isfinite(best_gain) & (best_gain > 1e-9)
    bitset_bins = jnp.arange(B)[None, :] <= split_b[:, None]
    bitset = jnp.concatenate([bitset_bins, na_left[:, None]], axis=1)
    # leaf treatment/control rates for values (any column's bin totals
    # equal the leaf totals; use the chosen column's)
    def at_col(x):
        return jnp.take_along_axis(x, col[:, None], axis=1)[:, 0]

    p_t = rate(at_col(tot[0]), at_col(tot[1]))
    p_c = rate(at_col(tot[2]), at_col(tot[3]))
    n_leaf = jnp.take_along_axis(tot[0] + tot[2], col[:, None],
                                 axis=1)[:, 0]
    # child rates at the chosen split (pre-written as child values, so
    # no extra final-level histogram pass is needed)
    li = jnp.arange(L)

    def pick(cum, na):
        base = cum[li, col, split_b]
        return base + jnp.where(na_left, na[li, col], 0.0)

    lwt_s, lwty_s = pick(cwt, nat[0]), pick(cwty, nat[1])
    lwc_s, lwcy_s = pick(cwc, nat[2]), pick(cwcy, nat[3])
    l_pt = rate(lwt_s, lwty_s)
    l_pc = rate(lwc_s, lwcy_s)
    r_pt = rate(at_col(tot[0]) - lwt_s, at_col(tot[1]) - lwty_s)
    r_pc = rate(at_col(tot[2]) - lwc_s, at_col(tot[3]) - lwcy_s)
    l_n = lwt_s + lwc_s
    return dict(do_split=do_split, col=col, bitset=bitset,
                p_t=p_t, p_c=p_c, n=n_leaf,
                l_pt=l_pt, l_pc=l_pc, r_pt=r_pt, r_pc=r_pc,
                l_n=l_n, r_n=n_leaf - l_n)


@functools.partial(
    jax.jit,
    static_argnames=("ntrees", "max_depth", "nbins", "k_cols", "metric",
                     "sample_rate", "min_rows", "kleaves", "hist_pallas",
                     "stats_dtype"))
def _train_uplift_forest(bins, treat, yv, w, active, key, *, ntrees: int,
                         max_depth: int, nbins: int, k_cols: int,
                         metric: str, sample_rate: float, min_rows: float,
                         kleaves: int = 4096, hist_pallas: bool = False,
                         stats_dtype: str = "f32"):
    """Whole uplift forest as one XLA program — the sparse-frontier
    pool engine (jit_engine.build_tree_frontier pattern): live leaves
    capped at ``kleaves`` per level with best-first selection by node
    size, nodes in a grows-with-splits pool with explicit child
    pointers.  Child rates come from the split's own cumsums, so no
    extra final-level histogram pass is needed."""
    from h2o_tpu.ops import statpack
    R, C = bins.shape
    D, B = max_depth, nbins
    widths = frontier_plan(D, kleaves)
    N = 1 + 2 * sum(widths)
    qmax = (statpack.stats_qmax(R, stats_dtype)
            if stats_dtype != "f32" else 0)

    def one_tree(carry, key_t):
        ks, kc = jax.random.split(key_t)
        samp = (jax.random.uniform(ks, (R,)) < sample_rate) & active
        wa = jnp.where(samp, w, 0.0)
        stats = jnp.stack([wa * treat, wa * treat * yv,
                           wa * (1 - treat), wa * (1 - treat) * yv], axis=1)
        if stats_dtype != "f32":
            # quantized carrier (ops/statpack.py): per-tree stochastic
            # rounding off this tree's own key, exact int32 tables,
            # dequantized once per level below
            stats, inv_sc = statpack.quantize_stats(
                stats, key_t, stats_dtype, qmax)
        else:
            inv_sc = None
        split_col = jnp.full((N + 1,), -1, jnp.int32)   # +1 trash slot
        bitset = jnp.zeros((N + 1, B + 1), bool)
        val_t = jnp.zeros((N + 1,), jnp.float32)
        val_c = jnp.zeros((N + 1,), jnp.float32)
        child = jnp.full((N + 1,), -1, jnp.int32)
        frontier = jnp.zeros((1,), jnp.int32)
        slot = jnp.where(samp, 0, -1).astype(jnp.int32)
        base = 1
        for d in range(D):
            L = widths[d]
            hist = histogram_build_traced(bins, slot, stats, L, B, 8192,
                                          False, pallas=hist_pallas)
            if inv_sc is not None:
                hist = statpack.dequant_table(hist, inv_sc)
            kc, kcol = jax.random.split(kc)
            if k_cols < C:
                r = jax.random.uniform(kcol, (L, C))
                kth = jnp.sort(r, axis=1)[:, k_cols - 1][:, None]
                col_allowed = r <= kth
            else:
                col_allowed = jnp.ones((L, C), bool)
            s = _find_uplift_splits(hist, col_allowed, metric, min_rows)
            live = s["n"] > 0
            do = s["do_split"] & live
            child_ptr = base + 2 * jnp.arange(L, dtype=jnp.int32)
            split_col = split_col.at[frontier].set(
                jnp.where(do, s["col"], -1))
            bitset = bitset.at[frontier].set(s["bitset"] & do[:, None])
            # node's own rates stand when it terminates here
            val_t = val_t.at[frontier].set(s["p_t"])
            val_c = val_c.at[frontier].set(s["p_c"])
            child = child.at[frontier].set(jnp.where(do, child_ptr, -1))
            # pre-write child rates at their fresh pool slots
            cvt = jnp.stack([s["l_pt"], s["r_pt"]], axis=1).reshape(2 * L)
            cvc = jnp.stack([s["l_pc"], s["r_pc"]], axis=1).reshape(2 * L)
            cmask = jnp.repeat(do, 2)
            val_t = jax.lax.dynamic_update_slice(
                val_t, jnp.where(cmask, cvt, 0.0), (base,))
            val_c = jax.lax.dynamic_update_slice(
                val_c, jnp.where(cmask, cvc, 0.0), (base,))
            if d + 1 < D:
                L_next = widths[d + 1]
                # best-first by child size: the biggest nodes have the
                # most evidence left to split on
                cn = jnp.stack([s["l_n"], s["r_n"]], axis=1).reshape(2 * L)
                ckey = jnp.where(cmask, cn, -jnp.inf)
                if 2 * L <= L_next:
                    sel = jnp.arange(2 * L, dtype=jnp.int32)
                else:
                    _, sel = jax.lax.top_k(ckey, L_next)
                    sel = sel.astype(jnp.int32)
                sel_valid = jnp.take(ckey, sel) > -jnp.inf
                frontier = jnp.where(sel_valid, base + sel, N)
                inv = jnp.full((2 * L,), -1, jnp.int32).at[sel].set(
                    jnp.where(sel_valid,
                              jnp.arange(L_next, dtype=jnp.int32), -1))
                act = slot >= 0
                sl = jnp.maximum(slot, 0)
                go_left, do_sl = _route_level(bins, sl, s, do, B)
                cand = 2 * sl + jnp.where(go_left, 0, 1)
                new_slot = jnp.where(act & do_sl, inv[cand], -1)
                slot = jnp.where(act, new_slot, slot)
            base += 2 * L
        return carry, (split_col[:N], bitset[:N], val_t[:N], val_c[:N],
                       child[:N])

    _, (sc, bs, vt, vc, ch) = jax.lax.scan(one_tree, 0,
                                           jax.random.split(key, ntrees))
    return sc, bs, vt, vc, ch


class UpliftDRFModel(Model):
    algo = "upliftdrf"


    def predict_raw(self, frame: Frame):
        out = self.output
        bins = st.bin_matrix_out(self.scoring_matrix(frame), out)
        D = int(out["max_depth"])
        T = max(int(out["ntrees_actual"]), 1)
        sc = jnp.asarray(out["split_col"])[:, None]
        bs = jnp.asarray(out["bitset"])[:, None]
        ch = jnp.asarray(out["child"])[:, None] \
            if out.get("child") is not None else None
        pt = st.forest_score(bins, sc, bs,
                             jnp.asarray(out["val_t"])[:, None], D,
                             child=ch)[:, 0] / T
        pc = st.forest_score(bins, sc, bs,
                             jnp.asarray(out["val_c"])[:, None], D,
                             child=ch)[:, 0] / T
        return jnp.stack([pt - pc, pt, pc], axis=1)

    def predict(self, frame: Frame) -> Frame:
        raw = self.predict_raw(frame)
        n = frame.nrows
        return Frame(["uplift_predict", "p_y1_ct1", "p_y1_ct0"],
                     [Vec(raw[:, j], nrows=n) for j in range(3)])

    def model_metrics(self, frame: Frame):
        """Qini-style uplift metrics (ModelMetricsBinomialUplift analog:
        AUUC computed over prediction-ranked buckets)."""
        out = self.output
        raw = np.asarray(self.predict_raw(frame))[: frame.nrows]
        y = np.asarray(frame.vec(self.params["response_column"])
                       .to_numpy(), np.float64)
        t = np.asarray(frame.vec(self.params["treatment_column"])
                       .to_numpy(), np.float64)
        order = np.argsort(-raw[:, 0])
        y, t = y[order], t[order]
        nt = np.cumsum(t)
        nc = np.cumsum(1 - t)
        yt = np.cumsum(y * t)
        yc = np.cumsum(y * (1 - t))
        # Qini curve: incremental gains at each cut
        qini = yt - yc * nt / np.maximum(nc, 1)
        auuc = float(np.trapezoid(qini) / max(len(y), 1))
        ate = float(raw[:, 0].mean())
        return mm.ModelMetrics("uplift", dict(
            auuc=auuc, ate=ate, qini=float(qini[-1])))


class UpliftDRF(ModelBuilder):
    ENGINE_FIXED = {"auuc_type": ("AUTO", "qini"), "auuc_nbins": (-1,)}

    algo = "upliftdrf"
    model_cls = UpliftDRFModel

    def default_params(self) -> Dict:
        p = super().default_params()
        p.update(treatment_column="treatment", uplift_metric="KL",
                 ntrees=50, max_depth=10, min_rows=10.0, nbins=20,
                 nbins_cats=1024, mtries=-2, sample_rate=0.632,
                 auuc_type="AUTO", auuc_nbins=-1)
        return p

    def _fit(self, job, x, y, train: Frame, valid: Optional[Frame]):
        p = self.params
        tcol = p["treatment_column"]
        tv = train.vec(tcol)
        if not tv.is_categorical or tv.cardinality != 2:
            raise ValueError("treatment_column must be a binary categorical")
        x = [c for c in x if c != tcol]
        di = DataInfo(train, x, y, mode="tree",
                      weights=p.get("weights_column"))
        if di.nclasses != 2:
            raise ValueError("UpliftDRF requires a binary response")
        binned = st.prepare_bins(di, int(p["nbins"]), int(p["nbins_cats"]))
        yv = jnp.nan_to_num(di.response())
        treat = tv.data.astype(jnp.float32)
        w = di.weights()
        active = di.valid_mask() & (tv.data >= 0)
        C = len(di.x)
        mtries = int(p["mtries"])
        if mtries == -1:
            mtries = max(1, int(np.sqrt(C)))
        elif mtries <= 0:
            mtries = C
        from h2o_tpu.core.log import get_logger
        from h2o_tpu.models.tree.jit_engine import (clamp_depth,
                                                    max_live_leaves)
        depth = clamp_depth(int(p["max_depth"]), get_logger("upliftdrf"))
        if depth != int(p["max_depth"]):
            job.warn(f"max_depth={p['max_depth']} exceeds the engine "
                     f"depth limit; trees were built to depth {depth}")
        T = int(p["ntrees"])
        job.update(0.1, f"training {T} uplift trees")
        from h2o_tpu.core.oom import kernel_fallback
        from h2o_tpu.ops import statpack
        key0 = self.rng_key()
        # stats carrier resolved OUTSIDE the trace (static jit arg),
        # same once-per-forest discipline as the GBM/DRF driver
        sdt = statpack.resolve_stats_dtype(statpack.stats_bucket(
            binned.bins.shape[0], binned.bins.shape[1], binned.nbins))
        statpack.note_train(sdt, int(binned.bins.shape[0]), 4, T)
        sc, bs, vt, vc, ch = kernel_fallback(
            "tree.block",
            lambda pallas: _train_uplift_forest(
                binned.bins, treat, yv, w, active, key0,
                ntrees=T, max_depth=depth, nbins=binned.nbins,
                k_cols=mtries,
                metric=(p["uplift_metric"] or "KL").lower(),
                sample_rate=float(p["sample_rate"]),
                min_rows=float(p["min_rows"]),
                kleaves=max_live_leaves(), hist_pallas=pallas,
                stats_dtype=sdt),
            # autotuned/forced Pallas decision for the uplift hist
            # shapes, resolved OUTSIDE the trace (static jit arg)
            pallas=pallas_env_enabled(hist_bucket(
                binned.bins.shape[0], binned.bins.shape[1],
                binned.nbins, min(1 << depth, max_live_leaves()))))
        out = dict(x=list(di.x), split_points=binned.split_points,
                   is_cat=binned.is_cat, nbins=binned.nbins,
                   col_nbins=binned.col_nbins,
                   split_col=np.asarray(sc), bitset=np.asarray(bs),
                   val_t=np.asarray(vt), val_c=np.asarray(vc),
                   child=np.asarray(ch),
                   max_depth=depth, ntrees_actual=T,
                   response_domain=di.response_domain,
                   domains={c: list(train.vec(c).domain)
                            for c in di.cat_names})
        model = self.model_cls(self.model_id, dict(p), out)
        model.params["response_column"] = y
        model.params["treatment_column"] = tcol
        model.output["training_metrics"] = model.model_metrics(train)
        if valid is not None:
            model.output["validation_metrics"] = model.model_metrics(valid)
        return model
