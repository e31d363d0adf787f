"""RuleFit — sparse linear model over tree-derived rules.

Reference (hex/rulefit/*, 1.6k LoC): fit a tree ensemble at depths
``min_rule_length..max_rule_length`` (algorithm AUTO→DRF), convert every
terminal-node root-path into a binary rule column, optionally append
winsorized linear terms, and fit an L1 GLM over the rule matrix
(RuleFitUtils / Condition / Rule); output is the rule-importance table
(coefficient-ranked rule descriptions with support).

TPU-native: rule features are NOT materialized per rule — a row's terminal
node per tree comes from the same vectorized heap descent as forest_score,
and the (rows, nodes) one-hot IS the rule matrix, built on device; the
sparse solver is the framework GLM (alpha=1 lasso on einsum Grams).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.frame import Frame, Vec
from h2o_tpu.models.model import DataInfo, Model, ModelBuilder
from h2o_tpu.models.tree import shared_tree as st
from h2o_tpu.ops.descend import descend


@functools.partial(jax.jit, static_argnames=("depth",))
def _terminal_nodes(bins, split_col, bitset, depth: int):
    """(R, T) heap index of each row's terminal node in every tree."""
    def one_tree(carry, tree):
        sc, bs = tree
        return carry, descend(bins, sc, bs, depth)

    _, nodes = jax.lax.scan(one_tree, 0, (split_col, bitset))
    return nodes.T                               # (R, T)


def _describe_rule(node: int, sc, bs, xs, split_points, is_cat,
                   domains) -> str:
    """Root-path conditions of a heap node, rendered like the reference's
    Condition.languageCondition strings."""
    conds = []
    n = node
    while n > 0:
        parent = (n - 1) // 2
        went_left = (n == 2 * parent + 1)
        c = int(sc[parent])
        if c >= 0:
            bits_left = bs[parent]                # (B+1,) left-membership
            bits = bits_left if went_left else ~bits_left
            col = xs[c]
            if is_cat[c]:
                dom = domains.get(col, [])
                levels = [dom[b] for b in range(min(len(dom), len(bits) - 1))
                          if bits[b]]
                cond = f"{col} in {{{', '.join(levels)}}}"
            else:
                sp = split_points[c]
                # split index comes from the un-flipped prefix bitset: the
                # right branch's complement would otherwise yield B-k-2.
                k = int(bits_left[:-1].sum()) - 1
                thr = sp[k] if 0 <= k < len(sp) and np.isfinite(sp[k]) \
                    else None
                op = "<" if went_left else ">="
                cond = f"{col} {op} {thr:.6g}" if thr is not None \
                    else f"{col} {op} ?"
            if bits[-1]:
                cond += " or NA"
            conds.append(cond)
        n = parent
    return " & ".join(reversed(conds)) if conds else "(root)"


class RuleFitModel(Model):
    algo = "rulefit"

    def _rule_frame(self, frame: Frame) -> Frame:
        """Rule + linear feature frame for the inner GLM."""
        out = self.output
        bins = st.bin_matrix_out(self.scoring_matrix(frame), out)
        cols: List[Vec] = []
        names: List[str] = []
        for fi, f in enumerate(out["forests"]):
            nodes = _terminal_nodes(bins, jnp.asarray(f["split_col"]),
                                    jnp.asarray(f["bitset"]),
                                    int(f["depth"]))        # (R, T)
            for (t, h), name in zip(f["rule_nodes"], f["rule_names"]):
                names.append(name)
                cols.append(Vec((nodes[:, t] == h).astype(jnp.float32),
                                nrows=frame.nrows))
        rf = Frame(names, cols)
        if out["linear_names"]:
            for c in out["linear_names"]:
                rf.add(f"linear.{c}", Vec(
                    jnp.nan_to_num(frame.vec(c).as_float()),
                    nrows=frame.nrows))
        return rf

    def _inner(self):
        from h2o_tpu.models.glm import GLMModel
        m = GLMModel.__new__(GLMModel)
        Model.__init__(m, self.output["glm_key"],
                       self.output["glm_params"], self.output["glm_output"])
        return m

    def predict_raw(self, frame: Frame):
        return self._inner().predict_raw(self._rule_frame(frame))

    def rule_importance(self, use_pandas: bool = False):
        rows = self.output["rule_importance"]
        if use_pandas:
            import pandas as pd
            return pd.DataFrame(rows, columns=[
                "rule_id", "coefficient", "support", "rule"])
        return rows


class RuleFit(ModelBuilder):
    algo = "rulefit"
    model_cls = RuleFitModel

    def default_params(self) -> Dict:
        p = super().default_params()
        p.update(algorithm="AUTO", min_rule_length=3, max_rule_length=3,
                 max_num_rules=-1, model_type="rules_and_linear",
                 rule_generation_ntrees=50, lambda_=None)
        return p

    def _fit(self, job, x, y, train: Frame, valid: Optional[Frame]):
        p = self.params
        di = DataInfo(train, x, y, mode="tree",
                      weights=p.get("weights_column"))
        nclass = di.nclasses
        depths = list(range(int(p["min_rule_length"]),
                            int(p["max_rule_length"]) + 1))
        ntrees = max(1, int(p["rule_generation_ntrees"]) // len(depths))
        algo = (p.get("algorithm") or "AUTO").upper()
        model_type = (p.get("model_type") or "rules_and_linear").lower()

        from h2o_tpu.models.tree.drf import DRF
        from h2o_tpu.models.tree.gbm import GBM
        tree_cls = GBM if algo == "GBM" else DRF

        binned = st.prepare_bins(di, 20, 1024)
        forests, support_total = [], []
        for d_i, depth in enumerate(depths):
            job.update(0.1 + 0.4 * d_i / len(depths),
                       f"rule trees depth {depth}")
            # rule extraction reads global-grid bitsets (_rule_conds):
            # pin the quantile engine regardless of the tree default
            tm = tree_cls(ntrees=ntrees, max_depth=depth,
                          seed=int(p.get("seed") or -1),
                          histogram_type="QuantilesGlobal",
                          **({"sample_rate": 0.632} if tree_cls is DRF
                             else {"learn_rate": 0.1}))
            tm_model = tm._fit(job, list(di.x), y, train, None)
            to = tm_model.output
            if to.get("child") is not None:
                # rule depths are bounded by max_rule_length; only the
                # dense-heap layout reaches here unless the frontier cap
                # (H2O_TPU_MAX_LIVE_LEAVES) was shrunk below 2^(depth-1)
                raise ValueError(
                    "RuleFit rule generation needs dense-heap trees; "
                    f"max_rule_length={depth} exceeded the frontier cap — "
                    "raise H2O_TPU_MAX_LIVE_LEAVES or lower "
                    "max_rule_length")
            K = to["split_col"].shape[1]
            # collapse the K class-tree axis: every (t, k) tree is a tree
            sc = to["split_col"].reshape(-1, to["split_col"].shape[2])
            bs = to["bitset"].reshape(-1, *to["bitset"].shape[2:])
            nodes = _terminal_nodes(binned.bins, jnp.asarray(sc),
                                    jnp.asarray(bs), depth)
            nodes_np = np.asarray(nodes)[: train.nrows]
            rule_nodes, rule_names = [], []
            H = sc.shape[1]
            for t in range(sc.shape[0]):
                seen = np.unique(nodes_np[:, t])
                for h in seen:
                    sup = float((nodes_np[:, t] == h).mean())
                    if sup <= 0.0 or sup >= 1.0:
                        continue
                    rule_nodes.append((int(t), int(h)))
                    rule_names.append(f"rule.d{depth}.t{t}.n{h}")
                    support_total.append(sup)
            forests.append(dict(split_col=sc, bitset=bs, depth=depth,
                                rule_nodes=rule_nodes,
                                rule_names=rule_names))

        linear_names = list(di.num_names) \
            if model_type in ("rules_and_linear", "linear") else []
        out_proto = dict(x=list(di.x), split_points=binned.split_points,
                         is_cat=binned.is_cat, nbins=binned.nbins,
                         col_nbins=binned.col_nbins,
                         forests=forests, linear_names=linear_names,
                         domains={c: list(train.vec(c).domain)
                                  for c in di.cat_names},
                         response_domain=di.response_domain
                         if nclass >= 2 else None)
        proto = self.model_cls(self.model_id, dict(p), out_proto)
        rf = proto._rule_frame(train)
        rf.add(y, train.vec(y))
        if p.get("weights_column"):
            rf.add(p["weights_column"], train.vec(p["weights_column"]))
        job.update(0.6, f"L1 GLM over {rf.ncols - 1} rule/linear features")

        from h2o_tpu.models.glm import GLM
        lam = p.get("lambda_")
        family = "binomial" if nclass == 2 else (
            "multinomial" if nclass > 2 else "gaussian")
        glm = GLM(family=family, alpha=1.0,
                  lambda_=lam if lam is not None else 1e-3,
                  standardize=True, seed=p.get("seed", -1),
                  weights_column=p.get("weights_column"))
        inner = glm._fit(job, [n for n in rf.names
                               if n not in (y, p.get("weights_column"))],
                         y, rf, None)

        coef = inner.coef() if hasattr(inner, "coef") else {}
        rules_flat = []
        domains = {c: list(train.vec(c).domain) for c in di.cat_names}
        i = 0
        for f in forests:
            for (t, h), name in zip(f["rule_nodes"], f["rule_names"]):
                beta = float(coef.get(name, 0.0))
                if abs(beta) > 1e-12:
                    desc = _describe_rule(
                        h, np.asarray(f["split_col"][t]),
                        np.asarray(f["bitset"][t]), list(di.x),
                        binned.split_points, binned.is_cat, domains)
                    rules_flat.append((name, beta, support_total[i], desc))
                i += 1
        for c in linear_names:
            beta = float(coef.get(f"linear.{c}", 0.0))
            if abs(beta) > 1e-12:
                rules_flat.append((f"linear.{c}", beta, 1.0, c))
        rules_flat.sort(key=lambda r: -abs(r[1]))
        max_rules = int(p.get("max_num_rules") or -1)
        if max_rules > 0:
            rules_flat = rules_flat[:max_rules]

        out = dict(out_proto, glm_key=str(inner.key),
                   glm_params=inner.params, glm_output=inner.output,
                   rule_importance=rules_flat)
        model = self.model_cls(self.model_id, dict(p), out)
        model.params["response_column"] = y
        model.output["training_metrics"] = \
            inner.output.get("training_metrics")
        if valid is not None:
            model.output["validation_metrics"] = model.model_metrics(valid)
        return model
