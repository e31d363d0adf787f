"""Scoring-time explain options for the SharedTree family.

- ``predict_contributions`` — exact TreeSHAP over the engine's
  compressed forest arrays (reference:
  hex/tree/SharedTreeModelWithContributions.java + the genmodel
  TreeSHAP.java recursion).  The hot path is the native kernel in
  h2o_tpu/native/treeshap.cpp (threads over rows); ``_py_treeshap``
  is the pure-numpy fallback and the test oracle.
- ``predict_leaf_node_assignment`` — per-tree terminal node id or L/R
  descent path (reference: hex/tree/AssignLeafNodeTask, client
  model_base.predict_leaf_node_assignment).
- ``staged_predict_proba`` — cumulative per-tree probabilities
  (reference: GBMModel.StagedPredictionsTask).

All three descend the SAME binned row space scoring uses, so the
assignments/contributions are exactly consistent with predict().

Sum(phi) + BiasTerm equals the model's raw margin (GBM link scale /
DRF vote mean) to float precision — asserted in tests/test_treeshap.py.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.frame import Frame, T_CAT, Vec
from h2o_tpu.models.tree import shared_tree as st


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _binned(model, frame: Frame) -> np.ndarray:
    out = model.output
    return np.asarray(st.bin_matrix_out(model.scoring_matrix(frame), out))


def _forest_arrays(model, need_cover: bool = True):
    """(T, K, N) stacks + None-able child/thr; ``need_cover`` requires
    node_w (TreeSHAP only — models trained before covers existed must
    retrain for contributions; leaf assignment and staged predictions
    never touch covers)."""
    out = model.output
    if need_cover and out.get("node_w") is None:
        raise ValueError(
            "this model predates per-node cover tracking; retrain to "
            "compute contributions")
    if out.get("node_w") is None:
        out = dict(out)
        out["node_w"] = np.zeros_like(np.asarray(out["split_col"]),
                                      dtype=np.float32)
    return (np.asarray(out["split_col"]), np.asarray(out["bitset"]),
            np.asarray(out["value"]), np.asarray(out["node_w"]),
            np.asarray(out["child"]) if out.get("child") is not None
            else None,
            np.asarray(out["thr_bin"]) if out.get("thr_bin") is not None
            else None,
            np.asarray(out["na_left"]) if out.get("thr_bin") is not None
            else None)


def _is_leaf(sc, ch, n) -> bool:
    if sc[n] < 0:
        return True
    return ch is not None and ch[n] < 0


def _children(ch, n):
    return (ch[n], ch[n] + 1) if ch is not None else (2 * n + 1, 2 * n + 2)


# ---------------------------------------------------------------------------
# numpy TreeSHAP (fallback + oracle); mirrors native/treeshap.cpp
# ---------------------------------------------------------------------------

def _py_treeshap(bins, sc_s, bs_s, vl_s, nw_s, ch_s, th_s=None,
                 na_s=None, fine_na: int = -1) -> np.ndarray:
    R, C = bins.shape
    T = sc_s.shape[0]
    B = bs_s.shape[-1] - 1
    phi = np.zeros((R, C + 1))

    def go_left(t, n, b):
        if th_s is not None and th_s[t][n] >= 0:
            if b == fine_na:
                return bool(na_s[t][n])
            return b < th_s[t][n]
        return bool(bs_s[t][n, min(b, B)])

    def tree_mean(t, n):
        sc, ch, vl, nw = sc_s[t], \
            (ch_s[t] if ch_s is not None else None), vl_s[t], nw_s[t]
        if _is_leaf(sc, ch, n):
            return vl[n]
        l, r = _children(ch, n)
        w = nw[n]
        if w == 0:
            return vl[n]
        return (nw[l] * tree_mean(t, l) + nw[r] * tree_mean(t, r)) / w

    def extend(path, pz, po, pi):
        # deep-copy: recursion branches must not share mutable elements
        path = [list(e) for e in path] + \
            [[pi, pz, po, 1.0 if not path else 0.0]]
        d = len(path) - 1
        for i in range(d - 1, -1, -1):
            path[i + 1][3] += po * path[i][3] * (i + 1) / (d + 1)
            path[i][3] = pz * path[i][3] * (d - i) / (d + 1)
        return path

    def unwind(path, pidx):
        d = len(path) - 1
        po, pz = path[pidx][2], path[pidx][1]
        nxt = path[d][3]
        path = [list(e) for e in path]
        for i in range(d - 1, -1, -1):
            if po != 0:
                tmp = path[i][3]
                path[i][3] = nxt * (d + 1) / ((i + 1) * po)
                nxt = tmp - path[i][3] * pz * (d - i) / (d + 1)
            elif pz != 0:
                path[i][3] = path[i][3] * (d + 1) / (pz * (d - i))
            else:
                path[i][3] = 0.0
        for i in range(pidx, d):
            path[i][:3] = path[i + 1][:3]
        return path[:d]

    def unwound_sum(path, pidx):
        d = len(path) - 1
        po, pz = path[pidx][2], path[pidx][1]
        nxt = path[d][3]
        total = 0.0
        for i in range(d - 1, -1, -1):
            if po != 0:
                tmp = nxt * (d + 1) / ((i + 1) * po)
                total += tmp
                nxt = path[i][3] - tmp * pz * ((d - i) / (d + 1))
            elif pz != 0:
                total += (path[i][3] / pz) / ((d - i) / (d + 1))
        return total

    def recurse(t, row, ph, n, path, pz, po, pi):
        sc, ch, vl, nw = sc_s[t], \
            (ch_s[t] if ch_s is not None else None), vl_s[t], nw_s[t]
        path = extend(path, pz, po, pi)
        if _is_leaf(sc, ch, n):
            for i in range(1, len(path)):
                w = unwound_sum(path, i)
                ph[path[i][0]] += w * (path[i][2] - path[i][1]) * vl[n]
            return
        col = int(sc[n])
        b = int(row[col])
        gl = go_left(t, n, b)
        l, r = _children(ch, n)
        hot, cold = (l, r) if gl else (r, l)
        w = nw[n]
        hz = nw[hot] / w if w != 0 else 0.5
        cz = nw[cold] / w if w != 0 else 0.5
        iz = io = 1.0
        pidx = next((i for i, e in enumerate(path) if e[0] == col), None)
        if pidx is not None:
            iz, io = path[pidx][1], path[pidx][2]
            path = unwind(path, pidx)
        recurse(t, row, ph, hot, path, hz * iz, io, col)
        recurse(t, row, ph, cold, path, cz * iz, 0.0, col)

    bias = sum(tree_mean(t, 0) for t in range(T))
    for r in range(R):
        phi[r, C] += bias
        for t in range(T):
            recurse(t, bins[r], phi[r], 0, [], 1.0, 1.0, -1)
    return phi


def _shap_matrix(bins, sc, bs, vl, nw, ch, th=None, na=None,
                 fine_na: int = -1) -> np.ndarray:
    """One class's (T, N) stack -> (R, C+1) contributions; native kernel
    with numpy fallback."""
    from h2o_tpu import native
    if native.treeshap_lib() is not None:
        return native.treeshap_contribs(bins, sc, bs, vl, nw, ch, th, na,
                                        fine_na)
    return _py_treeshap(bins, sc, bs, vl, nw, ch, th, na, fine_na)


# ---------------------------------------------------------------------------
# predict_contributions
# ---------------------------------------------------------------------------

def contributions_frame(model, frame: Frame, top_n: int = 0,
                        bottom_n: int = 0,
                        compare_abs: bool = False,
                        output_format: str = "Original") -> Frame:
    out = model.output
    dom = out.get("response_domain")
    if dom is not None and len(dom) > 2:
        raise NotImplementedError(
            "Calculating contributions is currently not supported for "
            "multinomial models.")
    if output_format not in (None, "", "Original"):
        raise NotImplementedError(
            'Only output_format "Original" is supported for this model.')
    sc, bs, vl, nw, ch, th, na = _forest_arrays(model)
    if sc.shape[1] != 1:
        raise NotImplementedError(
            "Calculating contributions is currently not supported for "
            "multinomial models.")
    bins = _binned(model, frame)
    fine_na = st.model_fine_na(model.output)
    phi = _shap_matrix(bins, sc[:, 0], bs[:, 0], vl[:, 0], nw[:, 0],
                       ch[:, 0] if ch is not None else None,
                       th[:, 0] if th is not None else None,
                       na[:, 0] if na is not None else None, fine_na)
    if model.algo == "drf":
        # DRF predicts the MEAN of its trees' votes; contributions sum
        # (with the bias) to the p1/mean prediction.  (The reference
        # divides by ntrees too — DRFModel.ScoreContributionsTaskDRF.)
        phi = phi / max(int(out["ntrees_actual"]), 1)
    else:
        phi[:, -1] += float(np.asarray(out["f0"]).reshape(-1)[0])
    x = list(out["x"])
    names = x + ["BiasTerm"]
    if not top_n and not bottom_n:
        return Frame(names, [Vec(phi[:, j], nrows=frame.nrows)
                             for j in range(len(names))])
    return _sorted_contributions(phi, x, top_n, bottom_n, compare_abs,
                                 frame.nrows)


def _sorted_contributions(phi: np.ndarray, x: List[str], top_n: int,
                          bottom_n: int, compare_abs: bool,
                          nrows: int) -> Frame:
    """ContributionComposer semantics (genmodel
    ContributionComposer.java): per row, feature ids sorted by value
    (or |value|), sliced to top_n/bottom_n; output columns are
    (feature, value) pairs + BiasTerm, features as categoricals over
    the contribution names."""
    C = len(x)
    contrib_names = x + ["BiasTerm"]

    def adjust(n):
        return C if (n < 0 or n > C) else n

    t_in, b_in = int(top_n or 0), int(bottom_n or 0)
    # ContributionComposer.composeContributions branch order:
    # only-top -> descending; only-bottom -> ASCENDING (bottom_n < 0 =
    # all ascending); both with sum >= C or either negative -> all
    # descending; else top_n descending + bottom_n ascending
    if t_in != 0 and b_in == 0:
        tn, bn = adjust(t_in), 0
    elif t_in == 0 and b_in != 0:
        tn, bn = 0, adjust(b_in)
    elif (t_in + b_in) >= C or t_in < 0 or b_in < 0:
        tn, bn = C, 0
    else:
        tn, bn = t_in, b_in
    vals = phi[:, :C]
    key = np.abs(vals) if compare_abs else vals
    desc = np.argsort(-key, axis=1, kind="stable")         # descending
    asc = np.argsort(key, axis=1, kind="stable")           # ascending
    order = np.concatenate([desc[:, :tn], asc[:, :bn]], axis=1)
    R, M = order.shape
    cols: Dict[str, Vec] = {}
    for j in range(M):
        prefix = ("top", j + 1) if j < tn else ("bottom", j - tn + 1)
        fname = f"{prefix[0]}_feature_{prefix[1]}"
        vname = f"{prefix[0]}_value_{prefix[1]}"
        cols[fname] = Vec(order[:, j].astype(np.float32), T_CAT,
                          domain=list(contrib_names), nrows=nrows)
        cols[vname] = Vec(np.take_along_axis(
            vals, order[:, j: j + 1], axis=1)[:, 0], nrows=nrows)
    cols["BiasTerm"] = Vec(phi[:, C], nrows=nrows)
    return Frame(list(cols), list(cols.values()))


# ---------------------------------------------------------------------------
# predict_leaf_node_assignment
# ---------------------------------------------------------------------------

def _tree_col_names(T: int, K: int) -> List[str]:
    """T{t+1}[.C{c+1}] (SharedTreeModel.makeAllTreeColumnNames)."""
    if K == 1:
        return [f"T{t + 1}" for t in range(T)]
    return [f"T{t + 1}.C{c + 1}" for t in range(T) for c in range(K)]


def leaf_assignment_frame(model, frame: Frame,
                          assign_type: str = "Path") -> Frame:
    out = model.output
    sc, bs, _vl, _nw, ch, th, na = _forest_arrays(model,
                                                  need_cover=False)
    T, K, N = sc.shape
    bins = _binned(model, frame)
    fine_na = st.model_fine_na(out)
    per_class = []
    for k in range(K):
        from h2o_tpu import native
        args = (bins, sc[:, k], bs[:, k],
                ch[:, k] if ch is not None else None,
                th[:, k] if th is not None else None,
                na[:, k] if na is not None else None, fine_na)
        if native.treeshap_lib() is not None:
            ids, paths = native.tree_leaf_assign(*args)
        else:
            ids, paths = _py_leaf_assign(*args)
        per_class.append((ids, paths))
    names = _tree_col_names(T, K)
    cols: List[Vec] = []
    for t in range(T):
        for k in range(K):
            ids, paths = per_class[k]
            if assign_type == "Node_ID":
                cols.append(Vec(ids[:, t].astype(np.float32),
                                nrows=frame.nrows))
            else:
                col = [p.decode() if isinstance(p, bytes) else str(p)
                       for p in paths[: frame.nrows, t]]
                dom = sorted(set(col))
                idx = {s: i for i, s in enumerate(dom)}
                cols.append(Vec(
                    np.asarray([idx[s] for s in col], np.float32),
                    T_CAT, domain=dom, nrows=frame.nrows))
    return Frame(names, cols)


def _py_leaf_assign(bins, sc_s, bs_s, ch_s, th_s=None, na_s=None,
                    fine_na: int = -1):
    R = bins.shape[0]
    T, N = sc_s.shape
    B = bs_s.shape[-1] - 1
    ids = np.zeros((R, T), np.int32)
    paths = np.zeros((R, T), "S64")
    for t in range(T):
        sc = sc_s[t]
        ch = ch_s[t] if ch_s is not None else None
        for r in range(R):
            n, p = 0, []
            while not _is_leaf(sc, ch, n) and len(p) < 63:
                col = int(sc[n])
                b = int(bins[r, col])
                if th_s is not None and th_s[t][n] >= 0:
                    go_left = bool(na_s[t][n]) if b == fine_na \
                        else b < th_s[t][n]
                else:
                    go_left = bool(bs_s[t][n, min(b, B)])
                p.append("L" if go_left else "R")
                l, rt = _children(ch, n)
                n = l if go_left else rt
            ids[r, t] = n
            paths[r, t] = "".join(p).encode()
    return ids, paths


# ---------------------------------------------------------------------------
# staged_predict_proba
# ---------------------------------------------------------------------------

def staged_proba_frame(model, frame: Frame) -> Frame:
    """Cumulative class probabilities after each tree (GBMModel.
    StagedPredictionsTask: binomial columns carry p0 — preds[1] after
    score0Probabilities)."""
    import jax
    out = model.output
    dom = out.get("response_domain")
    sc, bs, vl, _nw, ch, th, na = _forest_arrays(model,
                                                 need_cover=False)
    T, K, N = sc.shape
    bins = jnp.asarray(_binned(model, frame))
    per_tree = np.asarray(st.forest_tree_values(
        bins, jnp.asarray(sc), jnp.asarray(bs), jnp.asarray(vl),
        int(out["max_depth"]),
        child=jnp.asarray(ch) if ch is not None else None,
        thr=jnp.asarray(th) if th is not None else None,
        na_l=jnp.asarray(na) if na is not None else None,
        fine_na=st.model_fine_na(out)))                      # (T, K, R)
    F = np.cumsum(per_tree, axis=0)                          # (T, K, R)
    f0 = np.asarray(out["f0"]).reshape(-1)
    names = _tree_col_names(T, K)
    cols: List[Vec] = []
    dist = out.get("distribution_resolved", "gaussian")
    for t in range(T):
        if dom is not None and len(dom) == 2:
            p1 = 1.0 / (1.0 + np.exp(-(F[t, 0] + f0[0])))
            cols.append(Vec((1.0 - p1).astype(np.float32),
                            nrows=frame.nrows))               # p0
        elif dom is not None:
            logits = F[t] + f0[:, None]                       # (K, R)
            e = np.exp(logits - logits.max(axis=0))
            P = e / e.sum(axis=0)
            for k in range(K):
                cols.append(Vec(P[k].astype(np.float32),
                                nrows=frame.nrows))
        else:
            v = F[t, 0] + f0[0]
            if dist in ("poisson", "gamma", "tweedie"):
                v = np.exp(v)
            cols.append(Vec(v.astype(np.float32), nrows=frame.nrows))
    return Frame(names, cols)
