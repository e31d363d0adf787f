"""Plain reference of the histogram GBM on a mixed-type frame: numeric
columns with missing values beside enum (categorical) columns.

Straightforward numpy in float64, written from the published semantics
(H2O-3 ``hex/tree``: DHistogram bins, ``DTree.findBestSplitPoint``,
squared-error split gain, Newton leaf values, QuantilesGlobal binning)
and importing nothing of the program.  The semantics, in full:

* **Bins.**  A numeric column's thresholds are its own order statistics:
  of the n values that are present, sorted, threshold i of ``nbins - 1``
  is the one of rank ``floor(i / nbins * (n - 1))``, duplicates dropped;
  a value's bin is the number of thresholds at or below it.  An enum
  column's bins are its level codes, one bin a level (up to
  ``nbins_cats``).  A missing value (NaN; an enum code below 0 or past
  the column's levels) has a bucket of its own in every column.
* **Candidates of a node.**  For a numeric column, every prefix of its
  bins in value order goes left.  For an enum column the levels are
  ordered by the node's mean gradient (sum of gradients over rows;
  levels with no row in the node last) and every prefix of THAT order is
  a candidate left set: the classic exact search for a two-class or
  squared-error criterion.  Each candidate is tried with the missing
  bucket on either side, and "every present value left, missing right"
  is a candidate too.  A candidate stands only if both children hold
  ``min_rows`` ROWS or more.
* **Gain, values, carry.**  As ``benchmark/reference/gbm.py`` has them:
  gain = lg^2/lw + rg^2/rw - tg^2/tw over row counts; a node splits if
  its best gain passes ``max(min_split_improvement * SE(node), 1e-10)``;
  a leaf's value is ``learn_rate * sum(g) / sum(h)`` (Newton, bernoulli);
  F starts at the prior's log-odds and each tree's values are added to
  it before the next tree's gradients are taken.

``follow`` mode takes the trees the program built (each node's column
and left set from the artifact, ``trees_from_artifact``), recomputes
every node's statistics and value from the rows IT routes there, and
searches every column for the best split of each node of the first
trees: ``split_gap`` is how far, as a share of the node's squared
error, the program's own split falls short of that best.  Float32 ties
in the ordering of levels are legitimate, so gains are compared, never
orders.  ``build`` mode grows the trees itself; put in the program's
place it is the control and carries the planted faults.

Departures from H2O-3's ``DTree.findBestSplitPoint``, each on purpose:
H2O sorts an enum column's levels by mean response only for binomial
and regression trees (as here; multinomial uses code order), groups
levels into ``nbins_cats`` bins by code range where this reference caps
(the cell's enums are all below the cap), keeps a "missing vs the rest"
candidate only for columns that saw a missing value (the same set here,
since an empty missing bucket changes no gain), and breaks ties towards
the lower column index then the lower bin; the program's tie-break is
the same but ties do not reach the comparison.  H2O's ``min_rows`` is a
sum of weights; all weights here are 1.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

EPS = 1e-10          # the denominators' floor, as published
LOG_EPS = 1e-15      # the log-loss clip


@dataclass(frozen=True)
class Spec:
    max_depth: int
    nbins: int
    nbins_cats: int
    learn_rate: float
    min_rows: float
    min_split_improvement: float


class Tree(NamedTuple):
    """Dense heap (children of n at 2n+1, 2n+2), H = 2**(D+1) - 1."""
    col: np.ndarray      # (H,) int, -1 = terminal or dead
    thr: np.ndarray      # (H,) float32: numeric node, left iff x < thr
    left: np.ndarray     # (H, W) bool: enum node, level c left iff [n, c]
    na_left: np.ndarray  # (H,) bool: where the node sends a missing value
    value: np.ndarray    # (H,) float64, learn-rate-scaled leaf values


def round_like(x: np.ndarray, precision: Optional[str]) -> np.ndarray:
    """``x`` as a float32 matmul operand of that precision keeps it:
    None/"highest" float32, "bf16" one bfloat16 term."""
    x32 = np.asarray(x, np.float32)
    if precision in (None, "highest"):
        return x32.astype(np.float64)
    if precision == "bf16":
        import ml_dtypes
        return x32.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def quantile_ranks(n: int, nbins: int) -> np.ndarray:
    """QuantilesGlobal: threshold i of nbins-1 is the order statistic of
    rank floor(i/nbins * (n-1)), i = 1..nbins-1."""
    i = np.arange(1, nbins, dtype=np.float64)
    return np.floor(i / nbins * (n - 1)).astype(np.int64)


def _rank_gap(xs, prog, want) -> float:
    """Ranks by which the thresholds ``prog`` miss the wanted ranks
    ``want`` (ascending) of the sorted column ``xs``: every wanted rank
    against the nearest threshold, and every threshold against the
    nearest wanted rank.  A threshold's rank is the interval of ranks
    that hold its value, so a column of few distinct values (a year, a
    flight number: thousands of rows a value) reads the ranks missed and
    not the width of a tie."""
    if prog.size == 0 or want.size == 0:
        return float(np.inf) if prog.size != want.size else 0.0
    lt = np.searchsorted(xs, prog, side="left")
    le = np.searchsorted(xs, prog, side="right") - 1
    # a threshold that is no value of the column holds no rank: it
    # stands between ranks le and lt = le + 1
    lt, le = np.minimum(lt, le), np.maximum(lt, le)

    def miss(w, a, b):
        """Distance from rank w to the nearest interval [a_j, b_j]."""
        j = np.clip(np.searchsorted(a, w), 0, len(a) - 1)
        k = np.clip(j - 1, 0, len(a) - 1)
        dj = np.maximum(0, np.maximum(a[j] - w, w - b[j]))
        dk = np.maximum(0, np.maximum(a[k] - w, w - b[k]))
        return np.minimum(dj, dk)

    fwd = miss(want, lt, le)
    j = np.clip(np.searchsorted(want, lt), 0, len(want) - 1)
    k = np.clip(j - 1, 0, len(want) - 1)
    back = np.minimum(*(np.maximum(0, np.maximum(lt - want[i], want[i] - le))
                        for i in (j, k)))
    return float(max(fwd.max(), back.max()))


def trees_from_artifact(split_col, bitset, value, split_points, is_cat,
                        col_nbins) -> List[Tree]:
    """The program's trees in the reference's terms.  ``split_col``
    (T, H), ``bitset`` (T, H, B+1) left membership over the table's bins
    with the missing bucket's bit last, ``value`` (T, H),
    ``split_points`` (C, B-1) NaN-padded.  A numeric node's bitset is a
    prefix of its column's bins: the last bin that goes left names the
    threshold (every bin left: +inf, "present or missing").  An enum
    node's left set is its bitset over the column's levels."""
    split_col = np.asarray(split_col).astype(np.int64)
    bitset = np.asarray(bitset, bool)
    sp = np.asarray(split_points, np.float32)
    is_cat = np.asarray(is_cat, bool)
    col_nbins = np.asarray(col_nbins).astype(np.int64)
    B = bitset.shape[-1] - 1
    W = int(max([1] + [col_nbins[c] for c in np.nonzero(is_cat)[0]]))
    nthr = np.sum(~np.isnan(sp), axis=1)
    trees = []
    for t in range(split_col.shape[0]):
        col, bs = split_col[t], bitset[t]
        cc = np.maximum(col, 0)
        last_left = bs[:, :B].sum(axis=1) - 1
        thr = np.where(last_left < nthr[cc],
                       sp[cc, np.clip(last_left, 0, sp.shape[1] - 1)],
                       np.inf).astype(np.float32)
        numeric = (col >= 0) & ~is_cat[cc]
        thr = np.where(numeric, thr, np.nan).astype(np.float32)
        left = bs[:, :W] & ((col >= 0) & is_cat[cc])[:, None]
        trees.append(Tree(col, thr, left, bs[:, B] & (col >= 0),
                          np.asarray(value[t], np.float64)))
    return trees


class GbmMixedReference:
    def __init__(self, cols: Sequence[np.ndarray], card: Sequence[int],
                 y: np.ndarray, spec: Spec, threads: int = 4):
        """``cols``: one array a column, float32 (NaN = missing) where
        ``card`` is 0, int32 level codes (below 0 = missing) where it is
        the column's level count."""
        self.cols = list(cols)
        self.card = np.asarray(card, np.int64)
        self.is_cat = self.card > 0
        self.y = np.asarray(y, np.float64)          # (R,) in {0, 1}
        self.spec = spec
        self.C, self.R = len(self.cols), len(self.y)
        self.threads = threads
        # bins a column has; the missing bucket is bin B of every column
        self.nb = np.where(self.is_cat,
                           np.minimum(self.card, spec.nbins_cats),
                           spec.nbins)
        self.B = int(self.nb.max())
        self.W = int(max([1] + list(self.nb[self.is_cat])))
        self.split_points: List[np.ndarray] = []    # per column, ascending
        self.bins: List[np.ndarray] = []            # per column, int16/32

    # -- binning ------------------------------------------------------------

    def _missing(self, c) -> np.ndarray:
        x = self.cols[c]
        if self.is_cat[c]:
            return (x < 0) | (x >= self.nb[c])
        return np.isnan(x)

    def prepare(self, program_split_points=None) -> Dict[str, float]:
        """Own split points and bins; beside them ``rank_gap``: the most
        ranks by which a threshold the program holds for a numeric
        column (C, any width, NaN-padded) misses the order statistic it
        should be, or a wanted order statistic has no threshold."""
        bdt = np.int16 if self.B < 2 ** 15 else np.int32

        def one(c):
            col, na = self.cols[c], self._missing(c)
            if self.is_cat[c]:
                return (np.zeros(0, np.float32),
                        np.where(na, self.B, col).astype(bdt), 0.0)
            xs = np.sort(col[~na])
            ranks = quantile_ranks(len(xs), self.spec.nbins)
            sp = np.unique(xs[ranks])
            gap = 0.0
            if program_split_points is not None:
                prog = np.asarray(program_split_points[c], np.float32)
                gap = _rank_gap(xs, prog[~np.isnan(prog)], ranks)
            b = np.searchsorted(sp, col, side="right")
            return sp, np.where(na, self.B, b).astype(bdt), gap

        with ThreadPoolExecutor(self.threads) as ex:
            res = list(ex.map(one, range(self.C)))
        self.split_points = [r[0] for r in res]
        self.bins = [r[1] for r in res]
        if program_split_points is None:
            return {}
        return {"rank_gap": max(r[2] for r in res)}

    def init_f0(self) -> float:
        p = min(max(float(self.y.mean()), EPS), 1 - EPS)
        return float(np.log(p / (1 - p)))

    def logloss(self, F: np.ndarray) -> float:
        p = 1.0 / (1.0 + np.exp(-F))
        ll = np.where(self.y > 0.5, np.log(np.maximum(p, LOG_EPS)),
                      np.log(np.maximum(1.0 - p, LOG_EPS)))
        return float(-ll.mean())

    # -- one level ----------------------------------------------------------

    def _node_sums(self, local, L, *weights):
        out = [np.bincount(local, minlength=L + 1)[:L].astype(np.float64)]
        for w in weights:
            out.append(np.bincount(local, weights=w, minlength=L + 1)[:L])
        return out

    def _level_hist(self, local, L, g):
        """(C, L, B+1) row counts and gradient sums, the missing bucket
        last; rows with local == L are out of this level."""
        B1 = self.B + 1
        n = (L + 1) * B1
        cnt = np.empty((self.C, L, B1))
        G = np.empty((self.C, L, B1))

        def one(c):
            idx = local * B1 + self.bins[c]
            cnt[c] = np.bincount(idx, minlength=n)[:L * B1].reshape(L, B1)
            G[c] = np.bincount(idx, weights=g,
                               minlength=n)[:L * B1].reshape(L, B1)

        with ThreadPoolExecutor(self.threads) as ex:
            list(ex.map(one, range(self.C)))
        return cnt, G

    def _best_splits(self, cnt, G, cat_by_code: bool = False):
        """Best candidate per node over every column: ``(gain, column,
        k, na_left, order)``: the first ``k`` bins of the column's
        ``order`` (L, B) go left, the missing bucket goes ``na_left``.
        ``cat_by_code`` is the planted fault: enum levels are searched
        in code order, as if they were numbers."""
        B = self.B
        C, L, _ = cnt.shape
        w, g = cnt[:, :, :B], G[:, :, :B]
        with np.errstate(divide="ignore", invalid="ignore"):
            mean = np.where(w > 0, g / w, np.inf)
        natural = np.broadcast_to(np.arange(B, dtype=np.float64), w.shape)
        by_mean = self.is_cat & (not cat_by_code)
        order = np.argsort(np.where(by_mean[:, None, None], mean, natural),
                           axis=2, kind="stable")
        lw0 = np.cumsum(np.take_along_axis(w, order, axis=2), axis=2)
        lg0 = np.cumsum(np.take_along_axis(g, order, axis=2), axis=2)
        naw, nag = cnt[:, :, B:], G[:, :, B:]
        tw, tg = lw0[:, :, -1:] + naw, lg0[:, :, -1:] + nag
        mr = self.spec.min_rows
        gains = []
        for na_left in (False, True):
            lw = lw0 + (naw if na_left else 0.0)
            lg = lg0 + (nag if na_left else 0.0)
            rw, rg = tw - lw, tg - lg
            with np.errstate(divide="ignore", invalid="ignore"):
                gain = (lg ** 2 / lw + rg ** 2 / rw
                        - tg ** 2 / np.maximum(tw, EPS))
            gains.append(np.where((lw >= mr) & (rw >= mr), gain, -np.inf))
        gain = np.stack(gains, axis=3)                    # (C, L, B, 2)
        flat = gain.transpose(1, 0, 2, 3).reshape(L, C * B * 2)
        best = np.argmax(flat, axis=1)
        bc, rem = best // (B * 2), best % (B * 2)
        li = np.arange(L)
        return (flat[li, best], bc, rem // 2 + 1, (rem % 2).astype(bool),
                order[bc, li])

    def _go_left(self, tree: Tree, node: np.ndarray,
                 rows: np.ndarray) -> np.ndarray:
        """Whether each of ``rows`` goes left at its split node ``node``
        (same length), on raw values."""
        out = np.zeros(len(rows), bool)
        c = tree.col[node]

        def one(j):
            m = np.nonzero(c == j)[0]
            n, x = node[m], self.cols[j][rows[m]]
            if self.is_cat[j]:
                na = (x < 0) | (x >= self.nb[j])
                here = tree.left[n, np.clip(x, 0, self.W - 1)]
            else:
                na = np.isnan(x)
                here = x < tree.thr[n]
            out[m] = np.where(na, tree.na_left[n], here)

        with ThreadPoolExecutor(self.threads) as ex:
            list(ex.map(one, np.unique(tree.col[tree.col >= 0])))
        return out

    # -- one tree -----------------------------------------------------------

    def grow(self, F: np.ndarray, tree: Optional[Tree] = None,
             precision: Optional[str] = None, rows=None,
             search: bool = True, cat_by_code: bool = False):
        """One tree at link-scale ``F``.  With ``tree`` it follows that
        tree's splits and returns ``(ref_tree, report)``; without, it
        builds.  ``precision`` rounds the gradient statistics (the
        control); ``rows`` is a boolean mask of the rows counted (the
        half-batch fault); ``cat_by_code`` is ``_best_splits``' fault.
        ``search=False`` skips the search over candidates (leaf values
        and the carried F only)."""
        sp_, D = self.spec, self.spec.max_depth
        R = self.R
        p = 1.0 / (1.0 + np.exp(-F))
        g, h = self.y - p, p * (1.0 - p)
        gg = g * g
        if precision not in (None, "highest"):
            g, h, gg = (round_like(a, precision) for a in (g, h, gg))
        H = 2 ** (D + 1) - 1
        follow = tree is not None
        if follow:
            out = Tree(np.where(tree.col >= 0, tree.col, -1).astype(np.int64),
                       tree.thr, tree.left, tree.na_left, np.zeros(H))
        else:
            out = Tree(np.full(H, -1, np.int64),
                       np.full(H, np.nan, np.float32),
                       np.zeros((H, self.W), bool), np.zeros(H, bool),
                       np.zeros(H))
        col, val = out.col, out.value
        live_all = np.zeros(H, bool)
        cover = np.zeros(H)
        gaps = np.zeros(H)
        cur = np.zeros(R, np.int64)
        alive = np.ones(R, bool) if rows is None else rows.copy()
        for d in range(D):
            L = 2 ** d
            off = L - 1
            sl = slice(off, off + L)
            local = np.where(alive, cur - off, L)
            w, G, GG, Hs = self._node_sums(local, L, g, gg, h)
            live = w > 0
            live_all[sl] = live
            cover[sl] = w
            sep = GG - G ** 2 / np.maximum(w, EPS)
            thresh = np.maximum(
                sp_.min_split_improvement * np.maximum(sep, 0.0), EPS)
            best = np.full(L, -np.inf)
            if search or not follow:
                cnt, Gh = self._level_hist(local, L, g)
                best, bc, bk, bna, border = self._best_splits(
                    cnt, Gh, cat_by_code)
            if follow:
                do = (col[sl] >= 0) & live
            else:
                do = live & (best > thresh)
                col[sl] = np.where(do, bc, -1)
                out.na_left[sl] = do & bna
                for n in np.nonzero(do)[0]:
                    c, k = int(bc[n]), int(bk[n])
                    if self.is_cat[c]:
                        lv = border[n, :k]
                        out.left[off + n, lv[lv < self.W]] = True
                    else:
                        sp = self.split_points[c]
                        out.thr[off + n] = sp[k - 1] if k - 1 < len(sp) \
                            else np.inf
            col[sl] = np.where(do, col[sl], -1)
            term = live & ~do
            val[sl] = np.where(
                term, sp_.learn_rate * G / np.maximum(Hs, EPS), 0.0)
            # route
            lc = np.minimum(local, L - 1)
            moves = alive & do[lc]
            idx = np.nonzero(moves)[0]
            right = ~self._go_left(out, cur[idx], idx)
            cur[idx] = 2 * cur[idx] + 1 + right
            alive = moves
            # children's statistics, as routed
            if follow and search:
                cl = np.where(alive, cur - (2 * L - 1), 2 * L)
                cw, cG = self._node_sums(cl, 2 * L, g)
                lw_, rw_ = cw[0::2], cw[1::2]
                with np.errstate(divide="ignore", invalid="ignore"):
                    pg = (cG[0::2] ** 2 / lw_ + cG[1::2] ** 2 / rw_
                          - G ** 2 / np.maximum(w, EPS))
                okc = (lw_ >= sp_.min_rows) & (rw_ >= sp_.min_rows)
                pg = np.where(okc, pg, -np.inf)
                need = np.maximum(best, thresh)
                have = np.where(do, pg, thresh)
                gaps[sl] = np.where(
                    live, (need - have) / np.maximum(sep, EPS), 0.0)
        L = 2 ** D
        off = L - 1
        local = np.where(alive, cur - off, L)
        w, G, Hs = self._node_sums(local, L, g, h)
        live_all[off:off + L] = w > 0
        cover[off:off + L] = w
        val[off:off + L] = np.where(
            w > 0, sp_.learn_rate * G / np.maximum(Hs, EPS), 0.0)
        # leaf: the node each row ended in (a row left out by ``rows``
        # never leaves the root)
        report = {"split_gap": float(gaps.max()) if search else None,
                  "terminal": live_all & (col < 0), "cover": cover,
                  "leaf": cur}
        return out, report

    def predict(self, tree: Tree) -> np.ndarray:
        """The tree's value for every row, by descent on raw values."""
        cur = np.zeros(self.R, np.int64)
        for _ in range(self.spec.max_depth):
            idx = np.nonzero(tree.col[cur] >= 0)[0]
            right = ~self._go_left(tree, cur[idx], idx)
            cur[idx] = 2 * cur[idx] + 1 + right
        return tree.value[cur]

    # -- the comparison -----------------------------------------------------

    def check_forest(self, trees: List[Tree], f0: float,
                     history: Dict[int, float],
                     search_trees: int = 1) -> Dict[str, float]:
        """Follow ``trees`` (what the timed path built first) and return
        the numbers that decide ``correct``.  ``history`` maps a tree
        count to the training log-loss the program reported there: it
        came from the F the program's own routing made, the reference's
        from rows it routes itself by the artifact's left sets and
        missing sides."""
        f0_ref = self.init_f0()
        F = np.full(self.R, f0_ref)
        out = {"f0_gap": abs(float(f0) - f0_ref),
               "split_gap": 0.0, "leaf_value_gap": 0.0, "update_gap": 0.0,
               "median_leaf_gap": 0.0, "logloss_gap": 0.0}
        compared = 0
        for k, t in enumerate(trees):
            ref, rep = self.grow(F, tree=t, search=k < search_trees)
            if rep["split_gap"] is not None:
                out["split_gap"] = max(out["split_gap"], rep["split_gap"])
            term = rep["terminal"]
            vr, vp = ref.value[term], np.asarray(t.value, np.float64)[term]
            scale = np.maximum(np.abs(vr), np.median(np.abs(vr)))
            rel = np.abs(vp - vr) / scale
            # the median leaf: steady where one small leaf is noisy
            out["median_leaf_gap"] = max(out["median_leaf_gap"],
                                         float(np.median(rel)))
            i = int(rel.argmax())
            if float(rel[i]) > out["leaf_value_gap"]:
                out["leaf_value_gap"] = float(rel[i])
                node = int(np.nonzero(term)[0][i])
                out["worst_leaf"] = {
                    "tree": k, "node": node,
                    "rows": float(rep["cover"][node]),
                    "parent_rows": float(rep["cover"][(node - 1) // 2]),
                    "value": float(vr[i]), "program_value": float(vp[i]),
                    "median_abs_value": float(np.median(np.abs(vr)))}
            # the tree's update of F over the rows: norm of the difference
            # against the norm of the reference's
            n = rep["cover"][term]
            out["update_gap"] = max(out["update_gap"], float(
                np.sqrt(np.sum(n * (vp - vr) ** 2)
                        / np.sum(n * vr ** 2))))
            # a value the program put where the reference has no leaf
            stray = np.asarray(t.value, np.float64)[~term]
            if stray.size and np.max(np.abs(stray)) > 0:
                out["leaf_value_gap"] = max(out["leaf_value_gap"], 1.0)
            F = F + ref.value[rep["leaf"]]
            if (k + 1) in history:
                ll = self.logloss(F)
                out["logloss_gap"] = max(
                    out["logloss_gap"], abs(history[k + 1] - ll) / ll)
                compared += 1
        out["logloss_points"] = compared
        return out

    def build_forest(self, ntrees: int, precision=None, half_batch=False,
                     stale_state=False, cat_by_code=False, na_flip=False):
        """The reference in the program's place: ``(trees, f0, history)``
        as ``check_forest`` takes them.  The faults: ``half_batch``
        counts every other row only, ``stale_state`` hands every tree
        the first tree's F, ``cat_by_code`` searches enum levels in code
        order, ``na_flip`` routes every missing value to the other side
        than the node it writes down says."""
        f0 = self.init_f0()
        F = np.full(self.R, f0)
        rows = None
        if half_batch:
            rows = np.zeros(self.R, bool)
            rows[::2] = True
        trees, history = [], {}
        for k in range(ntrees):
            t, _ = self.grow(F, precision=precision, rows=rows,
                             cat_by_code=cat_by_code)
            trees.append(t)
            routed = t._replace(na_left=~t.na_left) if na_flip else t
            Fn = F + self.predict(routed)
            history[k + 1] = self.logloss(Fn)
            if not stale_state:
                F = Fn
        return trees, f0, history
