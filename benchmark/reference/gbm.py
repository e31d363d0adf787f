"""Plain reference of the histogram GBM the tree cells train.

Straightforward numpy in float64, written from the published semantics
(H2O-3 ``hex/tree``: DHistogram bins, squared-error split gain, Newton
leaf values, QuantilesGlobal / UniformAdaptive binning) and importing
nothing of the program.  It is the yardstick that decides ``correct``:

* ``follow`` mode takes the first trees the timed path produced and,
  level by level on the same rows, (a) recomputes every node's
  statistics and leaf value, (b) searches every (column, threshold)
  candidate for the best split and measures by how much of the parent's
  squared error the program's own split falls short of it, and (c)
  carries its own F forward, so that tree k is judged against the
  gradients that trees 1..k-1 should have left.  A near tie costs a
  gap near nought, where an independent roll-out would diverge.
* ``build`` mode grows the trees itself.  Put in the program's place it
  is the control (gradient statistics rounded as a lower matmul
  precision would round them) and carries the planted faults.

Data assumptions, true of every generator in ``benchmark/data.py``:
unit row weights, no missing values, numeric columns only, a binary
response (bernoulli deviance).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional

import numpy as np

EPS = 1e-10          # the denominators' floor, as published
LOG_EPS = 1e-15      # the log-loss clip


@dataclass(frozen=True)
class Spec:
    max_depth: int
    nbins: int
    learn_rate: float
    min_rows: float
    min_split_improvement: float
    histogram_type: str          # "QuantilesGlobal" | "UniformAdaptive"
    nbins_top_level: int = 1024


class Tree(NamedTuple):
    """Dense heap (children of n at 2n+1, 2n+2), H = 2**(D+1) - 1."""
    col: np.ndarray      # (H,) int, -1 = terminal or dead
    thr: np.ndarray      # (H,) float32: a row goes left iff x < thr
    value: np.ndarray    # (H,) float64, learn-rate-scaled leaf values


def round_like(x: np.ndarray, precision: Optional[str]) -> np.ndarray:
    """``x`` as a float32 matmul operand of that precision keeps it:
    None/"highest" float32, "high" two bfloat16 terms (three passes),
    "bf16" one."""
    x32 = np.asarray(x, np.float32)
    if precision in (None, "highest"):
        return x32.astype(np.float64)
    import ml_dtypes
    bf = ml_dtypes.bfloat16
    hi = x32.astype(bf).astype(np.float32)
    if precision == "bf16":
        return hi.astype(np.float64)
    if precision == "high":
        lo = (x32 - hi).astype(bf).astype(np.float32)
        return hi.astype(np.float64) + lo.astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def quantile_ranks(n: int, nbins: int) -> np.ndarray:
    """QuantilesGlobal: threshold i of nbins-1 is the order statistic of
    rank floor(i/nbins * (n-1)), i = 1..nbins-1."""
    i = np.arange(1, nbins, dtype=np.float64)
    return np.floor(i / nbins * (n - 1)).astype(np.int64)


def _rank_gap(xs, prog, want_sp) -> float:
    """Ranks by which the thresholds ``prog`` miss the order statistics
    ``want_sp`` of the sorted column ``xs``: every wanted rank against
    the nearest threshold, and every threshold against the nearest
    wanted rank (a threshold's rank is the interval of ranks that hold
    its value)."""
    if prog.size == 0 or want_sp.size == 0:
        return float(np.inf) if prog.size != want_sp.size else 0.0
    lt = np.searchsorted(xs, prog, side="left")
    le = np.searchsorted(xs, prog, side="right") - 1
    want = np.searchsorted(xs, want_sp, side="left")

    def miss(w, a, b):
        """Distance from rank w to the nearest interval [a_j, b_j]."""
        j = np.clip(np.searchsorted(a, w), 0, len(a) - 1)
        k = np.clip(j - 1, 0, len(a) - 1)
        dj = np.maximum(0, np.maximum(a[j] - w, w - b[j]))
        dk = np.maximum(0, np.maximum(a[k] - w, w - b[k]))
        return np.minimum(dj, dk)

    fwd = miss(want, lt, le)
    back = np.array([np.min(np.maximum(0, np.maximum(a - want, want - b)))
                     for a, b in zip(lt, le)])
    return float(max(fwd.max(), back.max()))


def uniform_split_points(lo: float, hi: float, fine: int) -> np.ndarray:
    """UniformAdaptive: fine-1 equally spaced thresholds over [min, max]
    of the column, rounded once to float32."""
    span = hi - lo if hi > lo else 1.0
    grid = np.arange(1, fine, dtype=np.float64) / fine
    return (np.float64(lo) + grid * np.float64(span)).astype(np.float32)


class GbmReference:
    def __init__(self, X: np.ndarray, y: np.ndarray, spec: Spec,
                 threads: int = 4):
        self.X = X                                  # (C, R) float32
        self.y = np.asarray(y, np.float64)          # (R,) in {0, 1}
        self.spec = spec
        self.C, self.R = X.shape
        self.threads = threads
        self.adaptive = spec.histogram_type != "QuantilesGlobal"
        # fine grid width: bins per column that rows are binned into once
        self.F = (max(spec.nbins_top_level, spec.nbins) if self.adaptive
                  else spec.nbins)
        self.split_points: List[np.ndarray] = []    # per column, ascending
        self.bins: List[np.ndarray] = []            # per column, int16/32

    # -- binning ------------------------------------------------------------

    def prepare(self, program_split_points=None) -> Dict[str, float]:
        """Own split points and bins; beside them, how far the program's
        split points (C, F-1, NaN-padded) lie from them:
        ``rank_gap`` — QuantilesGlobal: the most ranks by which a
        program threshold misses the order statistic it should be;
        ``value_gap`` — UniformAdaptive: the widest difference in units
        of one fine bin's width."""
        n, nb = self.R, self.spec.nbins
        ranks = quantile_ranks(n, nb)
        bdt = np.int16 if self.F < 2 ** 15 else np.int32

        def one(c):
            col = self.X[c]
            gap = 0.0
            prog = None
            if program_split_points is not None:
                prog = np.asarray(program_split_points[c], np.float32)
                prog = prog[~np.isnan(prog)]
            if self.adaptive:
                lo, hi = float(col.min()), float(col.max())
                sp = uniform_split_points(lo, hi, self.F)
                if prog is not None:
                    width = (hi - lo) / self.F if hi > lo else 1.0
                    gap = (np.inf if prog.shape != sp.shape else float(
                        np.max(np.abs(prog.astype(np.float64) - sp))
                        / width))
            else:
                xs = np.sort(col)
                sp = np.unique(xs[ranks])
                if prog is not None:
                    gap = _rank_gap(xs, prog, sp)
            b = np.searchsorted(sp, col, side="right").astype(bdt)
            return sp, b, gap

        with ThreadPoolExecutor(self.threads) as ex:
            res = list(ex.map(one, range(self.C)))
        self.split_points = [r[0] for r in res]
        self.bins = [r[1] for r in res]
        gap = max(r[2] for r in res)
        key = "value_gap" if self.adaptive else "rank_gap"
        return {key: gap} if program_split_points is not None else {}

    def control_split_points(self, precision: str) -> List[np.ndarray]:
        """The split points as arithmetic of that precision would place
        them (the control of the binning layer): quantile ranks, or the
        uniform grid, computed from operands rounded to it."""
        def r(x):
            return round_like(np.asarray(x, np.float64), precision)
        out = []
        for c in range(self.C):
            col = self.X[c]
            if self.adaptive:
                lo, hi = float(col.min()), float(col.max())
                grid = r(np.arange(1, self.F) / self.F)
                sp = r(r(lo) + r(grid * r(hi - lo))).astype(np.float32)
            else:
                i = np.arange(1, self.spec.nbins, dtype=np.float64)
                ranks = np.floor(r(r(i / self.spec.nbins)
                                   * r(self.R - 1))).astype(np.int64)
                sp = np.unique(np.sort(col)[np.clip(ranks, 0, self.R - 1)])
            out.append(sp)
        return out

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

    def _level_hist(self, local, L, g, B, bucket_of):
        """(C, L, B) row counts and gradient sums; rows with local == L
        are out of this level.  ``bucket_of(c)`` gives the column's
        bucket per row at this level."""
        n = (L + 1) * B
        cnt = np.empty((self.C, L, B))
        G = np.empty((self.C, L, B))

        def one(c):
            idx = local * B + bucket_of(c)
            cnt[c] = np.bincount(idx, minlength=n)[:L * B].reshape(L, B)
            G[c] = np.bincount(idx, weights=g,
                               minlength=n)[:L * B].reshape(L, B)

        with ThreadPoolExecutor(self.threads) as ex:
            list(ex.map(one, range(self.C)))
        return cnt, G

    def _best_splits(self, cnt, G):
        """Best (gain, column, bin) per node over every prefix split
        "buckets <= b go left", b = 0..B-2, with both children holding
        min_rows rows or more."""
        lw = np.cumsum(cnt, axis=2)[:, :, :-1]
        lg = np.cumsum(G, axis=2)[:, :, :-1]
        tw = cnt.sum(axis=2)[:, :, None]
        tg = G.sum(axis=2)[:, :, None]
        rw, rg = tw - lw, tg - lg
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = (lg ** 2 / lw + rg ** 2 / rw
                    - tg ** 2 / np.maximum(tw, EPS))
        mr = self.spec.min_rows
        gain = np.where((lw >= mr) & (rw >= mr), gain, -np.inf)
        C, L, Bm = gain.shape
        flat = gain.transpose(1, 0, 2).reshape(L, C * Bm)
        best = np.argmax(flat, axis=1)
        return flat[np.arange(L), best], best // Bm, best % Bm

    # -- one tree -----------------------------------------------------------

    def grow(self, F: np.ndarray, tree: Optional[Tree] = None,
             precision: Optional[str] = None, rows=None,
             search: bool = True):
        """One tree at link-scale ``F``.  With ``tree`` it follows that
        tree's splits and returns ``(ref_tree, report)``; without, it
        builds.  ``precision`` rounds the gradient statistics (the
        control); ``rows`` is a boolean mask of the rows counted (the
        half-batch fault).  ``search=False`` skips the search over
        candidates (leaf values and the carried F only)."""
        sp_, D = self.spec, self.spec.max_depth
        R = self.R
        p = 1.0 / (1.0 + np.exp(-F))
        g, h = self.y - p, p * (1.0 - p)
        gg = g * g
        if precision not in (None, "highest"):
            g, h, gg = (round_like(a, precision) for a in (g, h, gg))
        H = 2 ** (D + 1) - 1
        col = np.full(H, -1, np.int64)
        thr = np.full(H, np.nan, np.float32)
        val = np.zeros(H, np.float64)
        live_all = np.zeros(H, bool)
        cover = np.zeros(H)
        cur = np.zeros(R, np.int64)
        alive = np.ones(R, bool) if rows is None else rows.copy()
        arange = np.arange(R)
        gaps = np.zeros(H)
        follow = tree is not None
        B = sp_.nbins
        if self.adaptive:
            lo = np.zeros((1, self.C), np.int64)
            hi = np.full((1, self.C), self.F - 1, np.int64)
        for d in range(D):
            L = 2 ** d
            off = L - 1
            local = np.where(alive, cur - off, L)
            w, G, GG, Hs = self._node_sums(local, L, g, gg, h)
            live = w > 0
            live_all[off:off + L] = live
            cover[off:off + L] = w
            sep = GG - G ** 2 / np.maximum(w, EPS)
            thresh = np.maximum(
                sp_.min_split_improvement * np.maximum(sep, 0.0), EPS)
            Bd = max(B, self.F >> d) if self.adaptive else B
            if search or not follow:
                if self.adaptive:
                    lc0 = np.minimum(local, L - 1)
                    span = np.maximum(hi - lo + 1, 1)

                    def bucket_of(c, lc0=lc0, span=span, lo=lo, Bd=Bd):
                        x = np.clip(self.bins[c] - lo[lc0, c], 0,
                                    span[lc0, c] - 1)
                        return np.clip(x * Bd // span[lc0, c], 0, Bd - 1)
                else:
                    def bucket_of(c):
                        return self.bins[c]
                cnt, Gh = self._level_hist(local, L, g, Bd, bucket_of)
                best, bc, bb = self._best_splits(cnt, Gh)
                if self.adaptive:
                    # bucket k's first fine bin: lo + ceil(k*span/Bd)
                    li = np.arange(L)
                    fine_thr = (lo[li, bc]
                                + ((bb + 1) * span[li, bc] + Bd - 1) // Bd)
                    bthr = np.array(
                        [self.split_points[c][t - 1]
                         for c, t in zip(bc, fine_thr)], np.float32)
                else:
                    bthr = np.array([self.split_points[c][b]
                                     if b < len(self.split_points[c])
                                     else np.inf
                                     for c, b in zip(bc, bb)], np.float32)
            else:
                best = np.full(L, -np.inf)
            if follow:
                tcol = tree.col[off:off + L]
                tthr = tree.thr[off:off + L]
                do = (tcol >= 0) & live
            else:
                do = live & (best > thresh)
                tcol = np.where(do, bc, -1)
                tthr = np.where(do, bthr, np.nan).astype(np.float32)
            col[off:off + L] = np.where(do, tcol, -1)
            thr[off:off + L] = np.where(do, tthr, np.nan)
            term = live & ~do
            v = sp_.learn_rate * G / np.maximum(Hs, EPS)
            val[off:off + L] = np.where(term, v, 0.0)
            # route
            lc = np.minimum(local, L - 1)
            moves = alive & do[lc]
            x = self.X[np.maximum(tcol, 0)[lc], arange]
            right = ~(x < tthr[lc])
            cur = np.where(moves, 2 * cur + 1 + right, cur)
            alive = moves
            # children's statistics, as routed
            cl = np.where(alive, cur - (2 * L - 1), 2 * L)
            cw, cG = self._node_sums(cl, 2 * L, g)
            if follow and search:
                lw_, rw_ = cw[0::2], cw[1::2]
                with np.errstate(divide="ignore", invalid="ignore"):
                    pg = (cG[0::2] ** 2 / lw_ + cG[1::2] ** 2 / rw_
                          - G ** 2 / np.maximum(w, EPS))
                okc = (lw_ >= sp_.min_rows) & (rw_ >= sp_.min_rows)
                pg = np.where(okc, pg, -np.inf)
                need = np.maximum(best, thresh)
                have = np.where(do, pg, thresh)
                gaps[off:off + L] = np.where(
                    live, (need - have) / np.maximum(sep, EPS), 0.0)
            if self.adaptive and d + 1 < D and (search or not follow):
                lo, hi = self._child_ranges(cnt, lo, hi, Bd, do, tcol,
                                            tthr, L)
        L = 2 ** D
        off = L - 1
        local = np.where(alive, cur - off, L)
        w, G, Hs = self._node_sums(local, L, g, h)
        live_all[off:off + L] = w > 0
        cover[off:off + L] = w
        val[off:off + L] = np.where(
            w > 0, sp_.learn_rate * G / np.maximum(Hs, EPS), 0.0)
        ref = Tree(col, thr, val)
        report = {"split_gap": float(gaps.max()) if search else None,
                  "terminal": live_all & (col < 0), "cover": cover}
        return ref, report

    def _child_ranges(self, cnt, lo, hi, Bd, do, tcol, tthr, L):
        """UniformAdaptive: a child's fine range in each column is the
        parent's, tightened to the buckets that held rows; in the split
        column it ends (left) or starts (right) at the threshold."""
        have = cnt.transpose(1, 0, 2) > 0                    # (L, C, Bd)
        anyb = have.any(axis=2)
        first = have.argmax(axis=2)
        last = Bd - 1 - have[:, :, ::-1].argmax(axis=2)
        span = np.maximum(hi - lo + 1, 1)
        lo_e = lo + (first * span + Bd - 1) // Bd
        hi_e = lo + np.clip(((last + 1) * span + Bd - 1) // Bd, 1,
                            span) - 1
        nlo = np.where(anyb, lo_e, lo)
        nhi = np.where(anyb, np.maximum(hi_e, lo_e), hi)
        lo2 = np.repeat(nlo, 2, axis=0)
        hi2 = np.repeat(nhi, 2, axis=0)
        for n in np.nonzero(do)[0]:
            c = int(tcol[n])
            sp = self.split_points[c]       # fine threshold nearest to it
            i = int(np.clip(np.searchsorted(sp, tthr[n]), 1, len(sp) - 1))
            t = (i if abs(sp[i] - tthr[n]) < abs(sp[i - 1] - tthr[n])
                 else i - 1) + 1
            hi2[2 * n, c] = min(hi2[2 * n, c], t - 1)
            lo2[2 * n + 1, c] = max(lo2[2 * n + 1, c], t)
        return np.minimum(lo2, hi2), hi2

    def predict(self, tree: Tree) -> np.ndarray:
        """The tree's value for every row, by descent on raw values."""
        cur = np.zeros(self.R, np.int64)
        arange = np.arange(self.R)
        for _ in range(self.spec.max_depth):
            c = tree.col[cur]
            x = self.X[np.maximum(c, 0), arange]
            right = ~(x < tree.thr[cur])
            cur = np.where(c >= 0, 2 * cur + 1 + right, cur)
        return tree.value[cur]

    # -- the comparison -----------------------------------------------------

    def check_forest(self, trees: List[Tree], f0: float,
                     history: Dict[int, float],
                     search_trees: int = 1) -> Dict[str, float]:
        """Follow ``trees`` (what the timed path built first) and return
        the numbers that decide ``correct``.  ``history`` maps a tree
        count to the training log-loss the program reported there."""
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
                # which leaf reads widest: a small child of a large
                # parent carries the parent's rounding
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
            F = F + self.predict(ref)
            if (k + 1) in history:
                ll = self.logloss(F)
                out["logloss_gap"] = max(
                    out["logloss_gap"], abs(history[k + 1] - ll) / ll)
                compared += 1
        out["logloss_points"] = compared
        return out

    def build_forest(self, ntrees: int, precision=None, half_batch=False,
                     stale_state=False):
        """The reference in the program's place: ``(trees, f0, history)``
        as ``check_forest`` takes them.  The faults are step 3's:
        ``half_batch`` counts every other row only, ``stale_state``
        hands every tree the first tree's F."""
        f0 = self.init_f0()
        F = np.full(self.R, f0)
        rows = None
        if half_batch:
            rows = np.zeros(self.R, bool)
            rows[::2] = True
        trees, history = [], {}
        for k in range(ntrees):
            t, _ = self.grow(F, precision=precision, rows=rows)
            trees.append(t)
            Fn = F + self.predict(t)
            history[k + 1] = self.logloss(Fn)
            if not stale_state:
                F = Fn
        return trees, f0, history
