"""Plain reference of the random forest the DRF cell trains.

Straightforward numpy, written from the published semantics (H2O-3
``hex/tree/drf``: bagged trees grown on the response, ``mtries`` columns
drawn at each split, UniformAdaptive per-node histograms on the
``nbins_top_level`` fine grid halving to ``nbins``, squared-error split
gain, leaf value the mean response of the node's in-bag rows, training
metrics on each row's out-of-bag votes) and from the program's stated
rules for what H2O-3 leaves open (the counter-based draws and the
best-first frontier cap, both restated below), importing nothing of the
program.  Its grid helper is ``reference/gbm.py``'s.

* ``follow`` mode takes a tree the timed path produced and, level by
  level on the same rows, recomputes its bag, every node's cover and
  mean, each node's ``mtries`` columns and adaptive grid, searches every
  candidate of the grid over the allowed columns, and selects the next
  frontier itself; it reports by how much the program's split falls
  short of the best, nodes kept or cut differently, and split columns
  outside the node's draw.
* ``build`` mode grows trees itself into the program's pool layout: put
  in the program's place it carries the planted faults.
* ``check_forest`` routes every row down the artifact's trees, sums each
  row's out-of-bag votes and scores them as the program's history says
  it did.

The draws (``jit_engine.py`` states the same rule):

    mix(x)  = lowbias32 on uint32; hash(w1..wn) = h_n with h_0 = mix(k0),
              h_1 = mix(h_0 ^ k1), h_{i+1} = mix(h_i ^ w_i); (k0, k1) =
              (0, seed mod 2**32)
    bag     row r in tree t's bag iff hash(1, t, r) >> 8 <
            floor(sample_rate * 2**24)
    mtries  column c allowed at slot s of level d iff fewer than k
            columns j have (v_j, j) < (v_c, c), v_j = hash(2, t, d, s, j)
            >> 8

The frontier: a level of L nodes whose split children outnumber the
next level's width keeps the children of largest residual impurity
``wgg - wg**2 / w`` (a tie to the lower child index), in child order.

Data assumptions, true of ``benchmark/data.py``'s HIGGS generator: unit
row weights, no missing values, numeric columns only, a binary response.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from benchmark.reference.gbm import uniform_split_points

EPS = 1e-10
LOG_EPS = 1e-15
NEAR_TIE = 1e-6      # a frontier key this close to the cut is a tie


@dataclass(frozen=True)
class DrfSpec:
    max_depth: int
    nbins: int
    fine: int                   # nbins_top_level
    min_rows: float
    min_split_improvement: float
    mtries: int
    sample_rate: float
    cap: int                    # the frontier's width


class PoolTree(NamedTuple):
    """One tree in the program's pool layout: node 0 the root, a split
    node's children at ``child[n]`` and ``child[n] + 1``, ``child`` -2 a
    child the cap cut to a leaf; a row goes left iff its fine bin in
    ``col`` is below ``thr``."""
    col: np.ndarray       # (N,) int, -1 = no split
    thr: np.ndarray       # (N,) int fine-bin threshold
    value: np.ndarray     # (N,) float
    child: np.ndarray     # (N,) int
    cover: np.ndarray     # (N,) float, in-bag rows


def _mix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def counter_hash(seed: int, *words) -> np.ndarray:
    with np.errstate(over="ignore"):
        h = _mix(np.asarray([0], np.uint32)) ^ np.uint32(seed % 2 ** 32)
        h = _mix(h)
        for w in words:
            h = _mix(h ^ np.asarray(w).astype(np.uint32))
    return h


def bag(seed: int, t: int, rows: int, rate: float) -> np.ndarray:
    cut = int(rate * (1 << 24))
    return (counter_hash(seed, 1, t, np.arange(rows)) >> 8) < cut


def mtries(seed: int, t: int, d: int, L: int, C: int, k: int) -> np.ndarray:
    v = counter_hash(seed, 2, t, d, np.arange(L)[:, None],
                     np.arange(C)[None, :]) >> 8
    key = v.astype(np.int64) * C + np.arange(C)[None, :]
    rank = np.argsort(np.argsort(key, axis=1), axis=1)
    return rank < k


def frontier_plan(depth: int, cap: int) -> List[int]:
    widths, width = [], 1
    for _ in range(depth):
        widths.append(width)
        width = min(2 * width, cap)
    return widths


def _se(n, p):
    return p - p * p / np.maximum(n, EPS)


class DrfReference:
    def __init__(self, X: np.ndarray, y: np.ndarray, spec: DrfSpec,
                 seed: int, threads: int = 4):
        self.X = X                                  # (C, R) float32
        self.y = np.asarray(y, np.float64)          # (R,) in {0, 1}
        self.spec, self.seed = spec, int(seed)
        self.C, self.R = X.shape
        self.threads = threads
        self.bins: Optional[np.ndarray] = None      # (C, R) fine bins

    # -- binning ------------------------------------------------------------

    def prepare(self, program_split_points) -> Dict[str, float]:
        """``value_gap``: the widest distance, in fine-bin widths, of the
        program's split points from the uniform grid over each column's
        range.  The rows are then binned on the program's points, so
        that counts below compare exactly (a row's bin is its count of
        points at or below its value, the published rule)."""
        F = self.spec.fine
        sp_prog = np.asarray(program_split_points, np.float32)

        def one(c):
            col = self.X[c]
            lo, hi = float(col.min()), float(col.max())
            own = uniform_split_points(lo, hi, F)
            width = (hi - lo) / F if hi > lo else 1.0
            prog = sp_prog[c][~np.isnan(sp_prog[c])]
            gap = (np.inf if prog.shape != own.shape else float(
                np.max(np.abs(prog.astype(np.float64) - own)) / width))
            return gap, np.searchsorted(prog, col, side="right").astype(
                np.int16)

        with ThreadPoolExecutor(self.threads) as ex:
            res = list(ex.map(one, range(self.C)))
        self.bins = np.stack([r[1] for r in res])
        return {"value_gap": max(r[0] for r in res)}

    def bag(self, t: int) -> np.ndarray:
        return bag(self.seed, t, self.R, self.spec.sample_rate)

    # -- one tree -------------------------------------------------------------

    def grow(self, t: int, tree: Optional[PoolTree] = None,
             rows: Optional[np.ndarray] = None, fault: Optional[str] = None):
        """Tree ``t`` (absolute index) level by level.  With ``tree`` it
        follows that tree and returns ``(tree, report)``; without, it
        builds one (``fault``: ``"mtries_per_tree"``, ``"frontier_by_slot"``
        plant a fault; ``rows`` overrides the bag)."""
        sp = self.spec
        D, C, F, B = sp.max_depth, self.C, sp.fine, sp.nbins
        widths = frontier_plan(D, sp.cap)
        N = 1 + 2 * sum(widths)
        follow = tree is not None
        if not follow:
            tree = PoolTree(np.full(N, -1), np.full(N, -1), np.zeros(N),
                            np.full(N, -1), np.zeros(N))
        inbag = self.bag(t) if rows is None else rows
        rr = np.flatnonzero(inbag)
        yb = self.y[rr]
        slot = np.zeros(rr.size, np.int64)
        ids = np.zeros(1, np.int64)
        lo = np.zeros((1, C), np.int64)
        hi = np.full((1, C), F - 1, np.int64)
        base = 1
        rep = {"split_gap": 0.0, "mtries_gap": 0, "frontier_gap": 0,
               "cover_gap": 0.0, "cut": 0}
        leaf_prog, leaf_ref = [], []
        for d in range(D):
            L, Bd = widths[d], max(B, F >> d)
            on = slot >= 0
            sr, rw = slot[on], rr[on]
            n = np.bincount(sr, minlength=L).astype(np.float64)
            p = np.bincount(sr, weights=yb[on], minlength=L)
            have = ids >= 0
            idc = np.maximum(ids, 0)
            if follow:
                rep["cover_gap"] += float(np.abs(
                    np.where(have, tree.cover[idc], 0.0) - n).sum())
            allowed = mtries(self.seed, t, 0 if fault == "mtries_per_tree"
                             else d, L if fault != "mtries_per_tree" else 1,
                             C, sp.mtries)
            if allowed.shape[0] != L:
                allowed = np.repeat(allowed, L, axis=0)
            mn, mx = self._node_extent(sr, rw, L)
            span = np.maximum(hi - lo + 1, 1)
            best, bcol, bb = self._search(sr, rw, yb[on], n, p, lo, span,
                                          allowed, L, Bd)
            sep = _se(n, p)
            thresh = np.maximum(sp.min_split_improvement *
                                np.maximum(sep, 0.0), EPS)
            if follow:
                do = have & (np.where(have, tree.col[idc], -1) >= 0)
                pcol = np.where(do, tree.col[idc], 0)
                pthr = np.where(do, tree.thr[idc], 0)
                rep["mtries_gap"] += int(np.sum(do & ~allowed[np.arange(L),
                                                               pcol]))
            else:
                do = have & (n > 0) & (best > thresh)
                pcol = np.where(do, bcol, 0)
                pthr = np.where(do, lo[np.arange(L), pcol] + (
                    (bb + 1) * span[np.arange(L), pcol] + Bd - 1) // Bd, 0)
                tree.col[idc[do]] = pcol[do]
                tree.thr[idc[do]] = pthr[do]
                tree.cover[idc[have]] = n[have]
            # the program's split on these rows
            right = self.bins[pcol[sr], rw] >= pthr[sr]
            cand = 2 * sr + right
            nc = np.bincount(cand, minlength=2 * L).astype(np.float64)
            pc = np.bincount(cand, weights=yb[on], minlength=2 * L)
            if follow:
                nl, nr, pl, pr = nc[0::2], nc[1::2], pc[0::2], pc[1::2]
                with np.errstate(divide="ignore", invalid="ignore"):
                    pg = _se(n, p) - _se(nl, pl) - _se(nr, pr)
                pg = np.where((nl >= sp.min_rows) & (nr >= sp.min_rows),
                              pg, -np.inf)
                need = np.maximum(best, thresh)
                got = np.where(do, pg, thresh)
                live = have & (n > 0)
                if live.any():
                    gaps = np.where(live, (need - got) / np.maximum(sep, EPS),
                                    -np.inf)
                    j = int(np.argmax(gaps))
                    if gaps[j] > rep["split_gap"]:
                        # where the worst node is: level, slot, rows,
                        # positives, the program's column and threshold,
                        # the reference's best column and threshold
                        rep["split_gap"] = float(gaps[j])
                        rep["split_gap_at"] = [
                            d, j, float(n[j]), float(p[j]), int(pcol[j]),
                            int(pthr[j]) if do[j] else -1, int(bcol[j]),
                            int(lo[j, bcol[j]] + ((bb[j] + 1) * span[
                                j, bcol[j]] + Bd - 1) // Bd)]
            term = have & (n > 0) & ~do
            mean = p / np.maximum(n, EPS)
            if follow:
                leaf_prog.append(tree.value[idc[term]])
            else:
                tree.value[idc[term]] = mean[term]
            leaf_ref.append(mean[term])
            cmask = np.repeat(do, 2)
            cmean = pc / np.maximum(nc, EPS)
            cpool = base + np.arange(2 * L)
            if not follow:
                tree.child[idc[do]] = base + 2 * np.flatnonzero(do)
                tree.value[cpool[cmask]] = cmean[cmask]
                tree.cover[cpool[cmask]] = nc[cmask]
            if d + 1 == D:
                # the last level's children are leaves by depth
                if follow:
                    leaf_prog.append(tree.value[cpool[cmask]])
                    rep["cover_gap"] += float(np.abs(
                        tree.cover[cpool[cmask]] - nc[cmask]).sum())
                leaf_ref.append(cmean[cmask])
                break
            L_next = widths[d + 1]
            if 2 * L <= L_next:
                kept = cmask
            else:
                key = np.where(cmask, np.maximum(_se(nc, pc), 0.0), -np.inf)
                order = np.lexsort((np.arange(2 * L), -key))[:L_next]
                kept_ref = np.zeros(2 * L, bool)
                kept_ref[order] = key[order] > -np.inf
                if follow:
                    kept = cmask & (tree.child[cpool] != -2)
                    cut_key = key[order[-1]]
                    with np.errstate(invalid="ignore"):
                        tie = np.abs(key - cut_key) <= NEAR_TIE * max(
                            abs(cut_key), 1.0)
                    rep["frontier_gap"] += int(np.sum(
                        (kept != kept_ref) & ~tie))
                else:
                    kept = kept_ref
                    if fault == "frontier_by_slot":
                        kept = cmask & (np.cumsum(cmask) <= L_next)
                    tree.child[cpool[cmask & ~kept]] = -2
                lost = cmask & ~kept
                rep["cut"] += int(lost.sum())
                leaf_ref.append(cmean[lost])
                if follow:
                    leaf_prog.append(tree.value[cpool[lost]])
            # the kept children's slots, in child order
            nslot = np.where(kept, np.cumsum(kept) - 1, -1)
            if 2 * L <= L_next:
                nslot = np.where(cmask, np.arange(2 * L), -1)
            ids_next = np.full(L_next, -1, np.int64)
            ids_next[nslot[nslot >= 0]] = cpool[nslot >= 0]
            # children's grids: the node's grid tightened to the buckets
            # that held rows, the split column cut at the threshold
            nlo, nhi = self._refine(mn, mx, n > 0, lo, hi, span, Bd)
            lo2, hi2 = np.repeat(nlo, 2, axis=0), np.repeat(nhi, 2, axis=0)
            sn = np.flatnonzero(do)
            hi2[2 * sn, pcol[sn]] = np.minimum(hi2[2 * sn, pcol[sn]],
                                               pthr[sn] - 1)
            lo2[2 * sn + 1, pcol[sn]] = np.maximum(lo2[2 * sn + 1, pcol[sn]],
                                                   pthr[sn])
            lo2 = np.minimum(lo2, hi2)
            lo = np.zeros((L_next, C), np.int64)
            hi = np.zeros((L_next, C), np.int64)
            lo[nslot[nslot >= 0]] = lo2[nslot >= 0]
            hi[nslot[nslot >= 0]] = hi2[nslot >= 0]
            full = np.full(rr.size, -1, np.int64)
            full[np.flatnonzero(on)] = np.where(do[sr], nslot[cand], -1)
            slot, ids = full, ids_next
            base += 2 * L
        rep["leaf_prog"] = np.concatenate(leaf_prog) if follow else None
        rep["leaf_ref"] = np.concatenate(leaf_ref)
        return tree, rep

    def _node_extent(self, sr, rw, L):
        """(L, C) smallest and largest fine bin of each node's rows."""
        order = np.argsort(sr, kind="stable")
        s_sorted = sr[order]
        starts = np.flatnonzero(np.r_[True, s_sorted[1:] != s_sorted[:-1]]) \
            if s_sorted.size else np.zeros(0, np.int64)
        nodes = s_sorted[starts]
        mn = np.zeros((L, self.C), np.int64)
        mx = np.zeros((L, self.C), np.int64)
        rows_sorted = rw[order]

        def one(c):
            if not starts.size:
                return
            b = self.bins[c, rows_sorted]
            mn[nodes, c] = np.minimum.reduceat(b, starts)
            mx[nodes, c] = np.maximum.reduceat(b, starts)

        with ThreadPoolExecutor(self.threads) as ex:
            list(ex.map(one, range(self.C)))
        return mn, mx

    def _bucket(self, x, lo, span, Bd):
        return np.clip((np.clip(x - lo, 0, span - 1) * Bd) // span, 0,
                       Bd - 1)

    def _refine(self, mn, mx, anyb, lo, hi, span, Bd):
        """The node's grid tightened to its first and last non-empty
        bucket, in every column."""
        first = self._bucket(mn, lo, span, Bd)
        last = self._bucket(mx, lo, span, Bd)
        lo_e = lo + (first * span + Bd - 1) // Bd
        hi_e = lo + np.clip(((last + 1) * span + Bd - 1) // Bd, 1, span) - 1
        a = anyb[:, None]
        return (np.where(a, lo_e, lo),
                np.where(a, np.maximum(hi_e, lo_e), hi))

    def _search(self, sr, rw, yw, n, p, lo, span, allowed, L, Bd):
        """Best (gain, column, bucket) of each node over every prefix of
        its grid in every allowed column (a tie to the lower column, then
        the lower bucket)."""
        mr = self.spec.min_rows

        def one(c):
            m = allowed[sr, c]
            s = sr[m]
            b = self._bucket(self.bins[c, rw[m]].astype(np.int64), lo[s, c],
                             span[s, c], Bd)
            idx = s * Bd + b
            cnt = np.bincount(idx, minlength=L * Bd).reshape(L, Bd)
            pos = np.bincount(idx, weights=yw[m],
                              minlength=L * Bd).reshape(L, Bd)
            lw = np.cumsum(cnt, axis=1)[:, :-1].astype(np.float64)
            lg = np.cumsum(pos, axis=1)[:, :-1]
            rw_, rg = n[:, None] - lw, p[:, None] - lg
            with np.errstate(divide="ignore", invalid="ignore"):
                g = _se(n, p)[:, None] - _se(lw, lg) - _se(rw_, rg)
            g = np.where((lw >= mr) & (rw_ >= mr) & allowed[:, c:c + 1], g,
                         -np.inf)
            j = np.argmax(g, axis=1)
            return g[np.arange(L), j], j

        with ThreadPoolExecutor(self.threads) as ex:
            res = list(ex.map(one, range(self.C)))
        best = np.full(L, -np.inf)
        bcol = np.zeros(L, np.int64)
        bb = np.zeros(L, np.int64)
        for c, (g, j) in enumerate(res):
            better = g > best
            best = np.where(better, g, best)
            bcol = np.where(better, c, bcol)
            bb = np.where(better, j, bb)
        return best, bcol, bb

    # -- the forest -----------------------------------------------------------

    def route(self, tree: PoolTree) -> np.ndarray:
        """Each row's final node in ``tree``."""
        node = np.zeros(self.R, np.int64)
        rows = np.arange(self.R)
        for _ in range(self.spec.max_depth):
            c = tree.col[node]
            split = (c >= 0) & (tree.child[node] >= 0)
            right = self.bins[np.maximum(c, 0), rows] >= tree.thr[node]
            node = np.where(split, tree.child[node] + right, node)
        return node

    def oob_votes(self, trees: List[PoolTree], bags: List[np.ndarray]):
        """Per scoring point k: (votes, count) of the first k trees on the
        rows each left out of its bag; and each tree's cover gap (the
        rows its leaves hold under the bag, against the artifact's)."""
        votes = np.zeros(self.R)
        count = np.zeros(self.R)
        points, cover_gaps = [], []
        for tr, inb in zip(trees, bags):
            leaf = self.route(tr)
            held = np.bincount(leaf[inb], minlength=tr.cover.size)
            reached = np.unique(leaf)
            cover_gaps.append(float(np.abs(
                held[reached] - tr.cover[reached]).sum()))
            out = ~inb
            votes[out] += tr.value[leaf[out]]
            count[out] += 1
            points.append((votes.copy(), count.copy()))
        return points, cover_gaps

    def logloss(self, votes, count) -> float:
        m = count > 0
        if not m.any():
            return float("nan")     # no out-of-bag row: nothing to score
        pr = np.clip(votes[m] / count[m], 0.0, 1.0)
        y = self.y[m]
        ll = np.where(y > 0.5, np.log(np.maximum(pr, LOG_EPS)),
                      np.log(np.maximum(1.0 - pr, LOG_EPS)))
        return float(-ll.mean())

    def check_forest(self, trees: List[PoolTree], history: Dict[int, float],
                     final_logloss: float, final_rows: float,
                     follow_first: bool = True) -> Dict[str, float]:
        """The numbers that decide ``correct``.  ``history`` maps a tree
        count to the training (out-of-bag) log-loss the program reported
        there; ``final_*`` are its training metrics at the end."""
        out: Dict[str, float] = {}
        bags = [self.bag(t) for t in range(len(trees))]
        if follow_first and trees:
            _, rep = self.grow(0, tree=trees[0])
            d = np.abs(rep["leaf_prog"] - rep["leaf_ref"])
            out.update(split_gap=rep["split_gap"],
                       mtries_gap=rep["mtries_gap"],
                       frontier_gap=rep["frontier_gap"],
                       leaf_value_gap=float(d.max()) if d.size else 0.0,
                       median_leaf_gap=float(np.median(d)) if d.size else 0.0,
                       cover_gap_tree1=rep["cover_gap"],
                       cut_tree1=rep["cut"])
            if "split_gap_at" in rep:
                out["split_gap_at"] = rep["split_gap_at"]
        points, cover_gaps = self.oob_votes(trees, bags)
        out["bag_gap"] = float(sum(cover_gaps))
        gap, compared = 0.0, 0
        for k, (v, c) in enumerate(points, start=1):
            if k in history:
                ll = self.logloss(v, c)
                gap = max(gap, abs(history[k] - ll) / ll)
                compared += 1
        out["oob_logloss_gap"] = gap
        out["oob_points_missing"] = len(points) - compared
        if points:
            v, c = points[-1]
            ll = self.logloss(v, c)
            out["oob_final_gap"] = abs(final_logloss - ll) / ll
            out["oob_rows_gap"] = abs(float(final_rows) - float((c > 0).sum()))
        return out

    def build_forest(self, ntrees: int, fault: Optional[str] = None):
        """The reference in the program's place: ``(trees, history,
        final_logloss, final_rows)`` as ``check_forest`` takes them.
        Faults: ``all_rows`` (training metrics on every row's votes over
        every tree), ``mtries_per_tree``, ``bag_rate_1``,
        ``frontier_by_slot``, ``half_batch`` (every other row counted)."""
        trees, bags = [], []
        for t in range(ntrees):
            rows = None
            if fault == "bag_rate_1":
                rows = np.ones(self.R, bool)
            elif fault == "half_batch":
                rows = self.bag(t) & (np.arange(self.R) % 2 == 0)
            tree, _ = self.grow(t, rows=rows, fault=fault)
            trees.append(tree)
            bags.append(self.bag(t) if rows is None else rows)
        history = {}
        if fault == "all_rows":
            votes = np.zeros(self.R)
            for k, tr in enumerate(trees, start=1):
                votes += tr.value[self.route(tr)]
                history[k] = self.logloss(votes, np.full(self.R, k))
            return trees, history, history[ntrees], float(self.R)
        points, _ = self.oob_votes(trees, bags)
        for k, (v, c) in enumerate(points, start=1):
            history[k] = self.logloss(v, c)
        return trees, history, history[ntrees], float((points[-1][1] > 0)
                                                      .sum())
