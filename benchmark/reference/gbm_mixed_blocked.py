"""``benchmark/reference/gbm_mixed.py``'s reference, computed in row
blocks across the host's processes.

The semantics are ``GbmMixedReference``'s, letter for letter (that
module's docstring states them; this one imports its split search, its
leaf and gain formulas and its comparison): only where the rows are
read differs.  A frame of tens of millions of rows is cut into blocks of
rows; worker processes, forked once, each read their blocks of the
columns (shared with the parent, never copied) and write per-block
tables: node sums and (node, column, bin) counts and gradient sums,
which the parent adds in float64.  Routing and the gradients are
computed block by block in place, in arrays the workers share.  A
column's order statistics come from ``np.partition`` of its present
values, one process a column; the ranks at which a threshold of the
program stands come from counting, block by block.

Beside ``GbmMixedReference``'s numbers, ``prepare`` gives
``split_point_gap``: the thresholds of the program (a numeric column's
non-missing split points) that are not the exact order statistic the
program's own rank rule names, ``int(float32(i / nbins) * float32(n -
1))`` of the n present values (zeros of either sign alike, repeated
values once).  Every threshold the program computes is such an order
statistic, so a sound run reads 0; a shard's rows left out of the counts
move thousands of ranks.

Agreement with the single-pass reference: the same trees, and the
tables equal to float64 reassociation (``tests/test_sharded_airline.py``
holds both on a frame whose row count no block size divides).
"""

from __future__ import annotations

import mmap
import multiprocessing as mp
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark.reference.gbm_mixed import (EPS, LOG_EPS,  # noqa: F401
                                           GbmMixedReference, Spec, Tree,
                                           quantile_ranks, round_like)

# the worker's view of the shared state; set before the pool forks, so
# that every worker holds the same arrays
_W: Dict[str, object] = {}


def _shared(shape, dtype) -> np.ndarray:
    """An array in anonymous shared memory: what a worker forked after
    it writes, the parent reads."""
    n = int(np.prod(shape))
    buf = mmap.mmap(-1, max(n * np.dtype(dtype).itemsize, 1))
    return np.frombuffer(buf, dtype, count=n).reshape(shape)


def program_rank_rule(n: int, nbins: int) -> np.ndarray:
    """The ranks the program's split points stand at: float32
    probabilities i / nbins times float32(n - 1), truncated."""
    probs = np.arange(1, nbins, dtype=np.float32) / np.float32(nbins)
    return np.clip((probs * np.float32(n - 1)).astype(np.int64), 0,
                   max(n - 1, 0))


def rank_gap_from_counts(lt: np.ndarray, le: np.ndarray,
                         want: np.ndarray) -> float:
    """``gbm_mixed._rank_gap`` from the rank interval of each threshold:
    ``lt`` the present values below it, ``le`` those at or below it,
    less one (both ascending with the thresholds)."""
    if lt.size == 0 or want.size == 0:
        return float(np.inf) if lt.size != want.size else 0.0
    lt, le = np.minimum(lt, le), np.maximum(lt, le)

    def miss(w, a, b):
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


# ---- the workers --------------------------------------------------------

def _rows(k: int) -> slice:
    a = k * _W["block"]
    return slice(a, min(a + _W["block"], _W["R"]))


def _w_present(k: int) -> np.ndarray:
    s = _rows(k)
    return np.array([0 if cat else int(np.count_nonzero(~np.isnan(c[s])))
                     for c, cat in zip(_W["cols"], _W["is_cat"])])


def _w_order_stats(job):
    """A numeric column's present values at the ranks ``kth``, of every
    row or (``masked``) of the rows the shared ``rows`` mask keeps."""
    c, kth, masked = job
    x = _W["cols"][c]
    keep = ~np.isnan(x) & _W["rows"] if masked else ~np.isnan(x)
    v = x[keep]
    if v.size == 0 or len(kth) == 0:
        return np.zeros(0, np.float32)
    v.partition(kth)
    return v[kth]


def _w_rank_counts(job):
    """Per numeric column, the present values of block ``k`` below and at
    or below each threshold of ``progs`` (ascending)."""
    k, progs = job
    s = _rows(k)
    out = []
    for c, prog in enumerate(progs):
        if prog is None:
            out.append(None)
            continue
        x = _W["cols"][c][s]
        x = x[~np.isnan(x)]
        n = len(prog) + 1
        lt = np.bincount(np.searchsorted(prog, x, side="right"),
                         minlength=n)
        le = np.bincount(np.searchsorted(prog, x, side="left"),
                         minlength=n)
        out.append((np.cumsum(lt)[:-1], np.cumsum(le)[:-1]))
    return out


def _w_bin(job):
    k, sps = job
    s = _rows(k)
    B, nb, bins = _W["B"], _W["nb"], _W["bins"]
    for c, (col, cat) in enumerate(zip(_W["cols"], _W["is_cat"])):
        x = col[s]
        if cat:
            na = (x < 0) | (x >= nb[c])
            bins[c, s] = np.where(na, B, x)
        else:
            bins[c, s] = np.where(np.isnan(x), B,
                                  np.searchsorted(sps[c], x, side="right"))


def _w_grad(job):
    k, precision = job
    s = _rows(k)
    p = 1.0 / (1.0 + np.exp(-_W["F"][s]))
    g, h = _W["y"][s] - p, p * (1.0 - p)
    gg = g * g
    if precision not in (None, "highest"):
        g, h, gg = (round_like(a, precision) for a in (g, h, gg))
    _W["g"][s], _W["h"][s], _W["gg"][s] = g, h, gg
    _W["cur"][s] = 0
    _W["alive"][s] = _W["rows"][s]


def _local(s, L: int):
    off = L - 1
    return np.where(_W["alive"][s], _W["cur"][s] - off, L)


def _w_level(job):
    """Node sums (rows, g, gg, h) of the level's L nodes and, with
    ``search``, the (column, node, bin) counts and gradient sums of the
    block: into this block's slots."""
    k, L, search = job
    s = _rows(k)
    local = _local(s, L)
    nodes = _W["nodes"][k]
    nodes[0, :L] = np.bincount(local, minlength=L + 1)[:L]
    for i, name in enumerate(("g", "gg", "h"), start=1):
        nodes[i, :L] = np.bincount(local, weights=_W[name][s],
                                   minlength=L + 1)[:L]
    if search:
        B1 = _W["B"] + 1
        n = (L + 1) * B1
        g = _W["g"][s]
        tab = _W["tab"][k]
        for c in range(len(_W["cols"])):
            idx = local * B1 + _W["bins"][c, s]
            tab[0, c, :L] = np.bincount(idx, minlength=n)[:L * B1].reshape(
                L, B1)
            tab[1, c, :L] = np.bincount(idx, weights=g, minlength=n)[
                :L * B1].reshape(L, B1)


def _go_left(s, tree: Tree, node, idx):
    """Whether the block's rows ``idx`` go left at their nodes ``node``."""
    out = np.zeros(len(idx), bool)
    c = tree.col[node]
    nb, W = _W["nb"], _W["W"]
    for j in np.unique(c[c >= 0]):
        m = np.nonzero(c == j)[0]
        n, x = node[m], _W["cols"][j][s][idx[m]]
        if _W["is_cat"][j]:
            na = (x < 0) | (x >= nb[j])
            here = tree.left[n, np.clip(x, 0, W - 1)]
        else:
            na = np.isnan(x)
            here = x < tree.thr[n]
        out[m] = np.where(na, tree.na_left[n], here)
    return out


def _w_route(job):
    """Move the block's live rows of a level's split nodes to their
    children; with ``children`` the (rows, g) sums of the 2L children."""
    k, L, tree, do, children = job
    s = _rows(k)
    local = _local(s, L)
    lc = np.minimum(local, L - 1)
    alive = _W["alive"][s]
    moves = alive & do[lc]
    idx = np.nonzero(moves)[0]
    cur = _W["cur"][s]
    right = ~_go_left(s, tree, cur[idx], idx)
    cur[idx] = 2 * cur[idx] + 1 + right
    alive[:] = moves
    if children:
        cl = np.where(moves, cur - (2 * L - 1), 2 * L)
        nodes = _W["nodes"][k]
        nodes[0, :2 * L] = np.bincount(cl, minlength=2 * L + 1)[:2 * L]
        nodes[1, :2 * L] = np.bincount(cl, weights=_W["g"][s],
                                       minlength=2 * L + 1)[:2 * L]


def _w_predict(job):
    k, tree, depth = job
    s = _rows(k)
    cur = np.zeros(s.stop - s.start, np.int64)
    for _ in range(depth):
        idx = np.nonzero(tree.col[cur] >= 0)[0]
        right = ~_go_left(s, tree, cur[idx], idx)
        cur[idx] = 2 * cur[idx] + 1 + right
    _W["pred"][s] = tree.value[cur]


def _w_logloss(k: int) -> float:
    s = _rows(k)
    p = 1.0 / (1.0 + np.exp(-_W["F"][s]))
    y = _W["y"][s]
    ll = np.where(y > 0.5, np.log(np.maximum(p, LOG_EPS)),
                  np.log(np.maximum(1.0 - p, LOG_EPS)))
    return float(ll.sum())


class GbmMixedBlockedReference(GbmMixedReference):
    """``GbmMixedReference`` over row blocks and worker processes.  Use
    as a context manager (or call ``close``): the workers live as long
    as the object."""

    def __init__(self, cols: Sequence[np.ndarray], card: Sequence[int],
                 y: np.ndarray, spec: Spec, processes: int = 0,
                 block_rows: int = 0):
        super().__init__(cols, card, y, spec, threads=1)
        self.processes = processes or os.cpu_count() or 1
        R = self.R
        self.block = block_rows or max(1, -(-R // (2 * self.processes)))
        self.nblk = max(1, -(-R // self.block))
        D = spec.max_depth
        Lmax = 2 ** D
        bdt = np.int16 if self.B < 2 ** 15 else np.int32
        global _W
        W = _W = dict(
            R=R, block=self.block, cols=self.cols, y=self.y,
            is_cat=self.is_cat, nb=self.nb, B=self.B, W=self.W,
            bins=_shared((self.C, R), bdt),
            F=_shared((R,), np.float64), g=_shared((R,), np.float64),
            h=_shared((R,), np.float64), gg=_shared((R,), np.float64),
            pred=_shared((R,), np.float64), cur=_shared((R,), np.int64),
            alive=_shared((R,), bool), rows=_shared((R,), bool),
            nodes=_shared((self.nblk, 4, 2 * Lmax), np.float64),
            tab=_shared((self.nblk, 2, self.C, Lmax // 2, self.B + 1),
                        np.float64))
        self._w = W
        self._pool = mp.get_context("fork").Pool(self.processes)

    def close(self) -> None:
        if self._pool is not None:
            self._pool.close()
            self._pool.join()
            self._pool = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _map(self, fn, jobs):
        return self._pool.map(fn, jobs, chunksize=1)

    # -- binning ------------------------------------------------------------

    def prepare(self, program_split_points=None,
                rows: Optional[np.ndarray] = None) -> Dict[str, float]:
        """Own split points (the published float64 rank rule, as
        ``GbmMixedReference.prepare``) and bins; beside them
        ``rank_gap`` and ``split_point_gap`` of ``program_split_points``.
        ``rows`` (a mask) counts only those rows' values for the split
        points: a planted fault."""
        nbins = self.spec.nbins
        num = [c for c in range(self.C) if not self.is_cat[c]]
        if rows is None:
            present = np.sum(self._map(_w_present, range(self.nblk)), axis=0)
        else:
            present = np.array([0 if self.is_cat[c] else int(
                np.count_nonzero(~np.isnan(self.cols[c]) & rows))
                for c in range(self.C)])
        want = {c: quantile_ranks(int(present[c]), nbins) for c in num}
        rule = {c: program_rank_rule(int(present[c]), nbins) for c in num}
        kth = {c: np.unique(np.concatenate([want[c], rule[c]]))
               if present[c] else np.zeros(0, np.int64) for c in num}
        if rows is not None:
            self._w["rows"][:] = rows
        stats = dict(zip(num, self._map(
            _w_order_stats, [(c, kth[c], rows is not None) for c in num])))

        def at(c, ranks):
            return stats[c][np.searchsorted(kth[c], ranks)]

        sps: List[np.ndarray] = []
        for c in range(self.C):
            sps.append(np.unique(at(c, want[c])) if c in num and present[c]
                       else np.zeros(0, np.float32))
        self._map(_w_bin, [(k, sps) for k in range(self.nblk)])
        self.split_points = sps
        self.bins = list(self._w["bins"])
        if program_split_points is None:
            return {}
        progs = [None] * self.C
        gap = 0
        for c in num:
            prog = np.asarray(program_split_points[c], np.float32)
            prog = prog[~np.isnan(prog)]
            progs[c] = prog
            exact = np.unique(at(c, rule[c]) + np.float32(0.0)) \
                if present[c] else np.zeros(0, np.float32)
            m = min(len(prog), len(exact))
            gap += abs(len(prog) - len(exact)) + int(
                np.count_nonzero(prog[:m] != exact[:m]))
        counts = self._map(_w_rank_counts,
                           [(k, progs) for k in range(self.nblk)])
        rank_gap = 0.0
        for c in num:
            lt = np.sum([cnt[c][0] for cnt in counts], axis=0)
            le = np.sum([cnt[c][1] for cnt in counts], axis=0) - 1
            rank_gap = max(rank_gap, rank_gap_from_counts(
                np.asarray(lt, np.int64), np.asarray(le, np.int64),
                want[c]))
        return {"rank_gap": rank_gap, "split_point_gap": float(gap)}

    # -- rows -----------------------------------------------------------------

    def logloss(self, F: np.ndarray) -> float:
        self._w["F"][:] = F
        return -sum(self._map(_w_logloss, range(self.nblk))) / self.R

    def predict(self, tree: Tree) -> np.ndarray:
        self._map(_w_predict, [(k, tree, self.spec.max_depth)
                               for k in range(self.nblk)])
        return np.array(self._w["pred"])

    def _node_totals(self, n: int, L: int) -> List[np.ndarray]:
        return list(np.sum(self._w["nodes"][:, :n, :L], axis=0))

    # -- one tree -----------------------------------------------------------

    def grow(self, F: np.ndarray, tree: Optional[Tree] = None,
             precision: Optional[str] = None, rows=None,
             search: bool = True, cat_by_code: bool = False):
        """``GbmMixedReference.grow``, its row passes made block by
        block; the same arguments and report."""
        sp_, D = self.spec, self.spec.max_depth
        W = self._w
        W["F"][:] = F
        W["rows"][:] = True if rows is None else rows
        blocks = range(self.nblk)
        self._map(_w_grad, [(k, precision) for k in blocks])
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
        for d in range(D):
            L = 2 ** d
            off = L - 1
            sl = slice(off, off + L)
            hist = search or not follow
            self._map(_w_level, [(k, L, hist) for k in blocks])
            w, G, GG, Hs = self._node_totals(4, L)
            live = w > 0
            live_all[sl] = live
            cover[sl] = w
            sep = GG - G ** 2 / np.maximum(w, EPS)
            thresh = np.maximum(
                sp_.min_split_improvement * np.maximum(sep, 0.0), EPS)
            best = np.full(L, -np.inf)
            if hist:
                tab = np.sum(W["tab"][:, :, :, :L], axis=0)
                best, bc, bk, bna, border = self._best_splits(
                    tab[0], tab[1], cat_by_code)
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
            kids = follow and search
            self._map(_w_route, [(k, L, out, do, kids) for k in blocks])
            if kids:
                cw, cG = self._node_totals(2, 2 * L)
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
        self._map(_w_level, [(k, L, False) for k in blocks])
        w, G, _, Hs = self._node_totals(4, L)
        live_all[off:off + L] = w > 0
        cover[off:off + L] = w
        val[off:off + L] = np.where(
            w > 0, sp_.learn_rate * G / np.maximum(Hs, EPS), 0.0)
        report = {"split_gap": float(gaps.max()) if search else None,
                  "terminal": live_all & (col < 0), "cover": cover,
                  "leaf": np.array(W["cur"])}
        return out, report
