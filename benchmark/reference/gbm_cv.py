"""Plain reference of a CROSS-VALIDATED histogram GBM job: ``nfolds``
fold models and the main model on one frame, the holdout predictions
combined and scored once.

Straightforward numpy in float64, importing nothing of the program; the
numerics of one model (bins, node statistics, Newton leaves, the search
of every candidate, the log-loss) are ``benchmark/reference/gbm.py``'s,
by import.  What this file adds is H2O-3's contract for the job
(``hex/ModelBuilder.computeCrossValidation``, h2o-docs
``cross-validation.rst``):

* **The folds partition the rows.**  Modulo: row r is in fold
  ``r % nfolds``.  The reference makes its own ids and counts the rows
  on which the program's fold-assignment frame differs.
* **A fold model never sees its fold.**  Fold model i is the
  configuration's model trained with the rows of fold i at weight 0 in
  EVERY statistic: the prior ``f0``, each node's row count, gradient and
  hessian sums, the ``min_rows`` test, the leaf values.  (The split
  points are those of all rows: binning reads no weights.)  The
  reference *follows* one fold model as ``gbm.py`` follows a model, with
  that mask, and for every fold model holds the root's cover to the
  count of rows outside the fold.
* **A row's holdout prediction comes from the one model that never saw
  it.**  The reference routes the rows of fold i down fold model i's
  trees as the artifact states them (columns, thresholds, leaf values),
  carries a float64 F from the artifact's ``f0``, and compares P(class
  1) row by row with the combined holdout frame; the holdout log-loss
  after each tree with the fold model's scoring history (its stopping
  frame is its fold: ``cv_makeFoldValid``).
* **``cross_validation_metrics`` is the metric of the combined holdout
  predictions over all rows**; the summary holds the same metric fold by
  fold.
* **The main model is the configuration's model on all rows**: its
  root's cover, and its training log-loss a tree by the reference's own
  routing of the artifact (its search is the one-model cell's to hold).

Departures from H2O-3, on purpose: H2O-3 scores a fold model's holdout
through a copy of the frame restricted to the fold; the answers are the
same rows' and are compared as such.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark.reference.gbm import (EPS, LOG_EPS, GbmReference, Spec,
                                     Tree)

__all__ = ["GbmCvReference", "FoldReference", "FoldAnswers", "Spec", "Tree"]


def modulo_folds(rows: int, nfolds: int) -> np.ndarray:
    return (np.arange(rows) % nfolds).astype(np.int32)


def logloss_of(F: np.ndarray, y: np.ndarray) -> float:
    p = 1.0 / (1.0 + np.exp(-F))
    ll = np.where(y > 0.5, np.log(np.maximum(p, LOG_EPS)),
                  np.log(np.maximum(1.0 - p, LOG_EPS)))
    return float(-ll.mean())


def auc_of(p: np.ndarray, y: np.ndarray) -> float:
    """Exact AUC by the rank sum, ties at their mean rank."""
    order = np.argsort(p, kind="stable")
    ps = p[order]
    ranks = np.empty(len(p), np.float64)
    start = np.flatnonzero(np.r_[True, ps[1:] != ps[:-1]])
    end = np.r_[start[1:], len(p)]
    ranks[order] = np.repeat((start + end + 1) / 2.0, end - start)
    pos = y > 0.5
    n1, n0 = int(pos.sum()), int((~pos).sum())
    if n1 == 0 or n0 == 0:
        return float("nan")
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


class FoldReference(GbmReference):
    """``GbmReference`` with the rows outside ``rows_in`` at weight 0 in
    every statistic; it shares the prepared bins of ``base``."""

    def __init__(self, base: GbmReference, rows_in: np.ndarray):
        self.__dict__.update(base.__dict__)
        self.rows_in = rows_in

    def init_f0(self) -> float:
        p = min(max(float(self.y[self.rows_in].mean()), EPS), 1 - EPS)
        return float(np.log(p / (1 - p)))

    def logloss(self, F: np.ndarray) -> float:
        return logloss_of(F[self.rows_in], self.y[self.rows_in])

    def grow(self, F, tree: Optional[Tree] = None, precision=None,
             rows=None, search: bool = True):
        mask = self.rows_in if rows is None else rows & self.rows_in
        return super().grow(F, tree=tree, precision=precision, rows=mask,
                            search=search)


class FoldAnswers(dict):
    """One model of the job as the program (or the reference in its
    place) states it: ``trees`` (``Tree`` in raw thresholds), ``f0``,
    ``root_cover`` (a number a tree), ``train_history`` and
    ``holdout_history`` (tree count -> log-loss), ``planned``."""


class GbmCvReference:
    def __init__(self, X: np.ndarray, y: np.ndarray, spec: Spec,
                 nfolds: int, threads: int = 4):
        self.base = GbmReference(X, y, spec, threads=threads)
        self.nfolds = int(nfolds)
        self.fold = modulo_folds(self.base.R, self.nfolds)

    @property
    def R(self) -> int:
        return self.base.R

    def prepare(self, program_split_points=None) -> Dict[str, float]:
        return self.base.prepare(program_split_points)

    def fold_view(self, i: int) -> FoldReference:
        return FoldReference(self.base, self.fold != i)

    # -- routing by the artifact ------------------------------------------

    def leaf_values(self, tree: Tree, idx: np.ndarray) -> np.ndarray:
        """The artifact tree's value for the rows ``idx``, by descent on
        raw values."""
        X, cur = self.base.X, np.zeros(len(idx), np.int64)
        for _ in range(self.base.spec.max_depth):
            c = tree.col[cur]
            x = X[np.maximum(c, 0), idx]
            right = ~(x < tree.thr[cur])
            cur = np.where(c >= 0, 2 * cur + 1 + right, cur)
        return np.asarray(tree.value, np.float64)[cur]

    def follow_rows(self, trees: Sequence[Tree], f0: float,
                    idx: np.ndarray):
        """``(F, losses)``: the rows' float64 F after the last of
        ``trees`` and their log-loss after each."""
        F = np.full(len(idx), float(f0))
        y = self.base.y[idx]
        losses = []
        for t in trees:
            F = F + self.leaf_values(t, idx)
            losses.append(logloss_of(F, y))
        return F, losses

    # -- the comparison ---------------------------------------------------

    def check_job(self, fold_models: List[FoldAnswers], main: FoldAnswers,
                  fold_assignment: np.ndarray, holdout_p1: np.ndarray,
                  cv_logloss: float, cv_auc: Optional[float],
                  fold_loglosses: Sequence[float], followed: int,
                  search_trees: int = 1) -> Dict[str, float]:
        """The numbers that decide ``correct`` for the whole job."""
        R, y = self.R, self.base.y
        out: Dict[str, float] = {}
        out["fold_gap"] = int(
            len(fold_assignment) != R or
            np.sum(np.asarray(fold_assignment).astype(np.int64)
                   != self.fold))
        out["trees_missing"] = sum(
            int(m["planned"]) - len(m["trees"])
            for m in list(fold_models) + [main]) \
            + (self.nfolds - len(fold_models)) * int(main["planned"])
        holdout_p1 = np.asarray(holdout_p1, np.float64)
        out["holdout_rows_missing"] = int(
            R - np.isfinite(holdout_p1[:R]).sum())

        # every fold model: cover, holdout rows by the artifact's trees
        cover_gap = 0.0
        F_all = np.full(R, np.nan)
        pts_missing, ll_gap, fold_gap = 0, 0.0, 0.0
        for i, m in enumerate(fold_models):
            idx = np.flatnonzero(self.fold == i)
            for c in m["root_cover"]:
                cover_gap = max(cover_gap, abs(float(c) - (R - len(idx))))
            F, losses = self.follow_rows(m["trees"], m["f0"], idx)
            F_all[idx] = F
            for n in range(1, len(m["trees"]) + 1):
                if n in m["holdout_history"]:
                    ll_gap = max(ll_gap, abs(m["holdout_history"][n]
                                             - losses[n - 1])
                                 / losses[n - 1])
                else:
                    pts_missing += 1
            if i < len(fold_loglosses) and losses:
                fold_gap = max(fold_gap, abs(float(fold_loglosses[i])
                                             - losses[-1]) / losses[-1])
            else:
                fold_gap = float("inf")
        for c in main["root_cover"]:
            cover_gap = max(cover_gap, abs(float(c) - R))
        out["root_cover_gap"] = cover_gap
        out["holdout_points_missing"] = pts_missing
        out["holdout_logloss_gap"] = ll_gap
        out["cv_fold_gap"] = fold_gap
        p_ref = 1.0 / (1.0 + np.exp(-F_all))
        gap = np.abs(holdout_p1[:R] - p_ref)
        out["holdout_pred_gap"] = float(np.nanmax(gap)) \
            if np.isfinite(gap).any() else float("inf")
        cv_ref = logloss_of(F_all, y)
        out["cv_logloss_gap"] = abs(float(cv_logloss) - cv_ref) / cv_ref
        if cv_auc is not None:
            out["cv_auc_gap"] = abs(float(cv_auc) - auc_of(p_ref, y))

        # the main model: its training log-loss by the artifact's trees
        _, losses = self.follow_rows(main["trees"], main["f0"],
                                     np.arange(R))
        out["main_logloss_gap"] = max(
            [abs(main["train_history"][n] - losses[n - 1]) / losses[n - 1]
             for n in range(1, len(losses) + 1)
             if n in main["train_history"]] or [float("inf")])

        # one fold model followed as gbm.py follows a model
        m = fold_models[followed]
        nums = self.fold_view(followed).check_forest(
            m["trees"], m["f0"], m["train_history"], search_trees)
        nums.pop("logloss_points")
        out.update(nums)
        return out

    # -- the reference in the program's place -----------------------------

    def build_job(self, ntrees: int, leak: bool = False):
        """``(fold_models, main)`` as ``check_job`` takes them, grown by
        the reference itself.  ``leak``: every fold model counts its own
        fold's rows (planted fault (a))."""
        def answers(ref: GbmReference, idx_hold) -> FoldAnswers:
            trees, f0, history = ref.build_forest(ntrees)
            # a root covers the rows its model counts
            cover = [float(getattr(ref, "rows_in",
                                   np.ones(self.R, bool)).sum())] * ntrees
            hold = {}
            if idx_hold is not None:
                _, losses = self.follow_rows(trees, f0, idx_hold)
                hold = dict(enumerate(losses, start=1))
            return FoldAnswers(trees=trees, f0=f0, root_cover=cover,
                               train_history=history,
                               holdout_history=hold, planned=ntrees)

        folds = [answers(self.base if leak else self.fold_view(i),
                         np.flatnonzero(self.fold == i))
                 for i in range(self.nfolds)]
        return folds, answers(self.base, None)

    def holdout_F(self, fold_models: List[FoldAnswers],
                  shift: int = 0, upto: Optional[int] = None) -> np.ndarray:
        """The combined holdout F: fold i's rows by fold model ``i +
        shift``'s first ``upto`` trees."""
        F_all = np.empty(self.R)
        for i in range(self.nfolds):
            idx = np.flatnonzero(self.fold == i)
            m = fold_models[(i + shift) % self.nfolds]
            F_all[idx], _ = self.follow_rows(m["trees"][:upto], m["f0"],
                                             idx)
        return F_all
