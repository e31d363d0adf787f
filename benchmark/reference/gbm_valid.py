"""Plain reference of a GBM job with TWO frames: a training frame and a
validation frame that was parsed on its own, so that each frame has its
own enum domains (the sorted level strings present in THAT file), and
the forest is scored on the validation frame after every tree.

Straightforward numpy in float64, importing nothing of the program.  The
training side is ``benchmark/reference/gbm_mixed.py``'s, unchanged: own
split points and bins, every node's statistics and leaf value, a search
of every candidate, the training log-loss a tree.  What this file adds
is H2O-3's contract for the second frame (``hex/Model.adaptTestForTrain``)
and the per-tree validation log-loss:

* **Levels are matched by their string.**  The reference is handed each
  enum column of both data sets as the generator made it: a level
  IDENTITY a row (a global id that stands for the level's string; below
  0 = missing), never a frame's codes.  The training domain of a column
  is the sorted set of identities present in the training data, as a
  parser leaves it; a training row's code is its level's place in that
  set, and a validation row takes the SAME code by identity.
* **A level training never saw is missing.**  Such a validation row has
  no training code; it is routed as a missing value is: at every node
  that splits on that column it goes the node's NA side.
* **A validation row is routed by the artifact.**  Down each of the
  program's trees by that tree's own columns, thresholds, left sets and
  NA sides, on raw values; the tree's own leaf values are added to a
  float64 F that starts at the artifact's ``f0``, and the validation
  log-loss is taken after each tree.  (What the leaf values should have
  been is the training side's check.)
* **Early stopping** (``stops_at``): H2O-3's ``ScoreKeeper.stopEarly`` for
  a metric that is to fall: with k stopping rounds, after 2k scoring
  points or more the mean of the last k is compared with the mean of
  the k before; training stops unless the newer mean is under the older
  by more than the relative tolerance.

Departures from H2O-3, each on purpose: H2O also warns when a validation
column is absent and fills it with missing values (no cell lacks a
column; the program's tests hold that case); its moving averages use the
same points as here but it can also stop on a NaN metric (not reached).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark.reference.gbm_mixed import GbmMixedReference, Spec, Tree


def training_domain(ids: np.ndarray) -> np.ndarray:
    """The sorted level identities present (missing rows left out)."""
    return np.unique(ids[ids >= 0])


def codes_in(domain: np.ndarray, ids: np.ndarray):
    """``(codes, unseen)``: each row's place in ``domain`` by identity,
    -1 for a missing row and for a level the domain lacks; ``unseen``
    marks the second kind."""
    if len(domain) == 0:
        return np.full(len(ids), -1, np.int32), ids >= 0
    pos = np.clip(np.searchsorted(domain, ids), 0, len(domain) - 1)
    held = (ids >= 0) & (domain[pos] == ids)
    return (np.where(held, pos, -1).astype(np.int32),
            (ids >= 0) & ~held)


def stops_at(history: Sequence[float], rounds: int,
             tolerance: float) -> Optional[int]:
    """The number of scoring points after which a falling metric stops
    training, or None if it never does."""
    k = int(rounds)
    for n in range(2 * k, len(history) + 1) if k > 0 else ():
        recent = float(np.mean(history[n - k:n]))
        before = float(np.mean(history[n - 2 * k:n - k]))
        # departure: H2O multiplies by (1 + tolerance) where the older
        # mean is negative; a log-loss never is
        if not recent < before * (1.0 - tolerance):
            return n
    return None


class GbmValidReference:
    def __init__(self, train_cols: Sequence[np.ndarray],
                 valid_cols: Sequence[np.ndarray], is_enum: Sequence[bool],
                 y_train: np.ndarray, y_valid: np.ndarray, spec: Spec,
                 threads: int = 4):
        """``*_cols``: one array a column, float32 (NaN = missing) for a
        numeric column, int32 level identities (below 0 = missing) for an
        enum one: the same identity means the same level string in both
        data sets."""
        self.is_enum = np.asarray(is_enum, bool)
        self.domains: Dict[int, np.ndarray] = {}
        tcols, vcols, unseen = [], [], []
        for j, (t, v) in enumerate(zip(train_cols, valid_cols)):
            if not self.is_enum[j]:
                tcols.append(t)
                vcols.append(v)
                continue
            dom = self.domains[j] = training_domain(np.asarray(t))
            tcols.append(codes_in(dom, np.asarray(t))[0])
            vc, miss = codes_in(dom, np.asarray(v))
            vcols.append(vc)
            unseen.append(miss)
        # rows that hold a level training never saw, a column at a time
        self.unseen_rows = int(sum(int(m.sum()) for m in unseen))
        self.unseen_any = np.logical_or.reduce(unseen) if unseen \
            else np.zeros(len(y_valid), bool)
        self.card = [len(self.domains[j]) if self.is_enum[j] else 0
                     for j in range(len(tcols))]
        self.train = GbmMixedReference(tcols, self.card, y_train, spec,
                                       threads=threads)
        # the validation rows in TRAINING codes: only routed and scored,
        # never binned
        self.valid = GbmMixedReference(vcols, self.card, y_valid, spec,
                                       threads=threads)

    def prepare(self, program_split_points=None) -> Dict[str, float]:
        return self.train.prepare(program_split_points)

    def follow_valid(self, trees: List[Tree], f0: float):
        """``(F, losses)``: the validation rows' float64 F after the last
        of ``trees`` and the validation log-loss after each."""
        F = np.full(self.valid.R, float(f0))
        losses = []
        for t in trees:
            F = F + np.asarray(t.value, np.float64)[self.valid_leaves(t)]
            losses.append(self.valid.logloss(F))
        return F, losses

    def valid_leaves(self, tree: Tree) -> np.ndarray:
        """The node each validation row ends in, by descent on raw
        values (``GbmMixedReference.predict`` without the value)."""
        v = self.valid
        cur = np.zeros(v.R, np.int64)
        for _ in range(v.spec.max_depth):
            idx = np.nonzero(tree.col[cur] >= 0)[0]
            right = ~v._go_left(tree, cur[idx], idx)
            cur[idx] = 2 * cur[idx] + 1 + right
        return cur

    def check_valid(self, trees: List[Tree], f0: float,
                    history: Dict[int, float],
                    final: Optional[float] = None,
                    program_unseen_rows: Optional[int] = None,
                    probe_rows: Optional[np.ndarray] = None,
                    probe_p1: Optional[np.ndarray] = None
                    ) -> Dict[str, float]:
        """The numbers of the second frame.  ``history`` maps a tree
        count to the validation log-loss the program reported there,
        ``final`` is the log-loss of the validation metrics that ended
        ``train()``, ``program_unseen_rows`` the program's own count, and
        ``probe_p1`` the program's ``predict`` of P(class 1) for the
        validation rows ``probe_rows``, which hold every row with an
        unseen level."""
        F, losses = self.follow_valid(trees, f0)
        out = {"valid_logloss_gap": 0.0, "valid_logloss_points": 0}
        for n, ll in enumerate(losses, start=1):
            if n in history:
                out["valid_logloss_gap"] = max(
                    out["valid_logloss_gap"], abs(history[n] - ll) / ll)
                out["valid_logloss_points"] += 1
        if final is not None:
            out["valid_final_gap"] = abs(float(final) - losses[-1]) \
                / losses[-1]
        if program_unseen_rows is not None:
            out["unseen_rows_gap"] = abs(int(program_unseen_rows)
                                         - self.unseen_rows)
        if probe_rows is not None:
            p = 1.0 / (1.0 + np.exp(-F[probe_rows]))
            gap = np.abs(np.asarray(probe_p1, np.float64) - p)
            un = self.unseen_any[probe_rows]
            # a probe that misses a row with an unseen level proves
            # nothing about it
            out["unseen_rows_unprobed"] = int(self.unseen_any.sum()
                                              - un.sum())
            out["unseen_route_gap"] = float(gap[un].max()) if un.any() \
                else 0.0
            out["probe_gap"] = float(gap.max()) if len(gap) else 0.0
        return out
