"""Shared tree-training driver: chunked XLA blocks + scoring + early stop.

Reference: hex/tree/SharedTree.java ``scoreAndBuildTrees`` (:481-530) — the
per-tree driver loop with periodic ``doScoringAndSaveModel`` and ScoreKeeper
early stopping, and ``resumeFromCheckpoint`` (:465-478).

TPU-native: trees are trained in BLOCKS of ``score_tree_interval`` trees,
each block one fused XLA dispatch (jit_engine.train_forest with the F vector
carried across blocks).  Scoring is INCREMENTAL, and on the training
frame it descends nothing: the block's own carried F (``tf.f_final``: f0 +
offset + checkpoint forest + every kept tree, on every row) IS the training
frame's link-scale prediction, so the metric kernels read it where it lies.
Only a validation frame, whose rows the trainer never sees, keeps a running
F of its own to which the new block's trees are added (one forest_score
over the block); a scoring point then carries both frames' metrics, and
the validation metrics that end ``train()`` are read from that running F.
Either way total scoring work is O(T) — the reference's
per-scoring-round full-model rescore (BigScore over all trees) is avoided
entirely.

OOM DEGRADATION LADDER (core/oom.py): every block launch runs under
``oom_ladder("tree.block", ...)`` — a RESOURCE_EXHAUSTED dispatch first
sweeps the HBM LRU and retries, then HALVES the block size (the smaller
quantum sticks for the rest of the run) and retries again.  Degraded
runs stay bitwise-identical because per-tree RNG keys fold the ABSOLUTE
tree index into the forest master key (jit_engine), so any partition of
the forest into blocks reproduces the same trees.  A terminal OOM (or
any crash) inside a speculative launch first persists the completed-
but-uncheckpointed previous block, so Recovery resumes after it.

DOUBLE-BUFFERING: block *t+1* is DISPATCHED before block *t* is
materialized — the only device->host data t+1 needs is the carried F,
which never leaves the device — and block *t*'s arrays are pulled with
``copy_to_host_async`` so the transfer rides under t+1's compute.  Only
the ScoreKeeper decision point synchronizes (its metrics need host
values); an early stop discards the one speculatively-launched block,
which is why speculative launches never donate their F0 (the stop path
and the training-frame scorer still read the previous block's f_final).
The forest is bitwise the one a single block of ``ntrees`` builds: every
tree's key folds its absolute index into the master key, and a discarded
block's trees are exactly the ones the model never holds.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.chaos import chaos
from h2o_tpu.core.diag import DispatchStats, TimeLine
from h2o_tpu.core.oom import oom_ladder
from h2o_tpu.models.score_keeper import ScoreKeeper


def _set_node_array(model, name: str, new: np.ndarray) -> None:
    """Store a per-node array (gain, cover) covering ALL trees in the
    model (checkpoint resume prepends the checkpoint's values;
    checkpoints trained before the array existed get a zero prefix so
    indexing stays aligned with split_col)."""
    sc_all = np.asarray(model.output["split_col"])
    prior = model.output.get(name)
    if prior is not None and \
            prior.shape[0] + new.shape[0] == sc_all.shape[0]:
        new = np.concatenate([np.asarray(prior), new])
    elif new.shape[0] != sc_all.shape[0]:
        if name == "node_w":
            # fabricated zero covers would make TreeSHAP silently wrong
            # for the checkpoint's trees — keep the loud "retrain to
            # compute contributions" guard instead
            model.output[name] = None
            return
        # thr_bin prefix must be -1 (bitset mode) so checkpoint trees
        # keep their pure-bitset descent semantics; others pad zero
        fill = -1 if name == "thr_bin" else 0
        pad = np.full((sc_all.shape[0] - new.shape[0],) +
                      new.shape[1:], fill, new.dtype)
        new = np.concatenate([pad, new])
    model.output[name] = new


class IncrementalScorer:
    """Link-scale predictions of the growing forest, scored per block.

    to_metrics(F, ntrees_total) -> ModelMetrics converts F (model-specific
    link/vote semantics) and runs the metric kernels on the TRAINING
    frame.  Its F is the one the trainer carries: ``score`` reads the
    block's ``f_final``, descends nothing and keeps no F of its own.

    With ``bins`` (a validation frame's, ``bin_validation_frame``) the
    scorer also keeps that frame's running ``F``, from ``F_init`` (which
    holds ``ntrees`` trees already: a checkpoint's), adds each block's
    trees to it by one descent, and ``valid_metrics(F, ntrees_total)``
    scores it: a scoring point then carries both frames' metrics, the
    validation frame's last (the one a stopping rule reads, as H2O-3's).

    With ``holdout_metrics`` (a fold model of a cross-validated job: the
    fold's rows lie in the training frame at weight 0, and growth routes
    them like any row) the second metrics are read from the SAME carried
    F under the holdout weights (H2O-3's ``cv_makeFoldValid`` frame):
    nothing is binned, nothing descended, no F kept.

    With ``oob`` (DRF) ``to_metrics`` is handed the block's carried
    out-of-bag votes (``tf.oob``: vote sums and tree counts) instead of
    its F, as H2O-3 scores a random forest's training frame.
    """

    def __init__(self, to_metrics: Callable, bins=None, F_init=None,
                 depth: int = 0, fine_na: int = -1,
                 valid_metrics: Optional[Callable] = None,
                 prepared=None, ntrees: int = 0,
                 holdout_metrics: Optional[Callable] = None,
                 holdout_rows: int = 0, oob: bool = False):
        self.to_metrics = to_metrics
        self.oob = oob
        self.valid_metrics = valid_metrics
        self.holdout_metrics = holdout_metrics
        self.holdout_rows = holdout_rows
        self.bins = bins
        self.F = F_init
        self.ntrees = ntrees        # trees summed in ``F``
        self.depth = depth
        self.fine_na = fine_na
        self._prepared = prepared
        self.valid_rows = int(prepared[0]["rows"]) if prepared else 0

    @property
    def is_validation(self) -> bool:
        return self.bins is not None

    @property
    def source(self) -> str:
        """Where ``score`` finds the stopping frame's F: field of span
        train.block.score."""
        if self.is_validation:
            return "descent"
        return "carried_oob" if self.oob else "carried_F"

    def add(self, sc, bs, vl, ch=None, th=None, na=None) -> None:
        from h2o_tpu.core.cloud import donation_enabled
        from h2o_tpu.models.tree.shared_tree import forest_score
        delta = forest_score(
            self.bins, jnp.asarray(sc), jnp.asarray(bs), jnp.asarray(vl),
            self.depth,
            child=jnp.asarray(ch) if ch is not None else None,
            thr=jnp.asarray(th) if th is not None else None,
            na_l=jnp.asarray(na) if na is not None else None,
            fine_na=self.fine_na)
        # donate the running F into the accumulate: the scorer's carry is
        # never read after being replaced, so in-place aliasing is always
        # safe here (unlike the forest F, which speculation may re-read)
        acc = _accum_donate if donation_enabled() else _accum
        self.F = acc(self.F, delta)
        self.ntrees += int(sc.shape[0])

    def score(self, tf, ntrees_total: int):
        """``[(prefix, metrics)]`` of the forest up to and including
        block ``tf``: the training frame's, then the validation frame's
        where there is one."""
        out = [("training_", self.to_metrics(
            tf.oob if self.oob else tf.f_final, ntrees_total))]
        if self.holdout_metrics is not None:
            out.append(("validation_",
                        self.holdout_metrics(tf.f_final, ntrees_total)))
        elif self.is_validation:
            self.add(tf.split_col, tf.bitset, tf.value, tf.child,
                     tf.thr_bin, tf.na_left)
            out.append(("validation_",
                        self.valid_metrics(self.F, ntrees_total)))
            if self._prepared is not None:
                # the metrics above have synced: this fetch waits for
                # nothing
                ev, unseen = self._prepared
                if unseen is not None:
                    ev["unseen_rows"] = int(unseen)
                self._prepared = None
        return out


@jax.jit
@jax.named_scope("h2o.score.metrics")
def _accum(F, delta):
    return F + delta


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("h2o.score.metrics")
def _accum_donate(F, delta):
    return F + delta


def _fit_rows(arr: np.ndarray, want: int) -> np.ndarray:
    """Re-fit a checkpointed per-row carry (F, scorer F) to the CURRENT
    mesh's padded row count.  A checkpoint written on a different mesh
    shape (Cloud.reform) padded to a different row quantum; the valid
    prefix is identical — rows beyond it are masked everywhere — so the
    resize is a pure pad/truncate of the masked tail."""
    arr = np.asarray(arr)
    if arr.shape[0] == want:
        return arr
    if arr.shape[0] > want:
        return arr[:want]
    pad = np.zeros((want - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad])


_CKPT_LISTS = ("scs", "bss", "vls", "chs", "gns", "nws", "ths", "nas")

# TrainedForest fields pulled to the host per block (child, frontier may
# be None)
_BLOCK_FIELDS = ("split_col", "bitset", "value", "child", "node_gain",
                 "node_w", "thr_bin", "na_left", "varimp", "frontier")


def _start_host_pull(tf) -> None:
    """Enqueue async device->host copies of a block's tree arrays so the
    later ``np.asarray`` calls find the bytes already in flight (or
    landed) instead of stalling the pipeline."""
    for name in _BLOCK_FIELDS:
        a = getattr(tf, name)
        if a is not None:
            try:
                a.copy_to_host_async()
            except Exception:  # noqa: BLE001 — optional fast path only;
                return         # np.asarray below stays correct without it


def _block_nbytes(tf) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for name in _BLOCK_FIELDS
               for a in (getattr(tf, name),) if a is not None)


def _split_counts(sc, bs, th, na, is_cat) -> Dict[str, int]:
    """A pulled block's split nodes, counted on the host from the arrays
    the pull already holds: how many there are, how many sit on a
    categorical column, how many send their NA bucket left (a bitset
    split says so in its last bit, a threshold split in ``na_left``)."""
    split = sc >= 0
    na_left = np.where(th >= 0, na, bs[..., -1])
    return {"num_splits": int(split.sum()),
            "cat_splits": int(is_cat[sc[split]].sum()),
            "na_left_splits": int((na_left & split).sum())}


def _frontier_counts(fr: np.ndarray) -> Dict[str, int]:
    """A pulled block's sparse-frontier counters (``TrainedForest.
    frontier``), summed over its trees: the children the cap cut to
    leaves, the children of split nodes above the last level, and the
    levels at which the cap cut."""
    tot = fr.reshape(-1, 3).sum(axis=0)
    return {"frontier_cut": int(tot[0]),
            "frontier_split_children": int(tot[1]),
            "frontier_levels": int(tot[2])}


def _warn_empty_roots(job, node_w: np.ndarray) -> None:
    """A tree whose ROOT covers no row was grown from an empty histogram
    table: with rows to train on that is a fault of the table's build,
    never of the data (a contraction that the chip's compiler emitted
    wrong once zeroed every table of a job, which then returned a forest
    of no split and no error: PERF.md, PR 34).  Read from the cover the
    pull already holds; one warning on the job."""
    if node_w.size and not np.all(node_w[..., 0] > 0):
        job.warn("a tree's root covers no row: its histogram table came "
                 "back empty, so the forest holds trees of no split "
                 "(a fault of the program or its compiler, not of the "
                 "data)")


def run_tree_driver(job, p: Dict, train_kwargs: Dict, F0, key,
                    make_model: Callable,
                    scorer: Optional[IncrementalScorer],
                    kind: str, prior_trees: int = 0,
                    t_start: float = None, recovery=None,
                    data_frame=None, oob0=None) -> object:
    """Train ``p['ntrees']`` total trees (``prior_trees`` of which already
    exist on a checkpoint), scoring every ``score_tree_interval`` trees when
    early stopping / periodic scoring / a runtime budget is requested.

    make_model(sc, bs, vl, ch, n_new, F_final) -> Model; arrays are the
    NEW trees only (the builder prepends checkpoint trees itself); ch is
    None for dense-heap trees.  With ``oob0`` (DRF: (R, K + 1) zeros or
    a resumed carry) the out-of-bag votes are carried block to block
    like F, and make_model is also handed the last block's as ``oob=``.

    ``recovery`` (core/recovery.py Recovery): when attached, the driver
    runs in blocks regardless of scoring and saves an iteration-level
    checkpoint after each block — per-block tree arrays, the carried F,
    and the RNG key — so an interrupted build resumes MID-FOREST and,
    because the random stream continues exactly, reproduces the
    uninterrupted forest bit-for-bit.
    """
    from h2o_tpu.models.tree.jit_engine import (hist_narrow_levels,
                                                program_signature,
                                                resolve_train_levers,
                                                route_plan, train_forest,
                                                window_levels)
    from h2o_tpu.models.tree.shared_tree import (rng_key_from_np,
                                                 rng_key_to_np)

    # pin the tunable-lever flags ONCE for the whole run: every block —
    # including OOM-ladder retries and speculative re-dispatches — hits
    # the same (possibly autotuner-probed) executable, and a probe only
    # ever runs before the first block, never mid-forest
    train_kwargs = resolve_train_levers(dict(train_kwargs))
    if train_kwargs.get("kleaves"):
        # the frontier's split search sorts no bins when no column is
        # categorical: decided here, on the host, once for the forest
        train_kwargs["numeric_only"] = not np.asarray(
            train_kwargs["is_cat"], bool).any()
    # surface the resolved stats carrier on the job (clients see which
    # numeric contract — f32 reference vs quantized int — trained the
    # forest, same visibility rule as effective_max_depth)
    if train_kwargs.get("stats_dtype"):
        p["effective_stats_dtype"] = train_kwargs["stats_dtype"]

    # tiered column store: once binning is done, the RAW frame columns
    # are dead weight for the whole forest — under an HBM budget, demote
    # them to the host tier up front so the budget goes to the packed
    # bins + histograms instead of the ladder discovering this via
    # RESOURCE_EXHAUSTED mid-block (core/memory.py tier manager)
    if data_frame is not None:
        from h2o_tpu.core.memory import manager
        mm = manager()
        if mm.budget > 0:
            data_frame._matrix_cache.clear()
            for v in data_frame.vecs:
                if v._data is not None:
                    mm.demote(v)

    def oob_kw(carry):
        return {} if carry is None else {"oob0": carry}

    def oob_out(carry):
        return {} if carry is None else {"oob": carry}

    ntrees = int(p["ntrees"]) - prior_trees
    if prior_trees and ntrees <= 0:
        raise ValueError(
            f"checkpoint already has {prior_trees} trees >= ntrees="
            f"{p['ntrees']}; raise ntrees to continue training")
    rounds = int(p.get("stopping_rounds") or 0)
    interval = int(p.get("score_tree_interval") or 0)
    if p.get("score_each_iteration"):
        interval = 1
    max_rt = float(p.get("max_runtime_secs") or 0.0)
    t_start = t_start or time.time()

    sk = ScoreKeeper(p.get("stopping_metric", "AUTO"), kind,
                     stopping_rounds=rounds,
                     tolerance=float(p.get("stopping_tolerance", 1e-3)))

    want_scoring = (rounds > 0 or interval > 0 or max_rt > 0) and \
        scorer is not None
    ckpt_every = int(p.get("checkpoint_interval") or 0) \
        if recovery is not None else 0
    if recovery is not None and ckpt_every <= 0:
        ckpt_every = 10                 # default checkpoint cadence
    if (not want_scoring and recovery is None) or ntrees <= 0:
        # single-dispatch path: the OOM ladder can sweep-and-retry but
        # has no block to shrink (the blocked loop below does)
        tf = oom_ladder(
            "tree.block",
            lambda: train_forest(F0=F0, key=key, ntrees=max(ntrees, 0),
                                 t0=prior_trees, **oob_kw(oob0),
                                 **train_kwargs))
        model = make_model(np.asarray(tf.split_col), np.asarray(tf.bitset),
                           np.asarray(tf.value),
                           np.asarray(tf.child)
                           if tf.child is not None else None,
                           max(ntrees, 0), tf.f_final, **oob_out(tf.oob))
        model.output["scoring_history"] = []
        prior_vi = model.output.get("varimp")
        vi = np.asarray(tf.varimp)
        model.output["varimp"] = vi if prior_vi is None else prior_vi + vi
        _set_node_array(model, "node_gain", np.asarray(tf.node_gain))
        _set_node_array(model, "node_w", np.asarray(tf.node_w))
        _warn_empty_roots(job, np.asarray(tf.node_w))
        _set_node_array(model, "thr_bin", np.asarray(tf.thr_bin))
        _set_node_array(model, "na_left", np.asarray(tf.na_left))
        return model

    if interval > 0:
        block = min(interval, ckpt_every) if ckpt_every else interval
    else:
        block = ckpt_every or max(1, min(ntrees, 10))
    # host copy of a (C,) flag the builder made from a numpy array: read
    # once, before any block is queued
    is_cat_host = np.asarray(train_kwargs["is_cat"], bool)
    lists = {n: [] for n in _CKPT_LISTS}
    scs, bss, vls, chs = (lists[n] for n in ("scs", "bss", "vls", "chs"))
    gns, nws, ths, nas = (lists[n] for n in ("gns", "nws", "ths", "nas"))
    vi_total = None
    F, OOB = F0, oob0
    done = 0
    if recovery is not None:
        st = recovery.load_iteration()
        # resume only a checkpoint of THIS build shape — a stale state
        # from different params must not leak trees in
        if st and st.get("kind") == "tree" and \
                st.get("prior_trees") == prior_trees and \
                st.get("ntrees_target") == ntrees and \
                st.get("block") == block:
            done = int(st["done"])
            F = jnp.asarray(_fit_rows(st["F"], int(F0.shape[0])))
            if OOB is not None and st.get("oob") is not None:
                OOB = jnp.asarray(_fit_rows(st["oob"], int(F0.shape[0])))
            key = rng_key_from_np(st["key"])
            for n in _CKPT_LISTS:
                lists[n].extend(st["lists"][n])
            vi_total = st.get("vi_total")
            if st.get("sk") is not None:
                sk = st["sk"]
            # a training-frame scorer keeps no F (checkpoints written when
            # it did still carry one: not restored)
            if scorer is not None and scorer.is_validation and \
                    st.get("scorer_F") is not None:
                scorer.F = jnp.asarray(_fit_rows(
                    st["scorer_F"], int(scorer.F.shape[0])))
                scorer.ntrees = prior_trees + done
            job.update(0.05 + 0.85 * done / ntrees,
                       f"resumed mid-forest at {prior_trees + done} trees")
    may_stop = (rounds > 0 and scorer is not None) or max_rt > 0
    # speculative launches must not donate their F0: on an early stop /
    # runtime-budget break the discarded block's INPUT (the last kept
    # block's f_final) is still read by make_model, recovery checkpoints
    # np.asarray the post-block F after the next block has already been
    # dispatched, and a scorer reads block t's f_final as the training
    # frame's F when block t is absorbed, after t+1's launch.  With none
    # of those readers the default donation policy applies — the carry
    # is then written in place across blocks.
    donate_launch = False if (
        may_stop or recovery is not None or scorer is not None) else None
    launched = done
    no_donate = False       # latched by the OOM ladder: retries re-read F
    # a tree's routed levels, and those the select form routes; its
    # window levels and its levels of the narrow histogram form: the
    # rules the engine applies to each level's static shape, on the host
    route_levels, route_select_levels = route_plan(train_kwargs)
    n_window = window_levels(train_kwargs)
    n_narrow = hist_narrow_levels(train_kwargs)

    def _launch(off: int, n: int) -> Dict:
        nonlocal F, OOB, block, no_donate
        # Slice-loss choke point: a lost/preempted slice surfaces HERE,
        # at the block dispatch, as a RESUMABLE interrupt — every
        # already-absorbed block is durably checkpointed, the job layer
        # reclassifies the loss as INTERRUPTED (not FAILED), and the
        # membership recovery protocol replays this build from the last
        # block boundary on the reformed mesh, bitwise.
        if chaos().enabled:
            chaos().maybe_lose_slice("tree.block")
        # Per-tree RNG folds the ABSOLUTE tree index into the forest
        # master key (jit_engine), so every block receives the SAME
        # master key and any partition — including an OOM-degraded
        # halving below — reproduces the identical forest bitwise.
        F_in, OOB_in = F, OOB
        state = {"n": n}

        def attempt():
            return train_forest(F0=F_in, key=key, ntrees=state["n"],
                                t0=prior_trees + off,
                                donate=False if no_donate
                                else donate_launch,
                                **oob_kw(OOB_in), **train_kwargs)

        def shrink() -> bool:
            # OOM-ladder rung (b): halve the block; the smaller quantum
            # sticks for the rest of the run (stay degraded, stay alive)
            nonlocal block
            if state["n"] <= 1:
                return False
            state["n"] //= 2
            block = min(block, state["n"])
            return True

        def on_oom(_e):
            # a retried dispatch re-reads F_in — never donate it again
            nonlocal no_donate
            no_donate = True

        with TimeLine.span("train", "block.launch", t0=prior_trees + off,
                           route_levels=route_levels,
                           route_select_levels=route_select_levels,
                           window_levels=n_window,
                           hist_narrow_levels=n_narrow) as ev:
            tf, ici = DispatchStats.program_ici(
                ("tree.block", state["n"], donate_launch, no_donate,
                 program_signature(train_kwargs)),
                lambda: oom_ladder("tree.block", attempt, shrink=shrink,
                                   on_oom=on_oom))
            # a tree's collectives lie in the body of the scan over the
            # block's trees: noted once, shipped once a tree
            ev["ici_bytes"] = state["n"] * sum(ici.values())
            F, OOB = tf.f_final, tf.oob
            _start_host_pull(tf)
        TimeLine.record("dispatch", "tree_block_launch",
                        t0=prior_trees + off, n=state["n"])
        # key_after: the master key is block-invariant, so a checkpoint
        # resumed at any block boundary continues the same stream
        return {"tf": tf, "n": state["n"], "off": off, "key_after": key}

    def _absorb(cur: Dict) -> bool:
        """Materialize block ``cur``, fold it into the model state,
        score it, and write its recovery checkpoint; returns the early-
        stop decision.  Shared by the happy path and the crash path
        below (a speculative launch that dies must not lose the
        already-completed previous block)."""
        nonlocal vi_total, done
        tf, n = cur["tf"], cur["n"]
        # spans are HOST time: block t+1 is already queued, so "score"
        # also waits for t+1's build
        with TimeLine.span("train", "block.absorb",
                           t0=prior_trees + cur["off"], n=n):
            with TimeLine.span("train", "block.pull") as pulled:
                chaos().maybe_slow_transfer("tree_block")
                scs.append(np.asarray(tf.split_col))
                bss.append(np.asarray(tf.bitset))
                vls.append(np.asarray(tf.value))
                if tf.child is not None:
                    chs.append(np.asarray(tf.child))
                gns.append(np.asarray(tf.node_gain))
                nws.append(np.asarray(tf.node_w))
                ths.append(np.asarray(tf.thr_bin))
                nas.append(np.asarray(tf.na_left))
                vi = np.asarray(tf.varimp)
                pulled.update(_split_counts(scs[-1], bss[-1], ths[-1],
                                            nas[-1], is_cat_host))
                if tf.frontier is not None:
                    pulled.update(_frontier_counts(np.asarray(tf.frontier)))
            _warn_empty_roots(job, nws[-1])
            TimeLine.record("dispatch", "tree_block_materialize",
                            t0=prior_trees + cur["off"], n=n)
            DispatchStats.note_transfer("tree_block", _block_nbytes(tf))
            vi_total = vi if vi_total is None else vi_total + vi
            done += n
            stop = False
            if scorer is not None:
                with TimeLine.span(
                        "train", "block.score", source=scorer.source,
                        valid_rows=scorer.valid_rows,
                        holdout_rows=scorer.holdout_rows):
                    row = {"number_of_trees": prior_trees + done,
                           "timestamp": time.time()}
                    points = scorer.score(tf, prior_trees + done)
                    for prefix, mm in points:
                        for k in ("mse", "logloss", "AUC",
                                  "mean_residual_deviance", "err"):
                            if mm.get(k) is not None:
                                row[prefix + k.lower()] = mm.get(k)
                    # the stopping rule reads the last frame scored: the
                    # validation frame where there is one
                    sk.add(points[-1][1], row)
                job.update(0.05 + 0.85 * done / ntrees,
                           f"{prior_trees + done} trees, "
                           f"{sk.metric_name}={sk.history[-1]:.5g}")
                if sk.stop_early():
                    job.update(0.9,
                               f"early stop at {prior_trees + done} trees")
                    stop = True
            else:
                job.update(0.05 + 0.85 * done / ntrees,
                           f"{prior_trees + done} trees")
            if recovery is not None:
                with TimeLine.span("train", "block.checkpoint"):
                    recovery.save_iteration(
                        {"kind": "tree", "prior_trees": prior_trees,
                         "ntrees_target": ntrees, "block": block,
                         "done": done, "F": np.asarray(tf.f_final),
                         "oob": np.asarray(tf.oob)
                         if tf.oob is not None else None,
                         "key": rng_key_to_np(cur["key_after"]),
                         "lists": lists, "vi_total": vi_total, "sk": sk,
                         # a training-frame scorer's F is "F" above
                         "scorer_F": np.asarray(scorer.F)
                         if scorer is not None and scorer.is_validation
                         else None},
                        meta={"kind": "tree",
                              "trees_done": prior_trees + done,
                              "ntrees": int(p["ntrees"])})
        return stop

    pend = None
    if done < ntrees:
        pend = _launch(launched, min(block, ntrees - launched))
        launched += pend["n"]
    while done < ntrees:
        cur = pend
        pend = None
        if launched < ntrees:
            # dispatch block t+1 BEFORE materializing block t — the
            # host pulls below overlap its device build; only the
            # ScoreKeeper decision point below synchronizes
            try:
                pend = _launch(launched, min(block, ntrees - launched))
                launched += pend["n"]
            except BaseException:
                # the speculative launch died (crash, terminal OOM)
                # with block t complete on device but NOT yet
                # checkpointed — persist it best-effort before
                # propagating, so Recovery resumes AFTER it instead
                # of losing it (durability beats overlap on the
                # death path)
                if recovery is not None and cur is not None:
                    try:
                        _absorb(cur)
                        cur = None
                    except BaseException:  # noqa: BLE001
                        pass               # dying anyway
                raise
        tf = cur["tf"]
        stop = _absorb(cur)
        if not stop and max_rt > 0 and time.time() - t_start > max_rt:
            job.update(0.9, f"max_runtime_secs hit at {done} trees")
            stop = True
        if stop:
            if pend is not None:
                # discard the speculative block: its trees are not part
                # of the model; roll the carry back to the last kept
                # block (valid — speculative launches never donate F0)
                F, OOB = tf.f_final, tf.oob
                pend = None
            break
    model = make_model(np.concatenate(scs), np.concatenate(bss),
                       np.concatenate(vls),
                       np.concatenate(chs) if chs else None, done, F,
                       **oob_out(OOB))
    model.output["scoring_history"] = sk.events
    _set_node_array(model, "node_gain", np.concatenate(gns))
    _set_node_array(model, "node_w", np.concatenate(nws))
    _set_node_array(model, "thr_bin", np.concatenate(ths))
    _set_node_array(model, "na_left", np.concatenate(nas))
    prior_vi = model.output.get("varimp")
    if vi_total is not None:
        model.output["varimp"] = vi_total if prior_vi is None \
            else prior_vi + vi_total
    return model
