"""Model / ModelBuilder lifecycle.

Reference: hex/ModelBuilder.java:25 (param validation → async Driver →
train → metrics; n-fold CV at :535-690) and hex/Model.java (score() →
BigScore MRTask → per-row score0 + MetricBuilder reduce, Model.java:1866,
2189-2269).

TPU-native: the Driver runs as a host Job; per-row score0 loops become one
batched jit ``predict`` over the row-sharded matrix (BigScore ≡ the XLA
program; the MetricBuilder reduce ≡ the fused metric kernels in metrics.py).
Models are host objects in the DKV holding device parameter pytrees.
"""

from __future__ import annotations

import dataclasses
import functools
import pickle
import time
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.cloud import cloud
from h2o_tpu.core.diag import TimeLine
from h2o_tpu.core.frame import (Frame, T_CAT, Vec, _codes_in_domain,
                                domain_table, table_unseen_levels)
from h2o_tpu.core.job import Job
from h2o_tpu.core.landing import reshard_rows
from h2o_tpu.core.log import get_logger
from h2o_tpu.core.store import Key
from h2o_tpu.models import metrics as mm

log = get_logger("model")


class DataInfo:
    """Feature extraction/encoding (reference: hex/DataInfo.java:23,112-115).

    modes:
    - "tree":     categoricals stay integer codes (one bin per category);
                  NAs stay NaN (trees route them via the NA bucket).
    - "expanded": one-hot categorical expansion + optional standardization +
                  NA mean-imputation — the GLM/DL/KMeans input convention.
    """

    def __init__(self, frame: Frame, x: Sequence[str], y: Optional[str],
                 mode: str = "tree", weights: Optional[str] = None,
                 offset: Optional[str] = None, standardize: bool = False,
                 use_all_factor_levels: bool = False,
                 impute_missing: bool = False):
        self.frame = frame
        self.mode = mode
        self.response_name = y
        self.weights_name = weights
        self.offset_name = offset
        self.x = [c for c in x if c not in (y, weights, offset)]
        # batch-fill rollups for every candidate column in one kernel call
        frame.fill_rollups([c for c in self.x
                            if frame.vec(c).data is not None])
        # ignore constant cols (ignore_const_cols default, ModelBuilder)
        kept = []
        for c in self.x:
            v = frame.vec(c)
            if v.type in ("string", "uuid"):
                continue
            if v.is_categorical and v.cardinality <= 1:
                continue
            if v.is_numeric and v.rollups.sigma == 0:
                continue
            kept.append(c)
        self.x = kept
        self.cat_names = [c for c in self.x if frame.vec(c).is_categorical]
        self.num_names = [c for c in self.x if not frame.vec(c).is_categorical]
        # tree mode keeps frame column order; expanded puts cats first
        # (reference DataInfo puts categoricals before numerics)
        self.standardize = standardize
        self.use_all_factor_levels = use_all_factor_levels
        self.impute_missing = impute_missing
        self._matrix = None
        self._names_expanded: Optional[List[str]] = None

    # -- response/weights ---------------------------------------------------

    def response(self) -> jax.Array:
        v = self.frame.vec(self.response_name)
        if v.is_categorical:
            return jnp.where(v.data < 0, jnp.nan,
                             v.data.astype(jnp.float32))
        return v.data

    @property
    def response_domain(self) -> Optional[List[str]]:
        v = self.frame.vec(self.response_name)
        return v.domain

    @property
    def nclasses(self) -> int:
        d = self.response_domain
        return len(d) if d else 1

    def weights(self) -> jax.Array:
        if self.weights_name:
            return self.frame.vec(self.weights_name).data
        # row-sharded as the frame's columns: each shard makes its own
        # ones, no whole-frame vector on one device to move every call
        from h2o_tpu.core.cloud import hbroadcast_rows
        return hbroadcast_rows(1.0, self.frame.padded_rows)

    def offset(self) -> Optional[jax.Array]:
        return self.frame.vec(self.offset_name).data if self.offset_name \
            else None

    def valid_mask(self) -> jax.Array:
        """Rows usable for training: in-range and response present."""
        m = self.frame.row_mask()
        if self.response_name:
            m = m & ~jnp.isnan(self.response())
        return m

    # -- feature matrix -----------------------------------------------------

    def matrix(self) -> jax.Array:
        if self._matrix is not None:
            return self._matrix
        if self.mode == "tree":
            self._matrix = self.frame.as_matrix(self.x)
            self._names_expanded = list(self.x)
        else:
            cols, names = [], []
            for c in self.cat_names:
                v = self.frame.vec(c)
                codes = v.data
                lo = 0 if self.use_all_factor_levels else 1
                for k in range(lo, v.cardinality):
                    cols.append((codes == k).astype(jnp.float32))
                    names.append(f"{c}.{v.domain[k]}")
            for c in self.num_names:
                v = self.frame.vec(c)
                d = v.as_float()
                if self.impute_missing:
                    d = jnp.nan_to_num(d, nan=v.rollups.mean)
                if self.standardize:
                    sd = v.rollups.sigma or 1.0
                    d = (d - v.rollups.mean) / sd
                cols.append(d)
                names.append(c)
            m = jnp.stack(cols, axis=1) if cols else jnp.zeros(
                (self.frame.padded_rows, 0), jnp.float32)
            from h2o_tpu.core import landing
            self._matrix = landing.reshard_rows(m, cloud().matrix_sharding())
            self._names_expanded = names
        return self._matrix

    @property
    def expanded_names(self) -> List[str]:
        if self._names_expanded is None:
            self.matrix()
        return self._names_expanded


class Adapted(NamedTuple):
    """A frame as a model's scorer reads it (``adapt_frame``)."""
    matrix: jax.Array          # (padded_rows, len(x)) float32
    remapped: Tuple[str, ...]  # enum columns whose codes went through a table
    absent: Tuple[str, ...]    # columns the frame lacks: all NA
    unseen_levels: int         # levels of the frame's domains training lacks
    # device int32 scalar: rows, a column at a time, that hold such a
    # level; None when no column was remapped (no program ran)
    unseen_rows: Optional[jax.Array]


def adapt_frame(frame: Frame, x: Sequence[str],
                domains: Optional[Dict[str, List[str]]],
                warn=None) -> Adapted:
    """H2O-3's ``adaptTestForTrain`` contract, on the device: ``frame``'s
    columns ``x`` as the matrix a model trained on ``domains`` scores.
    An enum column is matched to training's by level STRING (the
    frame's own codes mean nothing to the model: a file parsed on its
    own holds the sorted levels present in THAT file); a level training
    never saw is NA, which binning sends to the NA bucket and every node
    to its NA side; a column the frame lacks is all NA, with one
    warning (``warn``: a job's, else the log).  Equal domains cost
    nothing: the frame's cached ``as_matrix``."""
    tables = {}
    for c in x:
        if c in frame and c in (domains or {}) and \
                frame.vec(c).is_categorical:
            t = domain_table(frame.vec(c).domain, domains[c])
            if t is not None:
                tables[c] = t
    absent = tuple(c for c in x if c not in frame)
    if absent:
        (warn or log.warning)(
            f"frame {frame.key} lacks column(s) {', '.join(absent)} the "
            "model was trained on: scored as missing values")
    if not tables and not absent:
        return Adapted(frame.as_matrix(x), (), (), 0, None)
    matrix, unseen_rows = frame.as_matrix_in_domains(x, tables)
    return Adapted(
        matrix, tuple(tables), absent,
        sum(table_unseen_levels(t, frame.vec(c).domain)
            for c, t in tables.items()),
        unseen_rows if tables else None)


def _raw_to_frame(raw, nrows: int, dom: Optional[List[str]]) -> Frame:
    """raw predictions -> prediction Frame ([predict, p0..pK-1] layout)."""
    raw = jnp.asarray(raw)
    if dom is None:
        return Frame(["predict"], [Vec(raw, nrows=nrows)])
    names = ["predict"] + list(dom)
    vecs = [Vec(raw[:, 0].astype(jnp.int32), T_CAT, nrows=nrows,
                domain=list(dom))]
    for k in range(len(dom)):
        vecs.append(Vec(raw[:, 1 + k], nrows=nrows))
    return Frame(names, vecs)


class Folds(NamedTuple):
    """A cross-validated job's fold assignment."""
    ids: jax.Array      # (padded_rows,) int32 on the device; a pad row -1
    n: int
    scheme: str         # modulo | random | stratified | fold_column
    counts: np.ndarray  # (n,) rows of each fold, counted on the host


@dataclasses.dataclass
class CVFold:
    """What a model of a cross-validated job is handed in place of frames
    of its own, by a builder whose ``_cv_shared`` prepares something: the
    job's shared preparation, and for a FOLD model the two weight vectors
    over the job's frame.  The main model gets ``shared`` alone."""
    shared: Any
    weights: Optional[jax.Array] = None   # the user's; 0 on the fold's rows
    holdout: Optional[jax.Array] = None   # the user's on the fold's rows
    holdout_rows: int = 0
    # set by ``_fit``: the model's raw predictions on EVERY row of the
    # job's frame, from the F it carried (the fold's rows among them)
    raw: Optional[jax.Array] = None


@functools.partial(jax.jit, static_argnames=("padded", "n"))
@jax.named_scope("h2o.cv.folds")
def _modulo_folds(nrows, padded: int, n: int):
    r = jnp.arange(padded, dtype=jnp.int32)
    return jnp.where(r < nrows, r % n, -1)


@jax.jit
@jax.named_scope("h2o.cv.weights")
def _fold_weights(fold, user_w, i):
    """Fold ``i``'s (training weights, holdout weights): H2O-3's
    ``cv_makeWeights``."""
    hold = fold == i
    return jnp.where(hold, 0.0, user_w), jnp.where(hold, user_w, 0.0)


@jax.jit
@jax.named_scope("h2o.cv.select")
def _select_holdout(fold, i, raw, combined):
    """Fold ``i``'s rows of ``raw`` into the combined holdout predictions."""
    hold = fold == i
    return jnp.where(hold[:, None] if raw.ndim == 2 else hold, raw,
                     combined)


class Model:
    """A trained model: params + output, DKV-visible, scoring capable."""

    algo: str = "base"

    def __init__(self, key: Optional[str], params: Dict[str, Any],
                 output: Dict[str, Any]):
        self.key = Key(key) if key else Key.make(self.algo)
        self.params = params
        self.output = output  # names, domains, training_metrics, ...
        self.run_time_ms = 0

    # -- scoring ------------------------------------------------------------

    def predict_raw(self, frame: Frame) -> jax.Array:
        """Device predictions over padded rows: (rows,) regression values or
        (rows, 1+K) [label, p0..pK-1] for classification."""
        raise NotImplementedError

    def scoring_matrix(self, frame: Frame) -> jax.Array:
        """``frame`` as this model's scorer reads it: columns
        ``output['x']`` in the training domains (``adapt_frame``)."""
        return adapt_frame(frame, self.output["x"],
                           self.output.get("domains")).matrix

    def predict(self, frame: Frame) -> Frame:
        """Public scoring: returns a Frame (the /3/Predictions surface)."""
        return _raw_to_frame(self.predict_raw(frame), frame.nrows,
                             self.output.get("response_domain"))

    # -- online fast path (serve/engine.py) ---------------------------------

    def predict_raw_array(self, X) -> jax.Array:
        """Device predictions over a raw (rows, len(output['x'])) matrix
        of column values in training order (categoricals as domain
        codes, NAs as NaN) — no Frame, no DKV, shape-stable so the
        serving engine can jit it per batch bucket.  Families with a
        device scoring path override this (GBM/DRF/XGBoost/GLM);
        ``predict_raw(frame)`` delegates to it where possible."""
        raise NotImplementedError(
            f"{self.algo} has no device array-predict fast path")

    def predict_array(self, X: np.ndarray) -> np.ndarray:
        """Online scoring entry: raw ndarray in, raw predictions out —
        never round-trips through a DKV Frame.  Uses the device fast
        path when the model family implements one, else the pure-numpy
        MOJO scorer over the same flattened artifact arrays."""
        X = np.asarray(X)
        try:
            return np.asarray(self.predict_raw_array(
                jnp.asarray(X, jnp.float32)))
        except NotImplementedError:
            pass
        from h2o_tpu.mojo import _flatten_arrays, scorers
        fn = getattr(scorers, f"score_{self.algo}", None)
        if fn is None:
            raise NotImplementedError(
                f"{self.algo} has neither a device predict_raw_array "
                "nor a standalone numpy scorer")
        out = {k: (np.asarray(v) if isinstance(v, jax.Array) else v)
               for k, v in self.output.items()}
        arrays, meta = _flatten_arrays(out)
        return np.asarray(fn(arrays, meta, np.asarray(X, np.float64)))

    # -- tree-family scoring options (hex/Model.java scoring flags) ---------

    def _require_forest(self, what: str) -> None:
        if self.output.get("split_col") is None:
            raise NotImplementedError(
                f"{what} is only supported for tree-based models "
                f"(model {self.key} is {self.algo})")

    def predict_contributions(self, frame: Frame, top_n: int = 0,
                              bottom_n: int = 0,
                              compare_abs: bool = False,
                              output_format: str = "Original") -> Frame:
        """TreeSHAP feature contributions
        (SharedTreeModelWithContributions.scoreContributions)."""
        self._require_forest("predict_contributions")
        from h2o_tpu.models.tree.contributions import contributions_frame
        return contributions_frame(self, frame, top_n=top_n,
                                   bottom_n=bottom_n,
                                   compare_abs=compare_abs,
                                   output_format=output_format)

    def predict_leaf_node_assignment(self, frame: Frame,
                                     assign_type: str = "Path") -> Frame:
        """Terminal node per tree (hex/tree/AssignLeafNodeTask)."""
        self._require_forest("predict_leaf_node_assignment")
        from h2o_tpu.models.tree.contributions import \
            leaf_assignment_frame
        return leaf_assignment_frame(self, frame, assign_type=assign_type)

    def staged_predict_proba(self, frame: Frame) -> Frame:
        """Cumulative probabilities per tree
        (GBMModel.StagedPredictionsTask)."""
        if self.algo not in ("gbm", "xgboost"):
            raise NotImplementedError(
                "staged_predict_proba is only supported for GBM models")
        self._require_forest("staged_predict_proba")
        from h2o_tpu.models.tree.contributions import staged_proba_frame
        return staged_proba_frame(self, frame)

    def model_metrics(self, frame: Frame) -> mm.ModelMetrics:
        """Score + metrics against a labeled frame."""
        return self.metrics_from_raw(self.predict_raw(frame), frame)

    def metrics_from_raw(self, raw, frame: Frame,
                         w=None) -> mm.ModelMetrics:
        """Metrics from given raw predictions (the MetricBuilder reduce
        decoupled from BigScore — used by CV holdout scoring)."""
        y_name = self.params.get("response_column")
        yv = frame.vec(y_name)
        dom = self.output.get("response_domain")
        valid = frame.row_mask()
        # the response is matched by level string like any enum column
        y = yv.as_float()
        table = domain_table(yv.domain, dom) \
            if dom is not None and yv.is_categorical else None
        if table is not None:
            y = _codes_in_domain(yv.data, jnp.asarray(table),
                                 jnp.int32(yv.nrows))[0]
        if w is None:
            wc = self.params.get("weights_column")
            w = frame.vec(wc).data if wc and wc in frame else None
        if dom is None:
            from h2o_tpu.models.distributions import get_distribution
            dist_name = self.params.get("distribution", "gaussian")
            dist = None
            # custom distributions report plain regression metrics (the
            # deviance column needs a built-in family)
            if dist_name not in ("gaussian", "auto", "custom", None):
                dist = get_distribution(
                    dist_name,
                    tweedie_power=self.params.get("tweedie_power", 1.5),
                    quantile_alpha=self.params.get("quantile_alpha", 0.5),
                    huber_alpha=self.params.get("huber_alpha", 1.0))
            return mm.regression_metrics(raw, y, w=w, valid=valid,
                                         distribution=dist)
        if len(dom) == 2:
            return mm.binomial_metrics(raw[:, 2], y, w=w, valid=valid,
                                       domain=dom)
        return mm.multinomial_metrics(raw[:, 1:], y, w=w, valid=valid,
                                      domain=dom)

    def varimp(self, use_pandas: bool = False):
        """Relative/scaled/percentage variable importance (the reference's
        SharedTreeModel varimp convention: max-scaled + share-of-total)."""
        vi = self.output.get("varimp")
        if vi is None:
            return None
        vi = np.asarray(vi, np.float64)
        names = list(self.output.get("x") or
                     [f"C{i}" for i in range(len(vi))])
        order = np.argsort(-vi)
        rel = vi[order]
        scaled = rel / rel[0] if len(rel) and rel[0] > 0 else rel
        pct = rel / rel.sum() if rel.sum() > 0 else rel
        rows = [(names[i], float(r), float(s), float(p))
                for i, r, s, p in zip(order, rel, scaled, pct)]
        if use_pandas:
            import pandas as pd
            return pd.DataFrame(rows, columns=[
                "variable", "relative_importance", "scaled_importance",
                "percentage"])
        return rows

    # -- persistence (binary save/load; MOJO-style export in io.py) --------
    #
    # Versioned envelope (the TypeMap/Icer-version analog, reference
    # water/AutoBuffer.java + Weaver serialization ids): a magic tag +
    # format version + JSON descriptor precede the payload, so readers
    # reject incompatible or foreign files instead of unpickling them
    # blind.  Like the reference's binary models, the payload itself is
    # a trusted same-framework artifact (h2o.load_model docs carry the
    # same caveat for Iced blobs).

    BIN_MAGIC = b"H2OTPUBIN\x00"
    BIN_VERSION = 1

    def save(self, path: str) -> str:
        import json as _json
        from h2o_tpu import __version__
        blob = {"algo": self.algo, "key": str(self.key),
                "params": self.params,
                "output": jax.tree.map(
                    lambda v: np.asarray(v) if isinstance(v, jax.Array)
                    else v, self.output)}
        desc = _json.dumps({"format_version": self.BIN_VERSION,
                            "framework": "h2o-tpu",
                            "framework_version": __version__,
                            "algo": self.algo}).encode()
        with open(path, "wb") as f:
            f.write(self.BIN_MAGIC)
            f.write(self.BIN_VERSION.to_bytes(2, "little"))
            f.write(len(desc).to_bytes(4, "little"))
            f.write(desc)
            pickle.dump(blob, f)
        return path

    @staticmethod
    def load(path: str) -> "Model":
        from h2o_tpu.models.registry import model_class
        with open(path, "rb") as f:
            head = f.read(len(Model.BIN_MAGIC))
            if head == Model.BIN_MAGIC:
                version = int.from_bytes(f.read(2), "little")
                if version > Model.BIN_VERSION:
                    raise ValueError(
                        f"model file {path} has format version {version}; "
                        f"this build reads <= {Model.BIN_VERSION} — "
                        "upgrade h2o-tpu to load it")
                dlen = int.from_bytes(f.read(4), "little")
                f.read(dlen)                      # JSON descriptor
                blob = pickle.load(f)
            else:
                # legacy pre-versioning artifact (round <= 2): plain pickle
                f.seek(0)
                blob = pickle.load(f)
        cls = model_class(blob["algo"])
        m = cls.__new__(cls)
        Model.__init__(m, blob["key"], blob["params"], blob["output"])
        return m


class ModelBuilder:
    """Train lifecycle: validate → Job(Driver) → Model in DKV."""

    algo: str = "base"
    model_cls = Model
    supervised = True
    # builders whose nfolds param means something other than CV model
    # orchestration (e.g. TargetEncoder's encoding folds) set this False
    supports_cv = True

    # Params the engine supports only at specific values (H2O semantics:
    # params work or error — never a silent no-op).  Maps param ->
    # iterable of accepted values; strings compare case-insensitively
    # with -_ collapsed.  Subclasses extend ENGINE_FIXED.
    ENGINE_FIXED: Dict[str, tuple] = {}

    @staticmethod
    def _norm(v):
        if isinstance(v, str):
            return v.lower().replace("_", "").replace("-", "")
        return v

    def _validate_fixed(self, user_params: Dict) -> None:
        for k, accepted in self.ENGINE_FIXED.items():
            if k not in user_params:
                continue
            v = self._norm(user_params[k])
            ok = any(v == self._norm(a) for a in accepted)
            if not ok:
                raise ValueError(
                    f"{self.algo}: param '{k}'={user_params[k]!r} is not "
                    f"supported by this engine (accepted: "
                    f"{sorted(map(str, accepted))}); refusing to train "
                    "with a silently-ignored setting")

    def __init__(self, **params):
        self.params = self.default_params()
        unknown = set(params) - set(self.params) - {"model_id"}
        if unknown:
            raise ValueError(f"{self.algo}: unknown params {sorted(unknown)}")
        self._validate_fixed(params)
        self.params.update(params)
        self.model_id = params.get("model_id")

    def default_params(self) -> Dict[str, Any]:
        return dict(response_column=None, ignored_columns=None,
                    weights_column=None, offset_column=None, seed=-1,
                    max_runtime_secs=0.0, distribution="auto",
                    tweedie_power=1.5, quantile_alpha=0.5, huber_alpha=0.9,
                    nfolds=0, fold_assignment="AUTO", fold_column=None,
                    keep_cross_validation_models=True,
                    keep_cross_validation_predictions=False,
                    keep_cross_validation_fold_assignment=False,
                    checkpoint=None, custom_metric_func=None,
                    # fault tolerance (core/recovery.py): snapshot this
                    # build's params+frame and iteration-level checkpoints
                    # under recovery_dir so auto_recover resumes it
                    # MID-BUILD after a crash; checkpoint_interval is the
                    # cadence in driver units (trees per checkpoint for
                    # the tree engines; 0 = engine default)
                    recovery_dir=None, checkpoint_interval=0)

    # -- public surface (mirrors h2o-py estimator.train) -------------------

    def train(self, x: Optional[Sequence[str]] = None,
              y: Optional[str] = None, training_frame: Frame = None,
              validation_frame: Optional[Frame] = None) -> Model:
        job = self.train_async(x, y, training_frame, validation_frame)
        model = job.join()
        return model

    def train_async(self, x=None, y=None, training_frame=None,
                    validation_frame=None) -> Job:
        assert training_frame is not None, "training_frame is required"
        y = y or self.params.get("response_column")
        if self.supervised:
            assert y, f"{self.algo} requires a response column"
            self.params["response_column"] = y
        ignored = set(self.params.get("ignored_columns") or ())
        if self.params.get("fold_column"):
            ignored.add(self.params["fold_column"])
        x = [c for c in (x or training_frame.names)
             if c != y and c not in ignored]
        t0 = time.time()
        # pin the model key now so the job's dest and the stored model agree
        # (clients fetch GET /3/Models/{job.dest} after polling)
        if not self.model_id:
            self.model_id = str(Key.make(self.algo))
        job = Job(dest=self.model_id, dest_type="Key<Model>",
                  description=f"{self.algo} on {training_frame.key}")
        use_cv = self.supports_cv and (
            int(self.params.get("nfolds") or 0) > 1 or
            self.params.get("fold_column"))

        # job-level fault tolerance (core/recovery.py): snapshot the
        # params + training frame up front; the algo drivers add
        # iteration-level checkpoints so auto_recover resumes mid-build
        rec = None
        if self.params.get("recovery_dir"):
            from h2o_tpu.core.recovery import Recovery
            rec = Recovery(self.params["recovery_dir"], "model",
                           self.model_id)
            self._recovery = rec
            if not getattr(self, "_recovery_resuming", False):
                rec.begin({k: v for k, v in self.params.items()
                           if not str(k).startswith("_")},
                          training_frame,
                          extra={"algo": self.algo, "x": list(x), "y": y})

        def body(j: Job) -> Model:
            # device_gate: parallel builds (grid parallelism, AutoML,
            # segments) must not execute collective programs
            # concurrently on the host-emulated mesh (core/cloud.py
            # device_gate; no-op on real TPU topologies)
            with cloud().device_gate():
                return _train(j)

        def _train(j: Job) -> Model:
            if use_cv:
                model = self._fit_cv(j, x, y, training_frame,
                                     validation_frame)
            else:
                model = self._fit(j, x, y, training_frame, validation_frame)
            if j.warnings:
                # engine-substitution warnings land on the model output
                # too (reference ModelBuilder warning plumbing ->
                # ModelSchemaV3; the job copy is what the stock client
                # re-raises as Python warnings)
                seen = model.output.setdefault("warnings", [])
                seen.extend(w for w in j.warnings if w not in seen)
            cmf = self.params.get("custom_metric_func")
            if cmf:
                # UDF metric (water/udf CMetricFunc flow, core/udf.py)
                from h2o_tpu.core.udf import attach_custom_metric
                for mkey, fr_m in (("training_metrics", training_frame),
                                   ("validation_metrics",
                                    validation_frame)):
                    mm_obj = model.output.get(mkey)
                    if mm_obj is not None and fr_m is not None:
                        attach_custom_metric(model, mm_obj, fr_m, cmf)
            model.run_time_ms = int((time.time() - t0) * 1000)
            if rec is not None:
                rec.done()          # success — drop the snapshot
            cloud().dkv.put(model.key, model)
            log.info("%s trained in %.2fs -> %s", self.algo,
                     time.time() - t0, model.key)
            return model

        cloud().jobs.start(job, body)
        return job

    def _fit(self, job: Job, x: List[str], y: Optional[str],
             train: Frame, valid: Optional[Frame]) -> Model:
        raise NotImplementedError

    # -- n-fold cross-validation orchestration -----------------------------
    # Reference: hex/ModelBuilder.java:535-690 (computeCrossValidation).
    # One orchestrator for every builder; a builder whose K+1 models can
    # share what it prepares from the frame says so through ``_cv_shared``.

    def _cv_shared(self, job: Job, x: List[str], y: Optional[str],
                   train: Frame):
        """The hook of ``_fit_cv``: what the fold models and the main
        model of one cross-validated job share, prepared ONCE from the
        job's frame (the tree builders: its ``BinnedData``).  A builder
        that returns something takes ``_fit(..., cv=CVFold)`` and trains
        a fold model on the job's own frame under the fold's weights,
        leaving its predictions on every row in ``cv.raw``.  None (the
        default): each fold model gets a frame and a holdout frame of
        its own and the holdout rows are scored by ``predict_raw``."""
        return None

    def _fold_assignment(self, train: Frame, y: Optional[str]) -> "Folds":
        """Each row's fold (H2O-3 ``cv_AssignFold``).  Modulo is an iota
        on the device; Random, Stratified and a fold column are drawn on
        the host and landed once; only Stratified reads the response."""
        p = self.params
        nrows, padded = train.nrows, train.padded_rows
        scheme = (p.get("fold_assignment") or "AUTO").lower()
        n = int(p.get("nfolds") or 0)
        if scheme == "modulo" and not p.get("fold_column"):
            ids = reshard_rows(_modulo_folds(jnp.int32(nrows), padded, n))
            counts = np.array([len(range(i, nrows, n)) for i in range(n)])
            return Folds(ids, n, scheme, counts)
        seed = int(p.get("seed") or -1)
        rng = np.random.default_rng(seed if seed >= 0 else None)
        if p.get("fold_column"):
            scheme = "fold_column"
            fv = train.vec(p["fold_column"])
            vals = np.asarray(fv.to_numpy(), np.float64)
            if np.isnan(vals).any() or (fv.is_categorical and
                                        (vals < 0).any()):
                raise ValueError("fold_column contains missing values")
            # remap to contiguous 0..n-1 (non-contiguous user fold ids
            # would otherwise create empty phantom folds)
            _, host = np.unique(vals, return_inverse=True)
        elif scheme == "stratified" and y and train.vec(y).is_categorical:
            yv = np.asarray(train.vec(y).to_numpy())
            host = np.zeros(nrows, np.int64)
            for k in np.unique(yv):
                idx = np.flatnonzero(yv == k)
                rng.shuffle(idx)
                host[idx] = np.arange(len(idx)) % n
        else:
            scheme = "random"
            host = rng.integers(0, n, nrows)
        n = int(host.max()) + 1
        full = np.full(padded, -1, np.int32)    # a pad row is in no fold
        full[:nrows] = host
        return Folds(cloud().device_put_rows(full), n, scheme,
                     np.bincount(host, minlength=n))

    def _fit_cv(self, job: Job, x: List[str], y: Optional[str],
                train: Frame, valid: Optional[Frame]) -> Model:
        """K fold models and the main model as one job, step by step as
        H2O-3's ``ModelBuilder.computeCrossValidation``:

        * ``cv_AssignFold`` / ``cv_makeWeights``: the fold ids once, and
          per fold the training weights (the user's, 0 on the fold's
          rows) and the holdout weights (the user's on the fold's rows,
          0 elsewhere), all on the device;
        * ``cv_buildModels``: fold model i sees its fold at weight 0 in
          every statistic and scores it as its stopping frame
          (``cv_makeFoldValid``).  Where the builder shares what it
          prepares (``_cv_shared``) the fold model trains on the job's
          own frame and both of its metrics, and its holdout
          predictions, come from the F it carried; otherwise it gets a
          weighted copy of the frame and a holdout slice, and the whole
          frame is scored again (``cv_scoreCVModels``);
        * ``cv_computeAndSetOptimalParameters``: a stopping rule's tree
          count goes to the main model as the mean of the fold models';
        * the main model on all rows, then ``cv_mainModelMetrics``: the
          combined holdout predictions scored once, and fold by fold for
          the summary.
        """
        p = self.params
        if train.is_ragged:
            train.repack()      # a row's place on the device is its index
        # the user's weights are weights, in every model of the job
        x = [c for c in x if c != p.get("weights_column")]
        nrows = train.nrows
        with TimeLine.span("train", "cv.folds", rows=nrows) as ev:
            folds = self._fold_assignment(train, y)
            ev.update(nfolds=folds.n, scheme=folds.scheme)
        fold, nfolds = folds.ids, folds.n
        user_w = train.vec(p["weights_column"]).data \
            if p.get("weights_column") \
            else jnp.ones((train.padded_rows,), jnp.float32)
        shared = self._cv_shared(job, x, y, train)
        bins, source = ("own", "descent") if shared is None \
            else ("shared", "carried_F")
        fold_host = None if shared is not None \
            else np.asarray(fold)[:nrows]

        cv_models, raw_combined = [], None
        for i in range(nfolds):
            w_i, hold_w = _fold_weights(fold, user_w, jnp.int32(i))
            sub_params = dict(p)
            sub_params.update(nfolds=0, fold_column=None, checkpoint=None,
                              model_id=None, recovery_dir=None)
            job.update((i + 0.0) / (nfolds + 1.0),
                       f"CV model {i + 1}/{nfolds}")
            rows_out = int(folds.counts[i])
            with TimeLine.span("train", "cv.model", fold=i + 1,
                               rows_in=nrows - rows_out, rows_out=rows_out,
                               bins=bins):
                if shared is not None:
                    sub = self._cv_sub(sub_params, y)
                    cvf = CVFold(shared, w_i, hold_w, rows_out)
                    m_i = sub._fit(job, x, y, train, None, cv=cvf)
                else:
                    wname = f"__cv_weights_{i}"
                    sub = self._cv_sub(dict(sub_params,
                                            weights_column=wname), y)
                    fr_i = Frame(train.names + [wname],
                                 train.vecs + [Vec(w_i, nrows=nrows)])
                    # holdout rows as the fold's validation frame, so that
                    # a stopping rule watches out-of-fold metrics
                    fr_hold = train.slice_rows(fold_host == i)
                    fr_hold.add(wname, Vec(np.asarray(hold_w)[:nrows][
                        fold_host == i]))
                    m_i = sub._fit(job, x, y, fr_i, fr_hold)
            m_i.key = Key(f"{self.model_id or self.algo}_cv_{i + 1}")
            cv_models.append(m_i)
            with TimeLine.span("train", "cv.holdout", source=source):
                raw_i = m_i.predict_raw(train) if shared is None \
                    else cvf.raw
                if raw_combined is None:
                    raw_combined = jnp.zeros_like(raw_i)
                raw_combined = _select_holdout(fold, jnp.int32(i), raw_i,
                                               raw_combined)

        # optimal-params transfer: early stopping resolved by CV
        if int(p.get("stopping_rounds") or 0) > 0 and \
                all("ntrees_actual" in m.output for m in cv_models):
            p = dict(p)
            p["ntrees"] = max(1, int(round(np.mean(
                [m.output["ntrees_actual"] for m in cv_models]))))
            p["stopping_rounds"] = 0
            self.params = p

        job.update(nfolds / (nfolds + 1.0), "main model on full data")
        with TimeLine.span("train", "cv.model", fold="main", rows_in=nrows,
                           rows_out=0, bins=bins):
            model = self._fit(job, x, y, train, valid) if shared is None \
                else self._fit(job, x, y, train, valid,
                               cv=CVFold(shared))

        with TimeLine.span("train", "cv.metrics", nfolds=nfolds):
            cvm = model.metrics_from_raw(raw_combined, train)
            fold_mms = [model.metrics_from_raw(
                raw_combined, train,
                w=_fold_weights(fold, user_w, jnp.int32(i))[1])
                for i in range(nfolds)]
        summary: Dict[str, Any] = {}
        for k, v in fold_mms[0].data.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                vals = [float(m.data[k]) for m in fold_mms
                        if isinstance(m.data.get(k), (int, float))]
                if vals:
                    summary[k] = dict(
                        mean=float(np.mean(vals)), sd=float(np.std(vals)),
                        values=vals)
        model.output["cross_validation_metrics"] = cvm
        model.output["cross_validation_metrics_summary"] = summary
        if p.get("keep_cross_validation_models", True):
            for m_i in cv_models:
                cloud().dkv.put(m_i.key, m_i)
            model.output["cross_validation_models"] = \
                [str(m.key) for m in cv_models]
        if p.get("keep_cross_validation_predictions"):
            pf = _raw_to_frame(raw_combined, nrows,
                               model.output.get("response_domain"))
            pf.key = Key(f"cv_holdout_prediction_{model.key}")
            cloud().dkv.put(pf.key, pf)
            model.output["cross_validation_holdout_predictions_frame_id"] = \
                str(pf.key)
        if p.get("keep_cross_validation_fold_assignment"):
            ff = Frame(["fold_assignment"],
                       [Vec(fold.astype(jnp.float32), nrows=nrows)])
            ff.key = Key(f"cv_fold_assignment_{model.key}")
            cloud().dkv.put(ff.key, ff)
            model.output["cross_validation_fold_assignment_frame_id"] = \
                str(ff.key)
        return model

    def _cv_sub(self, sub_params: Dict[str, Any], y: Optional[str]):
        """A fold model's builder: this builder's class and its
        parameters AS RESOLVED (a constructor that translates a client's
        names onto the engine's, XGBoost's, is handed none to translate
        again: its defaults under one name would override the user's
        value under the other)."""
        sub = self.__class__()
        sub.params.update({k: v for k, v in sub_params.items()
                           if k in sub.params})
        sub.params["response_column"] = y
        return sub

    # -- shared helpers -----------------------------------------------------

    def checkpoint_model(self) -> Optional[Model]:
        """Resolve the ``checkpoint`` param to a Model (SharedTree resume,
        SharedTree.java:465-478; DL continuation, DeepLearning.java:348)."""
        ck = self.params.get("checkpoint")
        if not ck:
            return None
        if isinstance(ck, Model):
            return ck
        m = cloud().dkv.get(str(ck))
        if m is None:
            raise ValueError(f"checkpoint model {ck} not found")
        return m

    def resolve_distribution(self, di: DataInfo) -> str:
        d = self.params.get("distribution", "auto")
        if d and d != "auto":
            return d
        if di.nclasses == 2:
            return "bernoulli"
        if di.nclasses > 2:
            return "multinomial"
        return "gaussian"

    def rng_key(self) -> jax.Array:
        seed = self.params.get("seed")
        seed = int(seed) if seed is not None else -1
        if seed < 0:
            seed = np.random.SeedSequence().entropy % (2 ** 31)
        return jax.random.key(seed)
