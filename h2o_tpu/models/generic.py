"""Generic — import an external scoring artifact as a first-class Model.

Reference: hex/generic/Generic.java + GenericModel.java (1.3k LoC) — wraps a
MOJO so it can live in the DKV, serve /3/Predictions, and join ensembles/
leaderboards like any trained model.

Scoring here routes the frame through the MOJO's pure-numpy scorer on the
host (artifacts may come from other builds and carry no device program) and
re-uploads predictions; metrics reuse the standard metric kernels.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from h2o_tpu.core.frame import Frame, T_TIME
from h2o_tpu.models.model import Model, ModelBuilder, adapt_frame


class GenericModel(Model):
    algo = "generic"

    @classmethod
    def from_mojo(cls, mojo, key: Optional[str] = None) -> "GenericModel":
        params = dict(mojo.params)
        out = dict(mojo.meta)
        out["__arrays__"] = {k: np.asarray(v)
                             for k, v in mojo.arrays.items()}
        out["source_algo"] = mojo.algo
        m = cls(key, params, out)
        from h2o_tpu.core.cloud import cloud
        cloud().dkv.put(m.key, m)
        return m

    def _mojo(self):
        arrays = self.output["__arrays__"]
        if "__genmodel_zip__" in arrays:
            # parse once per model: nested artifacts (StackedEnsemble)
            # are expensive to re-decode on every predict
            cached = getattr(self, "_mojo_cache", None)
            if cached is not None:
                return cached
            from h2o_tpu.mojo.genmodel import GenmodelMojoModel
            self._mojo_cache = GenmodelMojoModel(
                arrays["__genmodel_zip__"].tobytes())
            return self._mojo_cache
        from h2o_tpu.mojo import MojoModel
        return MojoModel(self.output["source_algo"], self.params,
                         {k: v for k, v in self.output.items()
                          if k != "__arrays__"},
                         arrays)

    def predict_raw(self, frame: Frame):
        mojo = self._mojo()
        cols = mojo.columns
        # adaptTestForTrain (models/model.py adapt_frame, on the device):
        # the frame's enum codes in the artifact's training domains, an
        # unseen level or an absent column NaN, score_matrix's NA
        ad = adapt_frame(frame, cols, {
            c: mojo.domain_of(c) for c in cols
            if mojo.domain_of(c) is not None})
        X = np.asarray(ad.matrix, np.float64)[: frame.nrows]
        # the device matrix is float32; a time column keeps its exact
        # float64 epoch-ms on the host (float32 is two minutes coarse
        # there), and the artifact's thresholds are read against that
        for j, c in enumerate(cols):
            if c in frame and frame.vec(c).type == T_TIME:
                X[:, j] = np.asarray(frame.vec(c).to_numpy(), np.float64)
        raw = mojo.score_matrix(X)
        # pad back to the frame's padded shape for the metric kernels
        pad = frame.padded_rows - frame.nrows
        raw = np.pad(np.asarray(raw, np.float32),
                     ((0, pad),) + ((0, 0),) * (raw.ndim - 1))
        return jnp.asarray(raw)


class Generic(ModelBuilder):
    algo = "generic"
    model_cls = GenericModel
    supervised = False

    def default_params(self) -> Dict:
        p = super().default_params()
        p.update(path=None, model_key=None)
        return p

    def _resolve_path(self) -> str:
        from h2o_tpu.core.cloud import cloud
        path = self.params.get("path")
        if not path and self.params.get("model_key"):
            # upload_mojo: model_key is the PostFile upload key whose DKV
            # value is the spooled server-side path
            mk = str(self.params["model_key"])
            src = cloud().dkv.get(mk)
            path = str(src) if src else mk.replace("nfs://", "")
        assert path, "Generic requires path or model_key to a MOJO"
        return path

    def train_async(self, x=None, y=None, training_frame=None,
                    validation_frame=None):
        # frame-less builder: the artifact IS the training input
        from h2o_tpu.core.cloud import cloud
        from h2o_tpu.core.job import Job
        from h2o_tpu.core.store import Key
        from h2o_tpu.mojo import load_mojo
        if not self.model_id:
            self.model_id = str(Key.make(self.algo))
        job = Job(dest=self.model_id, dest_type="Key<Model>",
                  description="generic model import")

        def body(j):
            return GenericModel.from_mojo(load_mojo(self._resolve_path()),
                                          key=self.model_id)

        cloud().jobs.start(job, body)
        return job

    def train(self, x=None, y=None, training_frame=None,
              validation_frame=None):
        return self.train_async().join()
