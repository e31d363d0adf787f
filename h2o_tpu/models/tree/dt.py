"""DT — single decision tree (CART-style).

Reference (hex/tree/dt/DT.java): one greedy binomial classification tree
over binned histograms — the reference's newest algo, a deliberately simple
single-tree builder (cf. single-decision-tree-benchmark.ipynb, the only
published perf artifact, SURVEY §6).

TPU-native: a DRF with ONE unsampled tree using all columns — same MXU
histogram engine, no bagging; leaf values are class frequencies.
"""

from __future__ import annotations

from typing import Dict, Optional

from h2o_tpu.core.frame import Frame
from h2o_tpu.models.tree.drf import DRF, DRFModel


class DTModel(DRFModel):
    algo = "dt"


class DT(DRF):
    algo = "dt"
    model_cls = DTModel

    def default_params(self) -> Dict:
        p = super().default_params()
        p.update(ntrees=1, max_depth=10, min_rows=10.0,
                 sample_rate=1.0, mtries=-2)   # -2 = all columns (DRF.java)
        return p

    def _fit(self, job, x, y, train: Frame, valid: Optional[Frame],
             cv=None):
        self.params["ntrees"] = 1
        self.params["sample_rate"] = 1.0
        # mtries: all columns, not DRF's sqrt subsampling
        self.params["mtries"] = len([c for c in x]) or -1
        return super()._fit(job, x, y, train, valid, cv=cv)
