"""h2o_tpu — a TPU-native distributed ML platform with the capabilities of H2O-3.

The reference implementation (read-only at /root/reference) is a cluster of JVMs
with a distributed K/V store of compressed column chunks and a fork-join
map/reduce engine (see SURVEY.md).  This package is a ground-up re-design for
TPU hardware:

- the "cloud" is a fixed ``jax.sharding.Mesh`` over TPU devices
  (``h2o_tpu.core.cloud``), replacing Paxos gossip membership
  (reference: h2o-core/src/main/java/water/Paxos.java);
- the distributed K/V store holds host-side metadata while bulk columnar data
  lives as row-sharded ``jax.Array`` shards in HBM (``h2o_tpu.core.store``,
  ``h2o_tpu.core.frame``; reference: water/DKV.java, water/fvec/*);
- the MRTask map/tree-reduce primitive becomes jit/shard_map over row shards
  with ICI ``psum`` reduces (``h2o_tpu.core.mrtask``; reference:
  water/MRTask.java);
- algorithms (GBM/DRF/GLM/KMeans/DeepLearning/...) are XLA programs with
  Pallas kernels for the hot loops (``h2o_tpu.models``, ``h2o_tpu.ops``;
  reference: h2o-algos/src/main/java/hex/**).
"""

__version__ = "0.1.0"

from h2o_tpu.core.cloud import Cloud, cloud  # noqa: F401
from h2o_tpu.core.frame import Frame, Vec  # noqa: F401
from h2o_tpu.core.parse import (parse_file, parse_files,  # noqa: F401
                                parse_setup, parse_svmlight)
