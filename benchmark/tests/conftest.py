"""Tests of the benchmark's own yardstick.  Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They are not part of ``tests/`` (the repo's tier-1 suite)."""

import os

# tiny frames: pad rows to 8, not 128 (read when h2o_tpu is imported)
os.environ.setdefault("H2O_TPU_ROW_ALIGN", "8")
