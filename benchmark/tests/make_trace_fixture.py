"""Record the small trace that the reduction test reads: a few matrix
products on the device with host sleeps between them, about a second.

    python3 -m benchmark.tests.make_trace_fixture <out-dir>

Run on the chip; the ``.xplane.pb`` it leaves is checked in as
``benchmark/tests/fixture_trace.xplane.pb``.
"""

from __future__ import annotations

import sys
import time


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    out = argv[1]
    f = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((2048, 2048), jnp.float32)
    f(a).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("fixture_window"):
        for i in range(4):
            with jax.profiler.TraceAnnotation(f"fixture_step_{i}"):
                for _ in range(3):
                    f(a).block_until_ready()
            with jax.profiler.TraceAnnotation("fixture_sleep"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
