"""Record the small trace that ``test_scopes.py`` reads: one jitted step
with two named scopes, a ``while`` (a scan whose body is scoped, whose
plumbing is not), an operation under no scope, and host spans named as
``TimeLine.span`` names them, with sleeps between the steps.

    python3 -m benchmark.tests.make_scope_fixture <out-dir>

Run on the chip; the ``.xplane.pb`` it leaves is checked in as
``benchmark/tests/fixture_scopes.xplane.pb``.
"""

from __future__ import annotations

import sys
import time


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    out = argv[1]

    def step(a):
        with jax.named_scope("h2o.fixture.matmul"):
            b = a @ a

        def body(c, _):
            with jax.named_scope("h2o.fixture.loop"):
                return jnp.sin(c) * 1.0001 + 0.1, None

        c, _ = jax.lax.scan(body, b, None, length=6)
        return jnp.cos(c).sum()                 # under no scope

    f = jax.jit(step)
    a = jnp.ones((2048, 2048), jnp.float32) / 2048
    f(a).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("h2o:job.run"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("h2o:fixture.step"):
                f(a).block_until_ready()
            with jax.profiler.TraceAnnotation("h2o:fixture.sleep"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
