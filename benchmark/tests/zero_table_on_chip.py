"""The histogram table that came back all zero (PERF.md section 6, PR 34;
ROADMAP D15), by hand on the chip, in three minutes and without the cell:

    python3 -m benchmark.tests.zero_table_on_chip [--rows 11500000] \
        [--cols 13] [--bins 337] [--leaves 1] [--seed 7]

Random bins of a frame's padded shape, one level's table built two ways
and held against numpy's float64 count: ``own shape`` is the parent's
block loop (the rows left over 8,192 contracted at their own, shorter
shape: at the defaults above its table is ZERO on a v5e, ``rows a column
0..0 of 11500000`` in PR 34's run), ``program`` is
``ops/histogram.histogram_build_traced`` as it stands (those rows padded
to a whole block).  Prints each table's row count a column and its
largest gap to numpy; exit code 1 if the program's table is off.  A
look at one compiler's output, not a measurement.
"""

from __future__ import annotations

import argparse
import functools
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=11500000)
    ap.add_argument("--cols", type=int, default=13)
    ap.add_argument("--bins", type=int, default=337)
    ap.add_argument("--leaves", type=int, default=1)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from benchmark import harness
    harness.require_accelerator(1)
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import h2o_tpu
    from h2o_tpu.core.cloud import cloud, hpsum, shard_map_compat
    from h2o_tpu.ops import histogram as H
    h2o_tpu.Cloud.boot(nodes=1)
    C, B, L, blk = args.cols, args.bins, args.leaves, 8192
    k = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    host = np.zeros((args.rows, 1), np.float32)
    R = cloud().device_put_rows(host).shape[0]          # a frame's padding
    live = jnp.arange(R) < args.rows
    bins = jax.random.randint(k[0], (R, C), 0, B + 1, jnp.int32)
    leaf = jnp.where(live, jax.random.randint(k[1], (R,), 0, L), -1)
    g = jax.random.uniform(k[2], (R,), jnp.float32, -0.5, 0.5)
    stats = jnp.where(live[:, None], jnp.stack(
        [jnp.ones(R), g, g * g, jnp.full((R,), 0.25)], axis=1), jnp.nan)
    bins = jax.device_put(bins, cloud().matrix_sharding())
    leaf = jax.device_put(leaf.astype(jnp.int32), cloud().row_sharding)
    stats = jax.device_put(stats.astype(jnp.float32),
                           cloud().matrix_sharding())

    def own_shape(b, l, s):
        dp = cloud().data_pspec

        @functools.partial(shard_map_compat, mesh=cloud().mesh,
                           in_specs=(dp(None), dp(), dp(None)),
                           out_specs=P(), check_vma=False)
        def run(b_sh, l_sh, s_sh):
            n = b_sh.shape[0] // blk

            def body(acc, xs):
                return acc + H._block_hist(*xs, L, B), None
            acc, _ = jax.lax.scan(
                body, jnp.zeros((C * (B + 1), L * 4), jnp.float32),
                (b_sh[: n * blk].reshape(n, blk, -1),
                 l_sh[: n * blk].reshape(n, blk),
                 s_sh[: n * blk].reshape(n, blk, -1)))
            if b_sh.shape[0] > n * blk:
                acc = acc + H._block_hist(b_sh[n * blk:], l_sh[n * blk:],
                                          s_sh[n * blk:], L, B)
            return hpsum(acc, "hist.table")
        return run(b, l, s).reshape(C, B + 1, L, 4).transpose(2, 0, 1, 3)

    def program(b, l, s):
        return H.histogram_build_traced(b, l, s, L, B, blk, False,
                                        pallas=False)

    hb = np.asarray(bins)[: args.rows]
    hl = np.asarray(leaf)[: args.rows]
    hs = np.asarray(stats)[: args.rows].astype(np.float64)
    want = np.zeros((L, C, B + 1, 4))
    for j in range(C):
        for s_ in range(4):
            want[:, j, :, s_] = np.bincount(
                hl * (B + 1) + hb[:, j], weights=hs[:, s_],
                minlength=L * (B + 1)).reshape(L, B + 1)
    off = 0
    for name, fn in (("own shape", own_shape), ("program", program)):
        got = np.asarray(jax.jit(fn)(bins, leaf, stats))
        rows = got[..., 0].sum(axis=(0, 2))
        gap = float(np.abs(got - want).max())
        print(f"{name:<10} rows a column {rows.min():.0f}..{rows.max():.0f}"
              f" of {args.rows}; largest gap to numpy {gap:.6g}")
        if name == "program" and not np.allclose(rows, args.rows):
            off = 1
    return off


if __name__ == "__main__":
    sys.exit(main())
