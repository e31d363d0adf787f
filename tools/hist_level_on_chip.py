#!/usr/bin/env python
"""One level of the histogram's block scan, in each contraction form, on
the chip: the standalone reading behind ``ops/histogram.hist_form``.

A level is one ``histogram_build_traced`` call at a cell's shape (rows x
columns x one-hot width, the global grid or the adaptive arm through
``map_buckets``) and an output width ``L*S``.  Each (shape, width) is
built in every form asked for:

  0  today's contraction, f32 at ``Precision.HIGHEST``
  a  the statistics as three exact bfloat16 parts side by side, one
     bfloat16 contraction of ``3*L*S`` columns with f32 accumulation
     (``statpack.bf16_parts``)
  b  the one-hot on the lanes: ``(L*S, R) x (R, C*B1)`` at HIGHEST,
     transposed once a block
  c  the two-level one-hot: ``b = hi*G + lo``, ``onehot(hi)`` against
     ``onehot(lo) (x) a`` batched over the columns at HIGHEST (G = 16)

Forms 0 and a run through the program's own ``_block_hist`` with
``hist_form`` held to one answer; b and c replace ``_block_hist`` for
the trace.  Each reading is the median of ``--reps`` calls after the
call that compiles, in milliseconds, with the compile's seconds and the
table's largest difference from form 0's over form 0's largest entry.
Lines go to ``--out`` (JSON, one a reading) and a table to stdout.

    python3 tools/hist_level_on_chip.py                    # every shape
    python3 tools/hist_level_on_chip.py --shapes higgs --widths 4 64
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# name: (rows, columns, nbins, fine grid or 0, widths run by default)
SHAPES = {
    "higgs": (5_250_048, 28, 255, 0, (4, 8, 16, 32, 64, 128, 256, 512)),
    "airline": (11_500_032, 13, 353, 0,
                (4, 8, 16, 32, 64, 128, 256, 512)),
    "adaptive1025": (5_250_048, 28, 1024, 1024, (4, 8, 16, 32, 64)),
    "adaptive513": (5_250_048, 28, 512, 1024, (4, 8, 16, 32, 64)),
    "adaptive257": (5_250_048, 28, 256, 1024, (4, 8, 16, 32, 64)),
}
FORMS = ("0", "a", "b", "c")
C_MAX_WIDTH = 32        # form c's operand grows with G * L * S
G = 16


def _block_lanes(bins_blk, leaf_blk, stats_blk, n_leaves, nbins,
                 mm_dtype=None, scopes=None):
    """Form b: the one-hot on the lanes, transposed once a block."""
    import jax
    import jax.numpy as jnp
    B1 = nbins + 1
    C, S = bins_blk.shape[1], stats_blk.shape[1]
    leafhot = leaf_blk[:, None] == jnp.arange(n_leaves)[None, :]
    stats_blk = jnp.where(leaf_blk[:, None] >= 0, stats_blk, 0)
    a = (leafhot[:, :, None] * stats_blk[:, None, :]).reshape(
        -1, n_leaves * S)
    binhot = (bins_blk[:, :, None] == jnp.arange(B1)[None, None, :]
              ).reshape(-1, C * B1).astype(jnp.float32)
    out = jax.lax.dot_general(
        a, binhot, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)          # (L*S, C*B1)
    return out.T


def _block_two_level(bins_blk, leaf_blk, stats_blk, n_leaves, nbins,
                     mm_dtype=None, scopes=None):
    """Form c: onehot(hi) (R, C, H) against onehot(lo) (x) a
    (R, C, G*L*S), batched over the columns."""
    import jax
    import jax.numpy as jnp
    B1 = nbins + 1
    Hn = -(-B1 // G)
    C, S = bins_blk.shape[1], stats_blk.shape[1]
    LS = n_leaves * S
    from h2o_tpu.ops.binpack import widen_bins
    b = widen_bins(bins_blk)
    leafhot = leaf_blk[:, None] == jnp.arange(n_leaves)[None, :]
    stats_blk = jnp.where(leaf_blk[:, None] >= 0, stats_blk, 0)
    a = (leafhot[:, :, None] * stats_blk[:, None, :]).reshape(-1, LS)
    hihot = (b[:, :, None] // G == jnp.arange(Hn)[None, None, :]
             ).astype(jnp.float32)                              # (R, C, H)
    lohot = b[:, :, None] % G == jnp.arange(G)[None, None, :]   # (R, C, G)
    rhs = (lohot[..., None] * a[:, None, None, :]).reshape(
        b.shape[0], C, G * LS)
    out = jax.lax.dot_general(
        hihot, rhs, (((0,), (0,)), ((1,), (1,))),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)          # (C, H, G*L*S)
    return out.reshape(C, Hn * G, LS)[:, :B1].reshape(C * B1, LS)


def _level_fn(H, shape, LS, form):
    """The jitted level: ``histogram_build_traced`` with the form's
    contraction, traced afresh."""
    import jax
    rows, C, nbins, fine, _ = shape
    L = LS // 4

    def level(bins, leaf, stats, *fm):
        fine_map = None
        if fine:
            fine_map = (fm[0], fm[1], fm[2], fm[3], fine)
        return H.histogram_build_traced(bins, leaf, stats, L, nbins,
                                        fine_map=fine_map)

    def traced(*args):
        keep = (H.hist_form, H._block_hist)
        try:
            if form in ("0", "a"):
                want = "highest" if form == "0" else "bf16x3"
                H.hist_form = lambda LS_: want
            elif form == "b":
                H._block_hist = _block_lanes
            else:
                H._block_hist = _block_two_level
            return level(*args)
        finally:
            H.hist_form, H._block_hist = keep
    return jax.jit(traced)


def _data(shape, LS, seed):
    import jax
    import jax.numpy as jnp
    rows, C, nbins, fine, _ = shape
    L = LS // 4
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    top = (fine if fine else nbins) + 1
    bins = jax.random.randint(k[0], (rows, C), 0, top, jnp.int32)
    # the left children of a split level: about half the rows are seen
    leaf = jax.random.randint(k[1], (rows,), -L, L, jnp.int32)
    leaf = jnp.where(leaf < 0, -1, leaf)
    stats = jax.random.normal(k[2], (rows, 4), jnp.float32)
    args = [bins, leaf, stats]
    if fine:
        args += [jnp.zeros((L, C), jnp.int32),
                 jnp.full((L, C), fine - 1, jnp.int32),
                 jnp.zeros((L, C), jnp.int32),
                 jnp.zeros((C,), bool)]
    return jax.block_until_ready(args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--widths", nargs="+", type=int, default=None)
    ap.add_argument("--forms", nargs="+", default=list(FORMS))
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--rows", type=int, default=0,
                    help="rows of every shape (0: the shape's own)")
    ap.add_argument("--seed", type=int, default=2147496001)
    ap.add_argument("--out",
                    default="benchmark/out/hist_level_on_chip.jsonl")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    import h2o_tpu
    from h2o_tpu.ops import histogram as H
    h2o_tpu.Cloud.boot(nodes=1)
    dev = jax.devices()[0]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    print("shape width form ms compile_s rel_diff", flush=True)
    for name in args.shapes:
        shape = SHAPES[name]
        if args.rows:
            shape = (args.rows,) + shape[1:]
        for LS in args.widths or shape[4]:
            data = _data(shape, LS, args.seed + LS)
            ref = None
            for form in args.forms:
                if form == "c" and LS > C_MAX_WIDTH:
                    continue
                rec = dict(shape=name, rows=shape[0], cols=shape[1],
                           nbins=shape[2], fine=shape[3], width=LS,
                           form=form, device=dev.device_kind)
                try:
                    f = _level_fn(H, shape, LS, form)
                    t = time.perf_counter()
                    table = np.asarray(jax.block_until_ready(f(*data)))
                    rec["compile_s"] = time.perf_counter() - t
                    times = []
                    for _ in range(args.reps):
                        t = time.perf_counter()
                        jax.block_until_ready(f(*data))
                        times.append(time.perf_counter() - t)
                    rec["ms"] = 1e3 * statistics.median(times)
                    rec["ms_all"] = [1e3 * x for x in times]
                    if form == "0":
                        ref = table
                    elif ref is not None:
                        scale = float(np.abs(ref).max()) or 1.0
                        rec["rel_diff"] = float(
                            np.abs(table - ref).max()) / scale
                        rec["equal"] = bool(np.array_equal(table, ref))
                except Exception as e:  # noqa: BLE001 - a form may not fit
                    rec["error"] = repr(e)[:300]
                out.write(json.dumps(rec) + "\n")
                out.flush()
                print(name, LS, form, "%.3f" % rec.get("ms", float("nan")),
                      "%.1f" % rec.get("compile_s", float("nan")),
                      rec.get("rel_diff", ""), rec.get("error", ""),
                      flush=True)
            del data
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
