"""Quantized per-row gradient/hessian stats — the stats twin of
``ops/binpack.py`` (PR 14 packed the bin INDICES; this layer packs the
VALUES the histogram matmul contracts against).

Quantized GBDT training (Shi et al., *Quantized Training of Gradient
Boosting Decision Trees*, NeurIPS 2022; LightGBM's grad-quant mode):
scale each tree's per-row stats ``(R, S)`` to a narrow integer carrier
with stochastic rounding, accumulate the (L, C, B+1, S) histogram
tables in int32 via an integer ``dot_general``
(``preferred_element_type=int32``), and dequantize ONCE per level at
the table — never per row.  Stats + one-hot operand bytes drop 2×
(int16) to 4× (int8), and sibling subtraction becomes EXACT (integer
subtraction does not round), so any block partition or mesh shape
reproduces the identical table bit for bit — a claim the f32 path
cannot make.

DECODE CONTRACT (the one screen that defines the approximation):

  * per (tree, slot) scale: ``scale[s] = qmax / max_r |stats[r, s]|``
    with ``qmax = min(carrier_max, (2**31 - 1) // rows)`` — the row
    bound guarantees the int32 table accumulation over ALL rows (and
    every psum partial) can NEVER overflow, so integer arithmetic on
    tables is exact, not just probably-fine;
  * stochastic rounding ``q = clip(floor(f * scale + u), -qmax, qmax)``
    with ``u ~ U[0, 1)`` drawn from a ``fold_in`` of the per-tree RNG
    key — unbiased (``E[q] = f * scale``) and row-deterministic: the
    per-tree keys already fold the ABSOLUTE tree index, and threefry
    draws are prefix-stable in the flattened row index, so any block
    partition of the forest and any mesh shape quantizes every row
    identically;
  * scale bound: ``|dequant(q) - f| < 1/scale[s] = max|f| / qmax`` per
    element (one quantization step).  At the default int16 carrier and
    R ≤ 2^16 rows that is max|f|/32767 ≈ 0.003 %.

WIDEN RULES (graftlint GL631 bans f32 re-widening of stat-named values
outside this module, receiver-narrow like GL630):

  * per-row quantized stats stay in the carrier dtype end to end; the
    histogram kernels cast the one-hot to the SAME carrier in-register
    (a fusing convert, never an f32 copy of (R, S) or (R, C*B1));
  * int32 TABLE arithmetic (scan accumulate, hpsum, sibling subtract)
    is integer → integer and untouched by the lint;
  * ``dequant_table`` below is THE sanctioned integer→f32 crossing —
    one convert + one multiply per (L, C, B+1, S) table per level.

Lever semantics (mirrors ``tree.bins_dtype``): ``tree.stats_dtype``
autotuner lever, env ``H2O_TPU_STATS_DTYPE`` tri-state — force the
quantized carrier (``1``/``int16``, or ``int8``), force the f32
reference (``0``/``f32``), or unset/``auto`` = measured decision (TPU
only; CPU tiers keep the bitwise pre-lever f32 path with zero probes).
The parity gate tolerance is the published table-level bound below —
NOT bitwise, which is why the tests additionally pin
whole-forest metrics (deviance/AUC) inside ``METRIC_TOL``.
"""

from __future__ import annotations

import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: quantized stats carriers by name; "f32" is the reference (no-op).
STATS_DTYPES = ("f32", "int16", "int8")
_CARRIER = {"int16": (jnp.int16, 32767), "int8": (jnp.int8, 127)}

#: published whole-forest metric tolerance for the quantized carrier:
#: deviance / AUC of an int16-stats forest must sit within this
#: relative band of the f32 reference (tests/test_stats_pack.py asserts
#: it; the autotuner additionally
#: disqualifies a candidate whose probe tables drift past TABLE_TOL).
METRIC_TOL = 0.02

#: table-level parity tolerance for the autotuner probe (rtol, atol):
#: each table entry is a sum of ≤ rows stochastic roundings, each off
#: by < one step, so the band is generous next to the per-element
#: bound but tight enough to catch a broken kernel outright.
TABLE_TOL = (0.02, 0.05)

_TINY = 1e-30
_QKEY_SALT = 0x51A7  # fold_in tag for the quantization noise stream

_LOCK = threading.Lock()
_COUNTS = {"quantized_trains": 0, "f32_trains": 0, "bytes_saved_est": 0}


def stats_itemsize(stats_dtype: str) -> int:
    """Carrier itemsize in bytes (4 for the f32 reference)."""
    return jnp.dtype(stats_qdtype(stats_dtype)).itemsize


def stats_qdtype(stats_dtype: str):
    """Carrier jnp dtype for a stats-dtype name."""
    if stats_dtype == "f32":
        return jnp.float32
    try:
        return _CARRIER[stats_dtype][0]
    except KeyError:
        raise ValueError(
            f"unknown stats dtype {stats_dtype!r}; one of {STATS_DTYPES}")


def stats_qmax(rows: int, stats_dtype: str) -> int:
    """The quantization ceiling: carrier max, tightened so an int32
    accumulation over ``rows`` rows of |q| ≤ qmax can never overflow
    ((2**31 - 1) // rows).  Static — ``rows`` is the padded row count,
    a trace-time constant."""
    cmax = _CARRIER[stats_dtype][1]
    return max(1, min(cmax, (2 ** 31 - 1) // max(int(rows), 1)))


def quantize_stats(stats, key, stats_dtype: str, qmax: int):
    """Per-slot scale + stochastic rounding -> (q, inv_scale).

    stats: (R, S) f32; key: per-tree (already fold_in'd) PRNG key;
    qmax: static ceiling from ``stats_qmax``.  Returns the carrier
    array (R, S) and the (S,) f32 dequantization factor 1/scale.
    """
    m = jnp.max(jnp.abs(stats), axis=0)                       # (S,)
    scale = qmax / jnp.maximum(m, _TINY)
    u = jax.random.uniform(jax.random.fold_in(key, _QKEY_SALT),
                           stats.shape)
    q = jnp.clip(jnp.floor(stats * scale[None, :] + u), -qmax, qmax)
    q = jax.lax.convert_element_type(q, stats_qdtype(stats_dtype))
    return q, jnp.maximum(m, _TINY) / qmax


def dequant_table(table, inv_scale):
    """THE sanctioned integer→f32 crossing: int32 histogram table
    (..., S) -> f32, once per level — one fused convert + multiply on
    O(table) elements, never O(rows)."""
    return table.astype(jnp.float32) * inv_scale


def bf16_parts(stats):
    """f32 ``stats`` as three bfloat16 parts whose f32 sum is ``stats``
    exactly: ``p1`` the value rounded to bfloat16's 8 significant bits,
    ``p2`` the residual rounded so, ``p3`` what is left, which has at
    most 8 significant bits of its own (exact down to 2**-103; below it
    the last bits are subnormal, and XLA flushes them to zero).  A
    product of a part with a 0/1 one-hot is exact in bfloat16 x bfloat16
    -> f32, so one bfloat16 contraction of the three parts side by side
    forms the products ``Precision.HIGHEST`` forms, in one pass.
    Rounded by ``reduce_precision``, not by a convert round trip, which
    XLA may fold away under excess precision."""
    p1 = jax.lax.reduce_precision(stats, exponent_bits=8, mantissa_bits=7)
    r = stats - p1
    p2 = jax.lax.reduce_precision(r, exponent_bits=8, mantissa_bits=7)
    return p1, p2, r - p2


def widen_stats(q):
    """Sanctioned in-register widen of carrier stats to int32 (kernel
    bodies that need int32 operands before the dot; the convert fuses —
    no int32 copy of (R, S) lands in HBM)."""
    return jax.lax.convert_element_type(q, jnp.int32)


def stats_pack_enabled(bucket=None) -> bool:
    """The boolean lever half: True = quantize (int16 by default).  An
    explicit H2O_TPU_STATS_DTYPE spelling (1/0 or a carrier name) wins
    with zero probes; otherwise the ``tree.stats_dtype`` lever decides
    (reference f32 on CPU-auto, measured on TPU)."""
    from h2o_tpu.core.autotune import resolve_flag, stats_dtype_forced
    forced = stats_dtype_forced()
    if forced is not None:
        return forced != "f32"
    return resolve_flag("tree.stats_dtype", bucket)


def resolve_stats_dtype(bucket=None) -> str:
    """Resolve the static stats-dtype name OUTSIDE any trace (the
    drivers call this once per forest): an explicit env spelling
    (``int16``/``int8``/``f32``, or 1/0) wins with zero probes;
    otherwise the ``tree.stats_dtype`` lever decides — reference f32
    on CPU-auto, measured on TPU."""
    from h2o_tpu.core.autotune import resolve_flag, stats_dtype_forced
    forced = stats_dtype_forced()
    if forced is not None:
        return forced
    return "int16" if resolve_flag("tree.stats_dtype", bucket) else "f32"


def stats_bucket(rows: int, cols: int, nbins: int) -> Tuple:
    """Shape bucket for the tree.stats_dtype lever (mirrors the
    bins-pack bucket: pow2 rows capped, pow2 cols, exact nbins)."""
    from h2o_tpu.core.exec_store import bucket_pow2
    return (min(bucket_pow2(int(rows)), 1 << 20),
            bucket_pow2(int(cols)), int(nbins))


# ---------------------------------------------------------------------------
# counters (host-side; conftest prints them in the session summary)
# ---------------------------------------------------------------------------


def note_train(stats_dtype: str, rows: int, n_stats: int,
               ntrees: int = 1) -> None:
    """Record one forest-block launch under ``stats_dtype``.  The bytes
    figure is the per-tree (R, S) stats stream saved vs f32 — an
    estimate (the one-hot operand saves more), kept deliberately
    conservative and cheap."""
    saved = rows * n_stats * (4 - stats_itemsize(stats_dtype)) \
        * max(int(ntrees), 1)
    with _LOCK:
        if stats_dtype == "f32":
            _COUNTS["f32_trains"] += 1
        else:
            _COUNTS["quantized_trains"] += 1
            _COUNTS["bytes_saved_est"] += max(saved, 0)


def stats() -> dict:
    with _LOCK:
        return dict(_COUNTS)


def reset_stats() -> None:
    with _LOCK:
        for k in _COUNTS:
            _COUNTS[k] = 0
