"""The one table of chip peaks, keyed by ``device_kind`` as JAX reports
it.  A device that is not in the table is an error, not a default, and no
environment variable overrides a peak.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       "add it to benchmark/peaks.py with its source") \
            from None


def least_seconds(ops: float, nbytes: float, device_kind: str,
                  chips: int = 1) -> Dict[str, float]:
    """The least time ``chips`` chips could take for that work: the larger
    of operations over peak operations/s and bytes over peak bytes/s,
    with which of the two bounds it."""
    p = peaks_for(device_kind)
    t_ops = ops / (p["flops_per_s"] * chips)
    t_bytes = nbytes / (p["hbm_bytes_per_s"] * chips)
    return {"seconds": max(t_ops, t_bytes),
            "bound": "bytes" if t_bytes >= t_ops else "ops"}
