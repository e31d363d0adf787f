"""The chip-to-chip interconnect's peak, keyed by ``device_kind`` as JAX
reports it; ``benchmark/peaks.py`` keeps the chip's own.  A device that
is not in the table is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
1,600 Gbps of interchip interconnect (ICI) bandwidth a chip, 200e9 bytes
a second.
"""

from __future__ import annotations

from typing import Dict

ICI_BYTES_PER_S: Dict[str, float] = {
    "TPU v5 lite": 1600e9 / 8,
    "TPU v5e": 1600e9 / 8,
}


def ici_bytes_per_s(device_kind: str) -> float:
    try:
        return ICI_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(f"no ICI peak recorded for device kind "
                       f"{device_kind!r}; add it to benchmark/ici_peaks.py "
                       "with its source") from None


def ring_all_reduce_seconds(nbytes: float, chips: int,
                            device_kind: str) -> float:
    """The least time an all-reduce of ``nbytes`` a chip can take over
    ``chips`` chips: a ring sends and receives 2 (n - 1) / n of the
    payload through each chip's interconnect."""
    if chips <= 1:
        return 0.0
    return 2.0 * (chips - 1) / chips * nbytes / ici_bytes_per_s(device_kind)
