"""Shard-direct landing layer — host rows onto the mesh, one shard at a time.

Reference: parsed chunks land directly on their HOME node (water/fvec/
ParseDataset distributes chunk writes by key home, SURVEY L4) — no node
ever materializes a whole distributed Vec.  The original TPU port
funnelled every frame through ONE ``jax.device_put(whole_array,
row_sharding)``: correct, but a single-host staging + transfer
bottleneck that caps ingest at one host's memory and PCIe link.

This module is the ONE sanctioned gateway for placing row-sharded data
(graftlint GL304 bans ``jax.device_put`` onto the row/matrix shardings
everywhere else):

- :func:`land_rows` — pad host rows to the mesh row quantum, then place
  each shard's slice on its home device individually
  (``jax.device_put(arr[shard_index], device)`` per device, assembled
  with ``jax.make_array_from_single_device_arrays``).  The largest
  single host->device transfer is ONE SHARD, never the whole column —
  the pull-accounting counters below prove it
  (``stats()["max_transfer_bytes"]``).
- :func:`reshard_rows` — sanctioned reshard of an EXISTING device array
  onto the row/matrix sharding (GSPMD moves shard-to-shard over the
  interconnect; no host staging), also accepting host arrays from the
  host-fallback munge paths (those route through the shard-direct
  placement above).
"""

from __future__ import annotations

import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from h2o_tpu.core.log import get_logger

log = get_logger("landing")

_lock = threading.Lock()
_counters = {
    "chunks_landed": 0,      # land_rows calls
    "bytes_landed": 0,       # logical bytes placed (sum over shards)
    "shard_transfers": 0,    # individual per-shard host->device puts
    "reshards": 0,           # device->device reshard_rows calls
    "max_transfer_bytes": 0, # largest SINGLE host->device transfer
}


def _note_transfer(nbytes: int, shards: int = 1) -> None:
    with _lock:
        _counters["shard_transfers"] += shards
        if nbytes > _counters["max_transfer_bytes"]:
            _counters["max_transfer_bytes"] = nbytes


def stats() -> dict:
    with _lock:
        return dict(_counters)


def reset_stats() -> None:
    with _lock:
        for k in _counters:
            _counters[k] = 0


def _row_sharding_for(arr_ndim: int) -> NamedSharding:
    from h2o_tpu.core.cloud import cloud
    c = cloud()
    return NamedSharding(c.mesh, c.data_pspec(*([None] * (arr_ndim - 1))))


def _shard_piece(arr: np.ndarray, index, rows: int) -> np.ndarray:
    """Shard ``index`` of ``arr`` padded to ``rows`` rows (NaN for a
    float column, else 0): only a shard that reaches past the host rows
    is copied to take its padding; no whole-array padded copy."""
    rs = index[0] if index else slice(None)
    start, stop, _ = rs.indices(rows)
    if stop <= arr.shape[0]:
        return arr[index]
    have = arr[(slice(min(start, arr.shape[0]), arr.shape[0]),)
               + tuple(index[1:])]
    fill = np.nan if np.issubdtype(arr.dtype, np.floating) else 0
    pad = [(0, (stop - start) - have.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(have, pad, constant_values=fill)


def _place(arr: np.ndarray, sh: NamedSharding,
           rows: Optional[int] = None) -> jax.Array:
    """Shard-direct placement: one device_put PER SHARD, assembled into
    the global array of ``rows`` rows (``arr``'s own by default; more
    pads the last shards) — no whole-array staging on any single
    transfer, and no whole-array padded copy on the host.

    On a two-level mesh this is also what keeps ingest SLICE-LOCAL: the
    sharding's device map sends each shard's rows straight to its home
    device inside its own ICI island, so DCN never carries raw rows on
    the way in — the host->device link is per-shard by construction."""
    shape = arr.shape if rows is None else (rows,) + arr.shape[1:]
    imap = sh.addressable_devices_indices_map(shape)
    shards = []
    for d, index in imap.items():
        piece = _shard_piece(arr, index, shape[0])
        # graftlint: disable=GL304  the sanctioned landing layer itself
        shards.append(jax.device_put(piece, d))
        _note_transfer(int(piece.nbytes))
    out = jax.make_array_from_single_device_arrays(shape, sh, shards)
    with _lock:
        _counters["bytes_landed"] += int(np.prod(shape)) * arr.itemsize
    return out


def land_rows(host_array, sharding: Optional[NamedSharding] = None
              ) -> jax.Array:
    """Pad host rows to the mesh row quantum and land them shard-direct.

    The one entry every column/matrix landing goes through: parse,
    streaming appends, spill reloads, and the tier manager's block
    paging all call here (mostly via ``Cloud.device_put_rows``), so the
    no-single-host-bottleneck invariant holds for the whole data plane.
    """
    from h2o_tpu.core.cloud import cloud
    arr = np.asarray(host_array)
    q = cloud().row_multiple()
    rows = arr.shape[0] + (-arr.shape[0]) % q
    sh = sharding if sharding is not None else _row_sharding_for(arr.ndim)
    with _lock:
        _counters["chunks_landed"] += 1
    return _place(arr, sh, rows)


def reshard_rows(arr, sharding: Optional[NamedSharding] = None
                 ) -> jax.Array:
    """Sanctioned row/matrix reshard.

    Device arrays move shard-to-shard under GSPMD (an interconnect
    exchange, no host staging — cheap and legal); host ndarrays route
    through the shard-direct placement so host-fallback munge paths
    keep the no-whole-frame-transfer invariant.  Assumes the caller's
    rows are ALREADY padded to the mesh quantum (munge kernel outputs
    and cached matrices are, by construction)."""
    sh = sharding
    if sh is None:
        sh = _row_sharding_for(np.ndim(arr))
    if isinstance(arr, jax.Array):
        with _lock:
            _counters["reshards"] += 1
        # graftlint: disable=GL304  the sanctioned reshard entry itself
        return jax.device_put(arr, sh)
    return _place(np.asarray(arr), sh)
