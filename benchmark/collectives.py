"""The collective operations of a traced slice, from the device trace.

An operation is a collective by its own opcode in the HLO text that names
its event (``%all-reduce.7 = f32[64,13,353,4]{...} all-reduce(...)``):
all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute,
each synchronous or as the ``-start`` / ``-done`` halves of an
asynchronous pair.  It is OWNED where its scope path (``tf_op``,
``benchmark/scopes.py``) holds an ``h2o.coll.<tag>`` component: the
program's own helpers (``core/cloud.py`` ``hpsum`` ...) put it there;
an unowned one the partitioner inserted.  A collective's payload is its
result's bytes, read from the same text: what ``note_collective``
counts at trace time for that call.  Nothing here is found (the readers
leave their metrics out) where there is no trace, or no operation of the
trace carries an ``h2o.`` scope (a parent commit).
"""

from __future__ import annotations

import re
from typing import List, NamedTuple, Optional

from benchmark import harness, scopes, trace

_OPCODE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"ragged-all-to-all|collective-permute|collective-broadcast)"
    r"(-start|-done)?\(")
_RESULT = re.compile(r" = \(?(\w+)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}


class Collective(NamedTuple):
    name: str
    seconds: float           # self seconds, summed over the device planes
    events: int              # over the device planes
    scope: str               # the deepest h2o. component, or "unscoped"
    half: str                # "" (synchronous), "-start" or "-done"
    nbytes: int              # the result's bytes (a start's first one)


def payload_bytes(name: str) -> int:
    m = _RESULT.search(name)
    if not m or m.group(1) not in _BYTES:
        return 0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n * _BYTES[m.group(1)]


def slice_ops(ctx):
    """``(ops, paths)`` of the run's traced slice: ``trace.reduce_xplane``'s
    operations and their scope paths; None where there is nothing to
    read."""
    tr = ctx.get("trace")
    if not tr or not tr.get("ops"):
        return None
    xp = trace.find_xplane(harness.OUT_DIR)
    if xp is None:
        return None
    paths = scopes.op_paths(xp)
    if not any(scopes.scope_of(p) != scopes.UNSCOPED
               for p in paths.values()):
        return None
    return tr["ops"], paths


def collectives(ctx) -> Optional[List[Collective]]:
    """Every collective operation of the slice; None where the slice
    cannot be read."""
    got = slice_ops(ctx)
    if got is None:
        return None
    ops, paths = got
    out = []
    for name, (seconds, events) in ops.items():
        m = _OPCODE.search(name)
        if m:
            out.append(Collective(name, seconds, events,
                                  scopes.scope_of(paths.get(name)),
                                  m.group(2) or "", payload_bytes(name)))
    return out


def total_seconds(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or not tr.get("ops"):
        return None
    return sum(s for s, _ in tr["ops"].values())
