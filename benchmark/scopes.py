"""Device time by the scope the PROGRAM gave each operation.

The program wraps its traced bodies in ``jax.named_scope("h2o.<layer>.
<phase>")``; XLA carries the scope path to every operation as the
``tf_op`` stat of the operation's event metadata in the profile
(``jit(_train_forest_impl)/.../h2o.tree.route/gather:``).
``jax.profiler.ProfileData`` does not expose that stat, the file does, so
this module reads it with a small protobuf wire reader (four message
kinds: XSpace, XPlane, XEventMetadata/XStatMetadata, XStat) and joins it
to the reduction ``trace.py`` already made: operation name -> self
seconds.  An operation belongs to the DEEPEST ``h2o.`` component of its
path; self time as ``trace.self_times`` counts it, so a ``while`` is not
counted twice and the shares sum to 100.  A fusion carries one ``tf_op``:
where XLA fuses operations of two scopes, the time goes to the scope the
fusion names.

By hand, for every PR's "where the saving appears":

    python3 -m benchmark.scopes <dir-or-file.xplane.pb>

prints the table by scope and the idle gaps by the innermost ``h2o:``
host span (``TimeLine.span`` enters a ``TraceAnnotation``).
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark import harness, trace

UNSCOPED = "unscoped"


# ---- protobuf wire format ---------------------------------------------

def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed-width field, bytes for a length-delimited one."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
        elif wt == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wt == 1:
            val, pos = int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
        elif wt == 5:
            val, pos = int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
        else:
            raise ValueError(f"wire type {wt} at byte {pos}")
        yield num, wt, val


def _map_value(entry: bytes) -> bytes:
    """The message of one ``map<int64, Message>`` entry (field 2)."""
    for num, wt, val in _fields(entry):
        if num == 2 and wt == 2:
            return val
    return b""


def _stat(buf: bytes):
    """XStat -> (metadata_id, str value or None, ref value or None)."""
    mid, text, ref = 0, None, None
    for num, wt, val in _fields(buf):
        if num == 1:
            mid = val
        elif num == 5 and wt == 2:
            text = val.decode("utf-8", "replace")
        elif num == 7 and wt == 0:
            ref = val
    return mid, text, ref


def _plane_tf_ops(buf: bytes) -> Dict[str, str]:
    """XPlane -> {event metadata name: its ``tf_op``}; empty for a plane
    that is not a device's."""
    name, stat_names, metas = "", {}, []
    for num, wt, val in _fields(buf):
        if wt != 2:
            continue
        if num == 2:
            name = val.decode("utf-8", "replace")
        elif num == 4:
            metas.append(_map_value(val))
        elif num == 5:                       # XStatMetadata: id=1, name=2
            sid, sname = 0, ""
            for n2, w2, v2 in _fields(_map_value(val)):
                if n2 == 1:
                    sid = v2
                elif n2 == 2 and w2 == 2:
                    sname = v2.decode("utf-8", "replace")
            stat_names[sid] = sname
    out: Dict[str, str] = {}
    if not name.startswith("/device:") or "CPU" in name:
        return out
    for meta in metas:                       # XEventMetadata
        ename, stats = "", []
        for num, wt, val in _fields(meta):
            if num == 2 and wt == 2:
                ename = val.decode("utf-8", "replace")
            elif num == 5 and wt == 2:
                stats.append(_stat(val))
        for mid, text, ref in stats:
            if stat_names.get(mid) == "tf_op":
                # a string, or a reference to a stat metadata's name
                op = text if text is not None else stat_names.get(ref, "")
                if op and ename not in out:
                    out[ename] = op
    return out


@functools.lru_cache(maxsize=4)
def _read(path: str, mtime_ns: int) -> Dict[str, str]:
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, str] = {}
    for num, wt, val in _fields(space):      # XSpace.planes = 1
        if num == 1 and wt == 2:
            for k, v in _plane_tf_ops(val).items():
                out.setdefault(k, v)
    return out


def op_paths(xplane: Path) -> Dict[str, str]:
    """Operation name (the full HLO string that keys ``ctx["trace"]
    ["ops"]``) -> ``tf_op`` scope path, over the device planes."""
    xplane = Path(xplane)
    return _read(str(xplane), xplane.stat().st_mtime_ns)


# ---- scopes ------------------------------------------------------------

def scope_of(tf_op: Optional[str]) -> str:
    """The deepest ``h2o.`` component of a ``tf_op`` path, else
    ``UNSCOPED``."""
    if tf_op:
        for part in reversed(tf_op.rsplit(":", 1)[0].split("/")):
            if part.startswith("h2o."):
                return part
    return UNSCOPED


def by_scope(ops: Dict[str, Tuple[float, int]],
             paths: Dict[str, str]) -> Dict[str, float]:
    """Self seconds by scope, from ``trace.reduce_xplane``'s ``ops``."""
    out: Dict[str, float] = {}
    for name, (seconds, _events) in ops.items():
        sc = scope_of(paths.get(name))
        out[sc] = out.get(sc, 0.0) + seconds
    return out


def window_scopes(ctx) -> Optional[Dict[str, float]]:
    """Seconds by scope of the run's traced slice: ``ctx["trace"]`` joined
    to the newest trace file under the harness's output directory.  None
    where there is no trace, or the program names no ``h2o.`` scope (a
    parent commit): the readers then leave their metrics out."""
    tr = ctx.get("trace")
    if not tr or not tr.get("ops"):
        return None
    xp = trace.find_xplane(harness.OUT_DIR)
    if xp is None:
        return None
    scopes = by_scope(tr["ops"], op_paths(xp))
    if not any(s != UNSCOPED for s in scopes):
        return None
    return scopes


def share_pct(ctx, *prefixes: str) -> Optional[float]:
    """Per cent of the slice's device self time under the scopes that
    start with one of ``prefixes`` (a reader's whole body)."""
    scopes = window_scopes(ctx)
    if scopes is None:
        return None
    total = sum(scopes.values())
    if total <= 0:
        return None
    return 100.0 * sum(s for name, s in scopes.items()
                       if name.startswith(prefixes)) / total


# ---- by hand -----------------------------------------------------------

def gaps_by_span(xplane: Path, top: int = 10) -> List[List]:
    """The device's idle gaps, summed by the innermost ``h2o:`` host
    span that covers half of the gap or more."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(xplane))
    spans, busy = [], []
    for p in profile.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                spans.extend((int(e.start_ns),
                              int(e.start_ns + e.duration_ns), e.name)
                             for e in ln.events
                             if e.name.startswith("h2o:"))
    planes = trace._device_planes(profile)
    if not planes:
        return []
    for ln in trace._op_lines(planes[0]):
        busy.extend((int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in ln.events if e.duration_ns > 0)
    if not busy:
        return []
    merged = trace.union(busy)
    named: Dict[str, float] = {}
    for s, e in trace.gaps(merged, (merged[0][0], merged[-1][1])):
        best, best_len = "no h2o: span", None
        for hs, he, hn in spans:
            if 2 * (min(e, he) - max(s, hs)) >= e - s and (
                    best_len is None or he - hs < best_len):
                best, best_len = hn, he - hs
        named[best] = named.get(best, 0.0) + (e - s) / 1e9
    return sorted(([k, v] for k, v in named.items()),
                  key=lambda t: -t[1])[:top]


def main(argv) -> int:
    path = Path(argv[1])
    if path.is_dir():
        path = trace.find_xplane(path)
    tr = trace.reduce_xplane(path)
    if tr is None:
        print("no device operation in", path)
        return 1
    paths = op_paths(path)
    scopes = by_scope(tr["ops"], paths)
    total = sum(scopes.values())
    print(f"{path}: busy {tr['busy_s']:.6f} s of {tr['window_s']:.6f} s")
    print(f"{'scope':<28}{'self s':>12}{'%':>9}")
    for name, s in sorted(scopes.items(), key=lambda t: -t[1]):
        print(f"{name:<28}{s:>12.6f}{100 * s / total:>9.3f}")
    print(f"{'sum':<28}{total:>12.6f}{100.0:>9.3f}")
    loose = sorted(((s, n) for n, (s, _) in tr["ops"].items()
                    if scope_of(paths.get(n)) == UNSCOPED), reverse=True)
    for s, n in loose[:8]:
        print(f"  unscoped {s:.6f} s  {paths.get(n, '-')[:60]}  {n[:70]}")
    print("idle gaps by innermost h2o: span:")
    for name, s in gaps_by_span(path):
        print(f"  {s:.9f} s  {name}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
