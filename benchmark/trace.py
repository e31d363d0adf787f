"""Reduction of a profiler trace (``.xplane.pb``) to the device's busy
time, its idle gaps and the operations that took most of it.

Busy is the union of the intervals in which an operation ran on the
device; the idle share is 1 - busy / traced span.  Kept with the
benchmark so that every PR computes the same number the same way.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]      # (start_ns, end_ns)

# device lines that restate other lines' time at a coarser grain
_SUMMARY_LINES = ("Steps", "XLA Modules", "Framework Ops",
                  "Framework Name Scope", "Source code")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


def gaps(merged: List[Interval], span: Interval) -> List[Interval]:
    """Idle intervals of ``span`` that ``merged`` (a union) leaves."""
    out, at = [], span[0]
    for s, e in merged:
        if s > at:
            out.append((at, min(s, span[1])))
        at = max(at, e)
    if at < span[1]:
        out.append((at, span[1]))
    return [(s, e) for s, e in out if e > s]


def self_times(events: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Per name, duration minus what nested events on the same line cover
    (a ``while`` holds its body's operations)."""
    total: Dict[str, int] = {}
    stack: List[List] = []          # [end, name, child_ns, start]
    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            end, nm, child, st = stack.pop()
            total[nm] = total.get(nm, 0) + (end - st) - child
        if stack:
            stack[-1][2] += min(e, stack[-1][0]) - s
        stack.append([e, name, 0, s])
    while stack:
        end, nm, child, st = stack.pop()
        total[nm] = total.get(nm, 0) + (end - st) - child
    return total


_STEM = re.compile(r"^(%?[A-Za-z_\-]+(?:[._][A-Za-z_\-]+)*)[.\d]* = (\(?\w+\[[\d,]*\])")


def op_groups(ops: Dict[str, Tuple[float, int]], top: int = 12):
    """Self seconds and events by kind of operation: HLO names differ
    only in a serial number (``%fusion.194``), so group by the name's
    stem, the result's shape and the fusion kind."""
    groups: Dict[str, List[float]] = {}
    for name, (seconds, events) in ops.items():
        m = _STEM.match(name)
        key = f"{m.group(1)} {m.group(2)}" if m else name[:60]
        k = re.search(r"kind=(\w+)", name)
        if k:
            key += " " + k.group(1)
        g = groups.setdefault(key, [0.0, 0, 0])
        g[0] += seconds
        g[1] += events
        g[2] += 1
    return sorted(([k, v[0], v[1], v[2]] for k, v in groups.items()),
                  key=lambda t: -t[1])[:top]


def find_xplane(logdir: Path) -> Optional[Path]:
    files = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def _device_planes(profile):
    return [p for p in profile.planes
            if p.name.startswith("/device:") and "CPU" not in p.name]


def _op_lines(plane):
    lines = [ln for ln in plane.lines if ln.name not in _SUMMARY_LINES]
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    return named or lines


def reduce_xplane(path: Path, top: int = 10) -> Optional[Dict]:
    """``busy_s`` (averaged over the device planes), ``window_s`` (the
    span of device activity traced, first start to last end over all
    planes and summary lines), the ``top`` operations by self time and
    the longest idle gaps, each named by the host event that overlapped
    it most.  None where the trace holds no device operation."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(path))
    planes = _device_planes(profile)
    per_plane, all_events, lo, hi = [], [], None, None
    for plane in planes:
        iv = []
        for ln in _op_lines(plane):
            ev = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                  for e in ln.events if e.duration_ns > 0]
            iv.extend((s, t) for s, t, _ in ev)
            all_events.append(ev)
        if iv:
            per_plane.append(union(iv))
            lo = min(iv)[0] if lo is None else min(lo, min(iv)[0])
            hi = max(t for _, t in iv) if hi is None else \
                max(hi, max(t for _, t in iv))
    if not per_plane or hi is None or hi <= lo:
        return None
    span = (lo, hi)
    busy = sum(sum(e - s for s, e in m) for m in per_plane) / len(per_plane)
    ops: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    for ev in all_events:
        for name, ns in self_times(ev).items():
            ops[name] = ops.get(name, 0) + ns
        for _, _, name in ev:
            counts[name] = counts.get(name, 0) + 1
    device_ops = sorted(((n[:160], ns / 1e9 / len(per_plane))
                         for n, ns in ops.items()),
                        key=lambda t: -t[1])[:top]
    host = []
    for p in profile.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                host.extend((int(e.start_ns),
                             int(e.start_ns + e.duration_ns), e.name)
                            for e in ln.events if e.duration_ns > 20_000)
    idle = sorted(gaps(per_plane[0], span), key=lambda g: g[0] - g[1])
    named: Dict[str, float] = {}
    for s, e in idle[:100]:
        # the most specific host event that covers half of the gap or more
        best, best_len = "host: no event recorded", None
        for hs, he, hn in host:
            if 2 * (min(e, he) - max(s, hs)) >= e - s and (
                    best_len is None or he - hs < best_len):
                best, best_len = hn, he - hs
        named[best] = named.get(best, 0.0) + (e - s) / 1e9
    idle_gaps = sorted(named.items(), key=lambda t: -t[1])[:top]
    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            # every operation: name -> (self seconds, events), all planes
            "ops": {n: (ns / 1e9, counts.get(n, 0)) for n, ns in ops.items()},
            "device_ops": [[n, s] for n, s in device_ops],
            "idle_gaps": [[n, s] for n, s in idle_gaps]}
