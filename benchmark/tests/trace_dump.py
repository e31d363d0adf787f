"""Look at one trace by hand: planes, lines, and the longest events.

    python3 -m benchmark.tests.trace_dump <dir-or-file.xplane.pb> [n]
"""

from __future__ import annotations

import sys
from pathlib import Path

from benchmark import trace as trace_mod


def main(argv) -> int:
    from jax.profiler import ProfileData
    path = Path(argv[1])
    n = int(argv[2]) if len(argv) > 2 else 8
    if path.is_dir():
        path = trace_mod.find_xplane(path)
    print("file", path, path.stat().st_size, "bytes")
    for plane in ProfileData.from_file(str(path)).planes:
        print("plane", repr(plane.name))
        for ln in plane.lines:
            ev = list(ln.events)
            print("  line", repr(ln.name), len(ev), "events")
            for e in sorted(ev, key=lambda e: -e.duration_ns)[:n]:
                print("     %12d ns  start %d  %s" % (
                    e.duration_ns, e.start_ns, e.name[:100]))
    print("reduced:", trace_mod.reduce_xplane(path))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
