"""The programs set-up made ready, from the program's own table.

``DispatchStats.programs()`` (h2o_tpu/core/diag.py) keeps one record per
program JAX made ready: ``fun``, ``trace_s``, ``lower_s``, ``compile_s``,
``cache`` ("hit": loaded from the persistent compile cache; "compiled":
compiled and written to it; "uncached": compiled, too quick for the
cache to keep; "traced": no backend compile followed) and ``ns``, its
start on the ring's clock.  Set-up's are those that started before the
window's root ``job.run`` (benchmark/spans.py); a program made after the
window, such as a probe that scores the model, is not set-up's.  A
program without the table (a parent commit) or a ring without a window
gives None, and the readers leave their metrics out.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark import spans


def setup_programs(events: Optional[List[Dict]] = None,
                   programs: Optional[List[Dict]] = None
                   ) -> Optional[List[Dict]]:
    """The records of the programs made ready before the window."""
    if programs is None:
        from h2o_tpu.core.diag import DispatchStats
        try:
            programs = DispatchStats.programs()
        except AttributeError:
            return None             # a parent that keeps no table
    root = next((e for e in spans.window_spans(events)
                 if (e["kind"], e["what"]) == ("job", "run")), None)
    if root is None:
        return None
    return [p for p in programs if p["ns"] < root["ns"]]
