"""The window's host spans, from the program's own ring.

``TimeLine.span`` (h2o_tpu/core/diag.py) leaves one event per span in
the ``/3/Timeline`` ring: ``kind``, ``what``, ``ns`` (start), ``dur_ns``
(HOST time), ``id``, ``parent`` and ``job``, the key every span of one
training shares.  The window is the last ``job.run`` root with
``train.block.*`` descendants: set-up's warm-up train is the one before
it, and ``warm_final_scoring`` runs under no job.  The ring holds 2,048
events; a window writes a few dozen.  A program without spans (a parent
commit) gives an empty list and the readers leave their metrics out.
"""

from __future__ import annotations

from typing import Dict, List, Optional


def window_spans(events: Optional[List[Dict]] = None) -> List[Dict]:
    """Every span of the window's job, in the ring's (closing) order."""
    if events is None:
        from h2o_tpu.core.diag import TimeLine
        events = TimeLine.snapshot()
    spans = [e for e in events if "dur_ns" in e and e.get("job")]
    trained = {e["job"] for e in spans if e["kind"] == "train"
               and e["what"].startswith("block.")}
    for root in reversed(spans):
        if (root["kind"], root["what"]) == ("job", "run") and \
                root["job"] in trained:
            return [e for e in spans if e["job"] == root["job"]]
    return []


def seconds(spans: List[Dict], kind: str, what: str) -> Optional[float]:
    """Summed host seconds of the spans ``kind.what``; None if none."""
    mine = [e["dur_ns"] for e in spans
            if e["kind"] == kind and e["what"] == what]
    return sum(mine) / 1e9 if mine else None
