"""Programs set-up made ready: the records of ``DispatchStats.programs()``
that began before the window's root ``job.run`` and reached a backend
compile, loaded from the persistent cache or compiled (eager single-op
programs count; benchmark/programs.py)."""

from benchmark import programs

UNIT, LAYER, MOVES, SOURCE = "count", "compile", "setup_s", \
    "program_counter"


def read(ctx, events=None, table=None):
    mine = programs.setup_programs(events, table)
    if mine is None:
        return None
    return sum(1 for p in mine if p["cache"] != "traced")
