"""Seconds set-up's programs spent tracing and lowering to MLIR
(``trace_s + lower_s`` of the records before the window's root
``job.run``): the part of getting a program ready that JAX does again in
every process, whatever the persistent cache holds (benchmark/programs.py).
A part of ``program_ready_s``."""

from benchmark import programs

UNIT, LAYER, MOVES, SOURCE = "s", "compile", "setup_s", "program_counter"


def read(ctx, events=None, table=None):
    mine = programs.setup_programs(events, table)
    if mine is None:
        return None
    return sum(p["trace_s"] + p["lower_s"] for p in mine)
