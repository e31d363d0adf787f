"""Of set-up's programs, those compiled and written to the persistent
compile cache (``cache`` "compiled"): what a warm cache would have
served.  It reads 0 where an earlier run warmed the cache, and tells a
cold side of a pair from a warm one.  A program whose compile is quicker
than the cache's threshold is compiled in every process and not counted
(``cache`` "uncached"; benchmark/programs.py)."""

from benchmark import programs

UNIT, LAYER, MOVES, SOURCE = "count", "compile", "setup_s", \
    "program_counter"


def read(ctx, events=None, table=None):
    mine = programs.setup_programs(events, table)
    if mine is None:
        return None
    return sum(1 for p in mine if p["cache"] == "compiled")
