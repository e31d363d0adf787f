"""XLA backend compiles inside the window
(``DispatchStats.xla_compiles()`` after - before).  The cell's invariant:
it reads 0."""

UNIT, LAYER, MOVES, SOURCE = "count", "compile", "train_rate", \
    "program_counter"


def read(ctx):
    return ctx["counters"].get("window_compiles")
