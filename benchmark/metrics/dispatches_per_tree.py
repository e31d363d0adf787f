"""Program dispatches the window made (``DispatchStats`` summed over
phases, after - before) per tree built (tree driver:
models/tree/driver.py)."""

UNIT, LAYER, MOVES, SOURCE = "1/tree", "tree driver", "train_rate", \
    "program_counter"


def read(ctx):
    c = ctx["counters"]
    if not c.get("trees"):
        return None
    return c["dispatches"] / c["trees"]
