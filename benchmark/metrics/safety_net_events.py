"""Times a safety net caught the process since it started: the OOM
ladder's events, degradations and terminal failures (core/oom.py) and
the autotuner's failed probes, parity disqualifications and resolve
errors (core/autotune.py).  The cell's invariant: it reads 0."""

UNIT, LAYER, MOVES, SOURCE = "count", "safety nets", "train_rate", \
    "program_counter"


def read(ctx):
    from h2o_tpu.core import autotune, oom
    o, a = oom.stats(), autotune.stats()
    return (o["oom_events"] + o["degradations"] + o["terminal_failures"]
            + a["probe_failures"] + a["parity_disqualified"]
            + a["resolve_errors"])
