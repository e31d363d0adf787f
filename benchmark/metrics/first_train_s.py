"""Host clock around the warm-up ``train()``: binning, and every program
of the window compiled or loaded from the disk cache (compile:
core/exec_store.py, the XLA persistent cache)."""

UNIT, LAYER, MOVES, SOURCE = "s", "compile", "setup_s", "host_clock"


def read(ctx):
    return ctx["clocks"].get("first_train_s")
