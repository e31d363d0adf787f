"""Host clock around data -> ``Frame`` on the device, ending in
``block_until_ready`` (ingest: core/frame.py, core/landing.py)."""

UNIT, LAYER, MOVES, SOURCE = "s", "ingest", "setup_s", "host_clock"


def read(ctx):
    return ctx["clocks"].get("landing_s")
