"""Seconds the process spent getting its programs ready, as JAX reports
them to ``DispatchStats``'s listener: tracing, lowering to MLIR and the
backend compile, which holds the persistent-cache lookup (so
``cache_retrieval_time_sec`` is a part of it and is not added again).
The window adds nothing while ``window_compiles`` reads 0, so this is
set-up's (compile: core/exec_store.py, the XLA persistent cache)."""

UNIT, LAYER, MOVES, SOURCE = "s", "compile", "setup_s", "program_counter"

_ADDENDS = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
            "backend_compile_duration")


def read(ctx):
    from h2o_tpu.core.diag import DispatchStats
    try:
        secs = DispatchStats.compile_seconds()
    except AttributeError:
        return None                 # a parent that keeps no durations
    if not secs:
        return None
    return sum(secs.get(k, 0.0) for k in _ADDENDS)
