"""The seam between the program and ``benchmark/``: every name the
yardstick imports from ``h2o_tpu`` still imports, and the counters its
``safety_net_events`` metric sums are still there.  A deletion that
breaks the seam fails here on a CPU in seconds, not on the chip as a
malformed run (the benchmark's own files cannot be edited by the PR
that breaks them).
"""

import ast
import importlib
import pathlib

BENCHMARK = pathlib.Path(__file__).resolve().parent.parent / "benchmark"


def _program_imports(root=BENCHMARK):
    """(module, name) for every ``from h2o_tpu... import name`` in the
    benchmark's non-test files under ``root``, found by reading them."""
    found = set()
    paths = [root] if root.is_file() else sorted(root.rglob("*.py"))
    for path in paths:
        if "tests" in path.relative_to(BENCHMARK).parts:
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.split(".")[0] == "h2o_tpu":
                found.update((node.module, a.name) for a in node.names)
    return sorted(found)


def _load(module, name):
    mod = importlib.import_module(module)
    if not hasattr(mod, name):                  # a submodule, not yet loaded
        return importlib.import_module(f"{module}.{name}")
    return getattr(mod, name)


def test_every_name_the_benchmark_imports_is_there():
    imports = _program_imports()
    assert imports                              # the reader found the seam
    for module, name in imports:
        _load(module, name)


# the counters ``safety_net_events`` sums, by the module it reads them from
COUNTERS = {"oom": {"oom_events", "degradations", "terminal_failures"},
            "autotune": {"probe_failures", "parity_disqualified",
                         "resolve_errors"}}


def test_safety_net_counters_are_there():
    read = _program_imports(BENCHMARK / "metrics" / "safety_net_events.py")
    assert read
    for module, name in read:
        if name in COUNTERS:
            assert COUNTERS[name] <= set(_load(module, name).stats())
