"""The data-driven harness: it knows no cell, configuration, traffic mix
or metric by name.  ``BENCHMARK.json`` names them; each lives in a file
of its own that is found by that name:

    benchmark/configs/<config>.json    a configuration: source, params, sizes
    benchmark/traffic/<traffic>.json   a traffic mix: its ``kind`` and parameters
    benchmark/kinds/<kind>.py          the one generator of a kind of window
    benchmark/metrics/<metric>.py      a per-layer metric's reader

so a later PR adds files and entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT_DIR = HERE / "out"          # traces and per-run logs; git-ignored


class Refused(SystemExit):
    """The run cannot be a measurement (no accelerator, unknown cell):
    exit non-zero and print no result."""

    def __init__(self, why: str, code: int = 3):
        print(f"benchmark: refused: {why}", file=sys.stderr)
        super().__init__(code)


@dataclass
class Job:
    """One run of one cell, as the command line and the files give it."""
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float                       # time.monotonic() at process entry
    out_dir: Path = OUT_DIR
    device: Dict[str, Any] = field(default_factory=dict)


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def load_module(directory: str, name: str):
    """``benchmark/<directory>/<name>.py`` by path: a metric's name may
    hold ``.`` and ``-``, which an import statement could not spell."""
    path = HERE / directory / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {directory} file for {name!r}: {path}")
    modname = "benchmark_%s_%s" % (
        directory, "".join(c if c.isalnum() else "_" for c in name))
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise Refused(f"BENCHMARK.json has no workload {workload!r}")


def load_cell(bench: Dict[str, Any], workload: str):
    """(cell, configuration, traffic) for a ``workloads`` name."""
    cell = find_cell(bench, workload)
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == cell["config"]), None)
    if cfg_entry is None:
        raise Refused(f"no configuration {cell['config']!r}")
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def metrics_for(bench: Dict[str, Any], group: str,
                workload: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    that list it under ``workloads``, and those with no such key."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def require_accelerator(chips: int) -> Dict[str, Any]:
    """The device as JAX reports it; refuses the CPU and too few chips.
    The harness has no option that lets a CPU run through."""
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform == "cpu":
        raise Refused("JAX found no accelerator (platform "
                      f"{devs[0].platform if devs else None!r})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def result_line(bench, job: Job, ctx: Dict[str, Any]) -> Dict[str, Any]:
    """The one JSON object a run prints last."""
    name = job.cell["name"]
    metrics: Dict[str, Dict[str, Any]] = {}
    if job.trace:
        for m in metrics_for(bench, "per_layer", name):
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_for(bench, "end_to_end", name):
            if m["name"] in ctx["end_to_end"]:
                metrics[m["name"]] = {"value": ctx["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    device = dict(job.device)
    device["memory_peak_bytes"] = ctx.get("memory_peak_bytes")
    line: Dict[str, Any] = {
        "correct": bool(ctx["correct"]), "attempted": ctx["attempted"],
        "failed": ctx["failed"], "metrics": metrics, "device": device}
    tr = ctx.get("trace")
    if job.trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"][:10],
                             "idle_gaps": tr["idle_gaps"][:10]}
    line["workload"] = name
    line["seed"] = job.seed
    line["notes"] = ctx.get("notes", {})
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in ctx["compared"].items()}
    return line


def emit(line: Dict[str, Any]) -> None:
    """Numbers compared beside their limits as the last lines of standard
    error, the result as the last line of standard output."""
    sys.stdout.flush()
    for k, c in line["compared"].items():
        print(f"compared {k}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    log = HERE / "out" / "runs.jsonl"
    try:
        log.parent.mkdir(parents=True, exist_ok=True)
        with open(log, "a") as f:
            f.write(json.dumps(line) + "\n")
    except OSError:
        pass                    # the log is a convenience, not a result
    print(json.dumps(line), flush=True)
