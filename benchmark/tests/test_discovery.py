"""A new configuration, traffic mix, kind, metric reader and ``workloads``
entry are each a new file or entry: nothing that is there is edited.
Shown on a copy of the benchmark's tree, through the command's own
``main``; and the checked-in files agree with ``BENCHMARK.json``."""

import json
import shutil

import pytest

from benchmark import harness, run


def test_files_agree_with_benchmark_json():
    bench = harness.load_benchmark()
    for c in bench["configs"]:
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["env"] == {"H2O_TPU_AUTOTUNE": "0"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        mod = harness.load_module("metrics", m["name"])
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert m["moves"] in e2e
    for w in bench["workloads"]:
        _, _, traffic = harness.load_cell(bench, w["name"])
        assert (harness.HERE / "kinds" / f"{traffic['kind']}.py").is_file()


KIND = '''
def run(job):
    return {"end_to_end": {"setup_s": 1.5, "ops_rate": 7.0},
            "clocks": {}, "counters": {"answer": 42}, "trace": None,
            "memory_peak_bytes": 1, "attempted": 3, "failed": 0,
            "compared": {"exact": (0, 0)}, "correct": True,
            "notes": {"env": __import__("os").environ.get("NEW_SWITCH")}}
'''
READER = '''
UNIT, LAYER, MOVES, SOURCE = "count", "new layer", "ops_rate", "program_counter"


def read(ctx):
    return ctx["counters"].get("answer")
'''


def test_add_files_and_entries_only(tmp_path, monkeypatch, capsys):
    root = tmp_path / "checkout"
    shutil.copytree(harness.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = harness.load_benchmark()
    b = root / "benchmark"
    (b / "configs" / "new-config.json").write_text(json.dumps(
        {"name": "new-config", "source": "a paper", "reduced": [],
         "env": {"NEW_SWITCH": "on"}}))
    (b / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "new_kind"}))
    (b / "kinds" / "new_kind.py").write_text(KIND)
    (b / "metrics" / "new.metric-1.py").write_text(READER)
    bench["configs"].append(
        {"name": "new-config", "source": "a paper",
         "file": "benchmark/configs/new-config.json", "reduced": [],
         "why": "w"})
    bench["workloads"].append(
        {"name": "new-config.new-mix", "config": "new-config",
         "traffic": "new-mix", "chips": 1, "why": "w"})
    bench["end_to_end"].append(
        {"name": "ops_rate", "unit": "ops/s", "better": "higher",
         "bound": 0.01, "source": "host_clock",
         "workloads": ["new-config.new-mix"]})
    bench["per_layer"].append(
        {"name": "new.metric-1", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "new layer",
         "moves": "ops_rate", "workloads": ["new-config.new-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "HERE", b)
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.delenv("NEW_SWITCH", raising=False)

    def last_line(trace):
        assert run.main(["--workload", "new-config.new-mix", "--seed",
                         "3000000000", "--seconds", "1", "--trace",
                         str(trace)]) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    line = last_line(0)
    assert line["metrics"] == {"ops_rate": {"value": 7.0, "unit": "ops/s"},
                               "setup_s": {"value": 1.5, "unit": "s"}}
    assert line["notes"]["env"] == "on"      # the configuration's switch
    assert line["seed"] == 3000000000
    line = last_line(1)
    assert line["metrics"] == {"new.metric-1": {"value": 42,
                                                "unit": "count"}}
    assert [k for k in ("correct", "attempted", "failed", "metrics",
                        "device") if k not in line] == []


def test_refusals(monkeypatch):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "no-such-cell", "--seed", "1",
                  "--seconds", "1"])
    assert e.value.code != 0
    # under JAX_PLATFORMS=cpu the real look for a chip refuses
    import jax
    if jax.devices()[0].platform == "cpu":
        with pytest.raises(SystemExit) as e:
            harness.require_accelerator(1)
        assert e.value.code != 0
