"""The control, kept at a size a test run can hold (200,000 rows): the
reference put in the program's place and computed in a lower precision
than the configuration states has to come out as not correct under the
cell's own limits; computed as stated it reads nought.  The readings at
the cells' own size are in PERF.md (``benchmark/tests/readings.py``)."""

import numpy as np
import pytest

from benchmark import harness
from benchmark.data import higgs_like
from benchmark.kinds.train_budgeted import spec_of
from benchmark.reference.gbm import GbmReference, round_like
from benchmark.tests.readings import reading

CELLS = ["gbm-higgs-xgbhist.train", "gbm-higgs-h2odefault.train"]


def test_round_like():
    x = np.array([1.0 + 2.0 ** -10, 3.14159274], np.float32)
    assert np.all(round_like(x, None) == x.astype(np.float64))
    assert round_like(x, "bf16")[0] == 1.0          # 8 bits of mantissa
    assert round_like(x, "high")[0] == float(x[0])  # 16 bits keep 2**-10
    assert abs(round_like(x, "high")[1] - x[1]) <= 2.0 ** -16 * 4
    assert round_like(x, "high")[1] != float(x[1])


@pytest.fixture(scope="module", params=CELLS)
def cell(request):
    bench = harness.load_benchmark()
    _, config, traffic = harness.load_cell(bench, request.param)
    X, y = higgs_like(200_000, int(config["cols"]), 2 ** 31 + 5)
    ref = GbmReference(X, y, spec_of(config))
    ref.prepare()
    return ref, traffic


def over(nums, limits):
    return [k for k, v in nums.items() if k in limits and not v <= limits[k]]


def test_sound_reference_reads_nought(cell):
    ref, traffic = cell
    nums = reading(ref, "sound", int(traffic["check_trees"]),
                   int(traffic["search_trees"]))
    assert over(nums, traffic["limits"]) == []
    assert nums["leaf_value_gap"] == 0.0 and nums["logloss_gap"] == 0.0


@pytest.mark.parametrize("mode", ["bf16", "half_batch",
                                  "stale_state", "altered"])
def test_control_and_faults_are_not_correct(cell, mode):
    ref, traffic = cell
    nums = reading(ref, mode, int(traffic["check_trees"]),
                   int(traffic["search_trees"]))
    assert over(nums, traffic["limits"]), (mode, nums)
