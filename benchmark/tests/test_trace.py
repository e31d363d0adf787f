"""The trace reduction: interval arithmetic by hand, and the small trace
recorded on a v5e that is checked in beside this file."""

from pathlib import Path

import pytest

from benchmark import trace

FIXTURE = Path(__file__).with_name("fixture_trace.xplane.pb")


def test_union_and_busy():
    iv = [(0, 10), (5, 12), (20, 30), (30, 31), (40, 41)]
    assert trace.union(iv) == [(0, 12), (20, 31), (40, 41)]
    assert trace.busy_ns(iv) == 12 + 11 + 1


def test_gaps():
    merged = [(5, 12), (20, 31)]
    assert trace.gaps(merged, (0, 40)) == [(0, 5), (12, 20), (31, 40)]
    assert trace.gaps(merged, (5, 31)) == [(12, 20)]


def test_self_times_nested():
    # a while of 100 ns holding two bodies of 30 ns, then a lone op
    ev = [(0, 100, "while"), (10, 40, "body"), (50, 80, "body"),
          (120, 130, "copy")]
    assert trace.self_times(ev) == {"while": 40, "body": 60, "copy": 10}


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded trace")
def test_recorded_trace():
    r = trace.reduce_xplane(FIXTURE)
    assert r is not None
    # four bursts of three matrix products with 50 ms sleeps between
    assert 0 < r["busy_s"] < r["window_s"]
    idle = 1 - r["busy_s"] / r["window_s"]
    assert 0.9 < idle < 0.999      # 90 us products, 50 ms sleeps
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=0.05)
    # the longest gaps are the host's sleeps
    assert "sleep" in r["idle_gaps"][0][0]
