"""The work count from shapes, against hand-computed cases for both
configurations, and the peaks table."""

import pytest

from benchmark import peaks, work


def test_bin_index_bytes():
    assert work.bin_index_bytes(20) == 1
    assert work.bin_index_bytes(255) == 1       # 256 values fit a byte
    assert work.bin_index_bytes(256) == 2
    assert work.bin_index_bytes(1024) == 2


def test_xgbhist_tree():
    # 10.5M rows x (28 one-byte bins + 4-byte node id + two 4-byte stats)
    lw = work.level_work(10_500_000, 28, 255)
    assert lw["bytes"] == 10_500_000 * 40 == 420_000_000
    assert lw["ops"] == 2 * 10_500_000 * 28 * 2 == 1_176_000_000
    tw = work.tree_work(10_500_000, 28, 255, 8)
    assert tw["bytes"] == 3_360_000_000 and tw["ops"] == 9_408_000_000


def test_h2odefault_tree():
    # rows are stored on the 1024-bin fine grid: two-byte indices
    tw = work.tree_work(10_500_000, 28, 20, 5, fine_nbins=1024)
    assert tw["bytes"] == 5 * 10_500_000 * (28 * 2 + 12) == 3_570_000_000
    assert tw["ops"] == 5 * 1_176_000_000


def test_least_seconds_is_bytes_bound():
    tw = work.tree_work(10_500_000, 28, 255, 8)
    ls = peaks.least_seconds(tw["ops"], tw["bytes"], "TPU v5 lite")
    assert ls["bound"] == "bytes"
    assert ls["seconds"] == pytest.approx(3.36e9 / 819e9)
    four = peaks.least_seconds(tw["ops"], tw["bytes"], "TPU v5 lite", 4)
    assert four["seconds"] == pytest.approx(ls["seconds"] / 4)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
