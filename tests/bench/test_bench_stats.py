"""Percentiles over every request, rates over the whole window, peaks."""
import pytest
from bench_testkit import ROOT  # noqa: F401

from bench import peaks, stats


def test_percentiles_take_every_value():
    values = list(range(1, 101))            # 1..100
    assert stats.percentile(values, 50) == pytest.approx(50.5)
    assert stats.percentile(values, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_over_the_whole_window():
    assert stats.rate(300, 10.0) == 30.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_peaks_known_kind_and_unknown_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(peaks.UnknownDevice, match="TPU v4"):
        peaks.peaks_for("TPU v4")
