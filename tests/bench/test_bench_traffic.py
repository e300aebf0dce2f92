"""The traffic generator: rates from the seed, and the same work for every seed."""
import json

import numpy as np
import pytest
from bench_testkit import ROOT

from bench import traffic


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 1])
def test_poisson_holds_rate_times_window(seed):
    t = traffic.arrival_times({"process": "poisson", "rate_per_s": 1000.0}, 5.0,
                              np.random.default_rng(seed))
    assert len(t) == 5000
    assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < 5.0
    gaps = np.diff(t)
    assert abs(gaps.mean() - 1e-3) < 5e-5
    assert 0.9 < gaps.std() / gaps.mean() < 1.1          # exponential gaps


def test_same_seed_same_schedule_other_seed_other_order():
    spec = {"process": "mmpp2", "mean_rate_per_s": 800.0, "high_to_low": 4, "mean_dwell_s": 0.5}
    a = traffic.arrival_times(spec, 10.0, np.random.default_rng(3))
    b = traffic.arrival_times(spec, 10.0, np.random.default_rng(3))
    c = traffic.arrival_times(spec, 10.0, np.random.default_rng(4))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert abs(len(a) - len(c)) <= 1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_mmpp_mean_rate_and_states(seed):
    spec = {"process": "mmpp2", "mean_rate_per_s": 800.0, "high_to_low": 4, "mean_dwell_s": 0.5}
    t = traffic.arrival_times(spec, 10.0, np.random.default_rng(seed))
    assert abs(len(t) / 10.0 - 800.0) <= 1
    low, high = traffic.mmpp2_rates(spec)
    assert (low, high) == pytest.approx((320.0, 1280.0))
    # half the window runs at each rate: 100 ms bins take one of two levels
    counts = np.histogram(t, bins=100, range=(0, 10))[0] / 0.1
    assert np.mean(counts > 800) == pytest.approx(0.5, abs=0.12)


def test_repository_mixes_load(tmp_path):
    for path in sorted((ROOT / "bench/traffic").glob("*.json")):
        mix = traffic.load(path)
        assert mix["loop"] in traffic.LOOPS
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"loop": "closed", "clients": 0, "prompt_tokens": 1,
                               "new_tokens": 1}))
    with pytest.raises(ValueError, match="clients"):
        traffic.load(bad)
