"""The one traffic generator: it reads a mix's parameters from
``traffic/<mix>.json`` and makes the requests of a run from the seed.

Two loops:

- ``open``: invocations arrive on a schedule whatever the system does.
  ``arrivals.process`` is ``poisson`` (``rate_per_s``) or ``mmpp2``, a
  two-state Markov-modulated Poisson process (``mean_rate_per_s``,
  ``high_to_low`` rate ratio, ``mean_dwell_s`` in each state).
- ``closed``: ``clients`` callers, each sending its next request when the
  last one is answered; ``prompt_tokens`` and ``new_tokens`` per request.

Every seed gets the same amount of work in another order, so that a
seed changes no median: a Poisson window holds ``round(rate x seconds)``
arrivals (a Poisson process conditioned on its count), and an MMPP
window spends half its time in each state, in dwells whose lengths are
one fixed set of exponential quantiles, shuffled.  The inter-arrival
sampling follows ``repro.core.workload`` (exponential gaps; memoryless
dwells), rescaled onto that fixed count.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

LOOPS = ("open", "closed")
PROCESSES = ("poisson", "mmpp2")


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    loop = mix.get("loop")
    if loop not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}, not {loop!r}")
    if loop == "open":
        proc = mix.get("arrivals", {}).get("process")
        if proc not in PROCESSES:
            raise ValueError(f"{path}: arrivals.process must be one of {PROCESSES}")
    else:
        for k in ("clients", "prompt_tokens", "new_tokens"):
            if not isinstance(mix.get(k), int) or mix[k] < 1:
                raise ValueError(f"{path}: {k} must be a positive whole number")
    return mix


def _conditioned_poisson(rng: np.random.Generator, n: int, span: float) -> np.ndarray:
    """n arrival times of a Poisson process on [0, span) given that it had n:
    exponential gaps, scaled so that an (n+1)-th arrival would land on span."""
    if n <= 0:
        return np.empty(0)
    gaps = rng.exponential(1.0, size=n + 1)
    return span * np.cumsum(gaps)[:-1] / gaps.sum()


def mmpp2_rates(arrivals: dict) -> tuple:
    """(low, high) rates with equal mean dwell, so the mean is their average."""
    ratio = float(arrivals["high_to_low"])
    low = 2.0 * float(arrivals["mean_rate_per_s"]) / (1.0 + ratio)
    return low, low * ratio


def arrival_times(arrivals: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Sorted due times in [0, seconds)."""
    if arrivals["process"] == "poisson":
        n = round(float(arrivals["rate_per_s"]) * seconds)
        return _conditioned_poisson(rng, n, seconds)
    low, high = mmpp2_rates(arrivals)
    dwell = float(arrivals["mean_dwell_s"])
    per_state = seconds / 2.0
    k = max(1, math.ceil(per_state / dwell))
    # one fixed set of exponential quantiles, scaled to fill half the window
    q = -np.log1p(-(np.arange(k) + 0.5) / k)
    lengths = {s: per_state * rng.permutation(q) / q.sum() for s in ("low", "high")}
    first = "high" if rng.random() < 0.5 else "low"
    order = [first, "low" if first == "high" else "high"]
    segments = []                       # (state, start, length) in window time
    t = 0.0
    for i in range(2 * k):
        state = order[i % 2]
        length = float(lengths[state][i // 2])
        segments.append((state, t, length))
        t += length
    out = []
    for state, rate in (("low", low), ("high", high)):
        segs = [(s0, ln) for st, s0, ln in segments if st == state]
        # arrivals on the state's own clock, then mapped into its segments
        local = _conditioned_poisson(rng, round(rate * per_state), per_state)
        ends = np.cumsum([ln for _, ln in segs])
        idx = np.searchsorted(ends, local, side="right")
        idx = np.minimum(idx, len(segs) - 1)
        starts_local = ends - np.array([ln for _, ln in segs])
        starts_window = np.array([s0 for s0, _ in segs])
        out.append(starts_window[idx] + (local - starts_local[idx]))
    times = np.sort(np.concatenate(out))
    return times[times < seconds]
