"""Summary statistics over every request of a window."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], p: float) -> float:
    """The p-th percentile of all values (linear interpolation), as
    ``repro.core.workload.percentile`` takes it."""
    if len(values) == 0:
        raise ValueError("no values: a window that completed nothing has no percentile")
    return float(np.percentile(np.asarray(values, dtype=np.float64), p))


def rate(count: int, seconds: float) -> float:
    """Work completed over the whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds
