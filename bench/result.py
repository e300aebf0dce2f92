"""What one run hands back, and the result line it prints last."""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Any, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class Compared:
    """One number of the correctness check beside its limit; it passes at or under it."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    compared: List[Compared]
    memory_peak_bytes: int
    observation: Any = None      # what per-layer readers read; its .trace in a --trace 1 run

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(c.ok for c in self.compared)


def result_line(outcome: Outcome, metrics: Dict[str, tuple], device: dict,
                breakdown: Optional[dict] = None) -> str:
    """The JSON object printed as the last line of standard output.
    ``metrics`` maps a name to (value, unit); the compared numbers come last."""
    doc: Dict[str, Any] = {
        "correct": outcome.correct,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": {k: {"value": v, "unit": u} for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        doc["breakdown"] = breakdown
    doc["compared"] = {c.name: {"value": c.value, "limit": c.limit} for c in outcome.compared}
    return json.dumps(doc)


def print_compared(compared: List[Compared], stream=None) -> None:
    stream = stream or sys.stderr
    for c in compared:
        print(f"compared {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=stream)
