"""The program's own spans on a trace's host plane, and device time put on
the host's clock.

The program opens spans named ``repro.*`` (``jax.profiler.TraceAnnotation``)
on the thread that runs it; the readers of ``bench/metrics`` that read
them share these helpers.  A trace of a program that opens no such span
gives them nothing to read.

A TPU's operations are placed on the trace's clock only to within a
millisecond or so of the host's spans.  ``device_offset_ns`` finds the
shift that puts them on the host's clock, from the host's launches of one
program and that program's runs on the device.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence

from bench import trace as tr
from bench.trace import Interval

# the runtime's span around the hand-off of one program run to the device,
# on the thread that called the jitted function
LAUNCH = "PJRT_LoadedExecutable_Execute"


def by_thread(trace: tr.Trace, name: str) -> Dict[str, List[Interval]]:
    """The spans ``name`` that started in the window, by host thread, in order."""
    lo, hi = trace.window
    out: Dict[str, List[Interval]] = {}
    for thread, events in trace.host_lines.items():
        spans = [(s, e) for n, s, e in events if n == name and lo <= s < hi]
        if spans:
            out[thread] = spans
    return out


def count(trace: tr.Trace, name: str) -> int:
    """How many spans ``name`` started in the window, on all threads."""
    return sum(len(s) for s in by_thread(trace, name).values())


def inside(outer: Interval, spans: Sequence[Interval]) -> List[Interval]:
    """Those of ``spans`` that started within ``outer``."""
    return [(s, e) for s, e in spans if outer[0] <= s < outer[1]]


def overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two lists of sorted, disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def launches(trace: tr.Trace, program: str) -> List[float]:
    """Start times of the host's launches of ``program``: the ``LAUNCH``
    events inside a call of its jitted function (``jit__decode`` is called
    as ``PjitFunction(_decode)``), on the thread that made the call."""
    call = f"PjitFunction({program.removeprefix('jit_')})"
    out: List[float] = []
    for events in trace.host_lines.values():
        calls = tr.merge([(s, e) for n, s, e in events if n == call], float("-inf"),
                         float("inf"))
        if not calls:
            continue
        ends = [e for _, e in calls]
        for n, s, _ in events:
            if n.startswith(LAUNCH):
                i = bisect.bisect_right(ends, s)
                if i < len(calls) and calls[i][0] <= s:
                    out.append(s)
    return sorted(out)


def device_offset_ns(trace: tr.Trace, program: str) -> Optional[float]:
    """The shift, in ns, that puts the device's events on the host's clock.

    Each run of ``program`` on a device (its ``XLA Modules`` line) is
    paired, in order, with the host's launch of it.  A run cannot start
    before its launch, so the offset is the least shift that puts every run
    at or after its launch; it is late by at most one launch latency.  None
    where the runs of a device and the launches do not pair one for one."""
    host = launches(trace, program)
    if not host:
        return None
    offset = None
    for dl in trace.devices.values():
        runs = sorted(s for n, s, _ in dl.modules if tr.short_module(n) == program)
        if len(runs) != len(host):
            return None
        shift = max(h - r for h, r in zip(host, runs))
        offset = shift if offset is None else max(offset, shift)
    return offset


def idle_in_ns(trace: tr.Trace, spans: Sequence[Interval], offset: float) -> float:
    """Device idle time that falls inside ``spans`` (host intervals), with the
    device's operations shifted by ``offset``, averaged over the devices;
    both are clipped to the window."""
    lo, hi = trace.window
    spans = tr.merge(spans, lo, hi)
    within = sum(e - s for s, e in spans)
    idle = 0.0
    for dl in trace.devices.values():
        busy = tr.merge([(s + offset, e + offset) for _, s, e in dl.ops], lo, hi)
        idle += within - overlap_ns(busy, spans)
    return idle / len(trace.devices)
