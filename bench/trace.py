"""Reduction of a profiler trace (``.xplane.pb``) to device busy and idle
time, per-program device time and the breakdown of a result line.

Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
event per HLO operation, nested where an operation (a ``while``) holds
others; their ``XLA Modules`` line holds one event per program run.  The
host plane ``/host:CPU`` holds the benchmark's own spans, named
``bench.*`` (``jax.profiler.TraceAnnotation``), on the thread that opened
them.  The traced window is the ``bench.window`` span.  All times are
nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class DeviceLine:
    ops: List[Tuple[str, float, float]]        # (hlo text, start, end), by start
    modules: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, DeviceLine]
    host_lines: Dict[str, List[Tuple[str, float, float]]]   # thread -> events
    window: Interval

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def short_op(text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def short_module(text: str) -> str:
    """``jit__decode(7880959121110125288)`` -> ``jit__decode``."""
    return text.split("(", 1)[0]


def load(path: Path) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    devices: Dict[str, DeviceLine] = {}
    host: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dl = DeviceLine([], [])
            for line in plane.lines:
                target = {"XLA Ops": dl.ops, "XLA Modules": dl.modules}.get(line.name)
                if target is not None:
                    target.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                  for e in line.events)
            dl.ops.sort(key=lambda e: (e[1], -e[2]))
            dl.modules.sort(key=lambda e: e[1])
            devices[plane.name] = dl
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                evs.sort(key=lambda e: (e[1], -e[2]))
                host[f"{line.name}#{i}"] = evs
    windows = [(s, e) for evs in host.values() for n, s, e in evs if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {WINDOW_SPAN} span, found {len(windows)}")
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    return Trace(devices, host, windows[0])


def find(directory: Path) -> Path:
    found = sorted(Path(directory).glob("**/*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"{directory}: expected one .xplane.pb, found {len(found)}")
    return found[0]


def merge(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The union of intervals, clipped to [lo, hi], as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, device: str) -> float:
    lo, hi = trace.window
    return sum(e - s for s, e in merge([(s, e) for _, s, e in trace.devices[device].ops], lo, hi))


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    return sum(busy_ns(trace, d) for d in trace.devices) / len(trace.devices) / 1e9


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def idle_gaps(trace: Trace, device: str) -> List[Interval]:
    lo, hi = trace.window
    busy = merge([(s, e) for _, s, e in trace.devices[device].ops], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def module_runs(trace: Trace, name: str) -> List[Interval]:
    """Runs of the program ``name`` (its short module name) that started in the window."""
    lo, hi = trace.window
    return [(s, e) for d in trace.devices.values() for n, s, e in d.modules
            if short_module(n) == name and lo <= s < hi]


def op_self_times(trace: Trace, device: str) -> Dict[str, float]:
    """Self time of each operation in the window, by ``<module>:<op>``: an
    operation's duration less that of the operations nested in it."""
    lo, hi = trace.window
    dl = trace.devices[device]
    mod_starts = [s for _, s, _ in dl.modules]
    totals: Dict[str, float] = collections.defaultdict(float)
    stack: List[list] = []                   # [key, end, self]

    def close(entry):
        totals[entry[0]] += max(0.0, entry[2])

    for name, s, e in dl.ops:
        if not (lo <= s < hi):
            continue
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= e - s
        i = bisect.bisect_right(mod_starts, s) - 1
        mod = short_module(dl.modules[i][0]) if i >= 0 and dl.modules[i][2] >= e else "?"
        stack.append([f"{mod}:{short_op(name)}", e, e - s])
    while stack:
        close(stack.pop())
    return dict(totals)


def _innermost(events: Sequence[Tuple[str, float, float]], points: Sequence[float],
               keep) -> List[Optional[str]]:
    """For each sorted point, the innermost event (of those ``keep`` accepts)
    that contains it, on one thread whose events nest."""
    out: List[Optional[str]] = []
    stack: List[Tuple[str, float, float]] = []
    i = 0
    for p in points:
        while i < len(events) and events[i][1] <= p:
            ev = events[i]
            i += 1
            if not keep(ev[0]):
                continue
            while stack and stack[-1][2] <= ev[1]:
                stack.pop()
            stack.append(ev)
        while stack and stack[-1][2] <= p:
            stack.pop()
        out.append(stack[-1][0] if stack else None)
    return out


def gap_labels(trace: Trace, gaps: Sequence[Interval]) -> List[str]:
    """What the host thread that holds the window span was doing in each gap:
    its innermost ``bench.*`` span and its innermost event of any kind."""
    thread = next(t for t, evs in trace.host_lines.items()
                  if any(n == WINDOW_SPAN for n, _, _ in evs))
    evs = trace.host_lines[thread]
    mids = [(s + e) / 2 for s, e in gaps]
    order = sorted(range(len(mids)), key=mids.__getitem__)
    pts = [mids[i] for i in order]
    span = _innermost(evs, pts, lambda n: n.startswith("bench."))
    any_ev = _innermost(evs, pts, lambda n: True)
    labels: List[str] = [""] * len(gaps)
    for k, i in enumerate(order):
        a, b = span[k] or "no bench span", any_ev[k]
        labels[i] = a if b is None or b == a else f"{a} > {b}"
    return labels


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most (self) time, and idle time by
    what the host was doing, each at most ``top`` entries, in seconds."""
    ops: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for dev in sorted(trace.devices):
        for k, v in op_self_times(trace, dev).items():
            ops[k] += v / 1e9
        gaps = idle_gaps(trace, dev)
        for label, (s, e) in zip(gap_labels(trace, gaps), gaps):
            idle[label] += (e - s) / 1e9
    n = len(trace.devices)

    def top_of(d):
        return [[k, v / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": top_of(ops), "idle_gaps": top_of(idle)}


def op_seconds(trace: Trace, match) -> Tuple[float, int]:
    """Total device seconds and count of the operations in the window whose
    HLO text ``match`` accepts, over all devices."""
    lo, hi = trace.window
    total, count = 0.0, 0
    for d in trace.devices.values():
        for name, s, e in d.ops:
            if lo <= s < hi and match(name):
                total += e - s
                count += 1
    return total / 1e9, count
