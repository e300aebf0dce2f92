"""Inter-token gap of the serving engine: the interval between the ends of
successive read-backs (spans ``repro.serve.readback``, each step's tokens
on the host) within one call of ``ServingEngine.generate`` (span
``repro.serve.generate``), median over the traced window, in ms."""
import statistics

from bench import spans as sp


def read(obs):
    trace = getattr(obs, "trace", None)
    if trace is None:
        return None
    readbacks = sp.by_thread(trace, "repro.serve.readback")
    gaps = []
    for thread, calls in sp.by_thread(trace, "repro.serve.generate").items():
        for call in calls:
            ends = [e for _, e in sp.inside(call, readbacks.get(thread, []))]
            gaps += [b - a for a, b in zip(ends, ends[1:])]
    return statistics.median(gaps) / 1e6 if gaps else None
