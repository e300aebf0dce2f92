"""Time to first token of the serving engine: from the start of a call of
``ServingEngine.generate`` (span ``repro.serve.generate``) to the end of
its first read-back (span ``repro.serve.readback``: the prefill's tokens
are on the host), median over the traced calls, in ms."""
import statistics

from bench import spans as sp


def read(obs):
    trace = getattr(obs, "trace", None)
    if trace is None:
        return None
    readbacks = sp.by_thread(trace, "repro.serve.readback")
    times = []
    for thread, calls in sp.by_thread(trace, "repro.serve.generate").items():
        for call in calls:
            first = sp.inside(call, readbacks.get(thread, []))
            if first:
                times.append(first[0][1] - call[0])
    return statistics.median(times) / 1e6 if times else None
