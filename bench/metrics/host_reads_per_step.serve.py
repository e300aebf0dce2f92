"""Device-to-host reads per step of the serving engine: the spans
``repro.serve.host_read`` over the spans ``repro.serve.readback`` in the
traced window (``ServingEngine.generate`` reads back once after prefill
and once after every decode step, one read per running slot)."""
from bench import spans as sp


def read(obs):
    trace = getattr(obs, "trace", None)
    if trace is None:
        return None
    passes = sp.count(trace, "repro.serve.readback")
    return sp.count(trace, "repro.serve.host_read") / passes if passes else None
