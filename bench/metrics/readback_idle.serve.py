"""Share of the traced window, in %, in which the device is idle while the
serving engine reads tokens back: the device's idle time inside the spans
``repro.serve.readback`` (``ServingEngine.generate``), averaged over the
devices, with the device's operations put on the host's clock by the
offset that the launches and runs of the decode step give
(``bench.spans.device_offset_ns``)."""
from bench import spans as sp

SPAN = "repro.serve.readback"
PROGRAM = "jit__decode"


def read(obs):
    trace = getattr(obs, "trace", None)
    if trace is None:
        return None
    readbacks = [s for spans in sp.by_thread(trace, SPAN).values() for s in spans]
    offset = sp.device_offset_ns(trace, PROGRAM)
    if not readbacks or offset is None:
        return None
    lo, hi = trace.window
    return 100.0 * sp.idle_in_ns(trace, readbacks, offset) / (hi - lo)
