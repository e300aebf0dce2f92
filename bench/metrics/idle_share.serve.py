"""Share of the traced window, in %, in which no operation ran on the
device, while the endpoint served whole batches."""
from bench import trace as tr


def read(obs):
    trace = getattr(obs, "trace", None)
    return None if trace is None else 100.0 * tr.idle_share(trace)
