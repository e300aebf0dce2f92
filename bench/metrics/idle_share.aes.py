"""Share of the traced window, in %, in which no operation ran on the
device.  At a fixed offered rate it reads the device work per invocation,
not the host's speed."""
from bench import trace as tr


def read(obs):
    trace = getattr(obs, "trace", None)
    return None if trace is None else 100.0 * tr.idle_share(trace)
