"""One module per kind of system a configuration deploys; each has
``run(cell, *, seed, seconds, trace_seconds, t_start, peaks) -> Outcome``."""
