"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``
and ``systems/<system>.py`` (named by the configuration).  The yardstick
(traffic generation, trace reduction, peaks, operation and byte counts,
the plain references) lives here and imports nothing of the program.
"""
