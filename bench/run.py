"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (loading, warm-up of the cell's own shapes, compilation) counts as
``setup_s``; then the cell is measured for ``--seconds``; then what the
window produced is compared with the plain reference.  ``--trace 0``
reports the end-to-end metrics, ``--trace 1`` the per-layer ones, read
from a profiler trace of the first part of the window.  The last line of
standard output is one JSON object; the numbers compared for ``correct``
are the last lines of standard error.  Any platform but a TPU is refused
with a non-zero exit and no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import spec  # noqa: E402
from bench.device import NoAccelerator, enable_compile_cache, require_tpu  # noqa: E402
from bench.peaks import UnknownDevice, peaks_for  # noqa: E402
from bench.result import print_compared, result_line  # noqa: E402

# seconds of the window that a --trace 1 run records: a trace of the whole
# window would hold millions of device events
TRACE_SECONDS = 2.0


def per_layer_metrics(cell: spec.Cell, observation) -> dict:
    out = {}
    for m in cell.per_layer:
        value = spec.load_reader(cell, m.name)(observation)
        if value is not None:
            out[m.name] = (value, m.unit)
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, device: dict,
             peaks: dict, t_start: float) -> str:
    """Drive the cell and return its result line."""
    system = spec.load_system(cell)
    outcome = system.run(cell, seed=seed, seconds=seconds,
                         trace_seconds=TRACE_SECONDS if trace else 0.0,
                         t_start=t_start, peaks=peaks)
    device = dict(device, memory_peak_bytes=outcome.memory_peak_bytes)
    breakdown = None
    if trace:
        from bench import trace as tr
        trace = outcome.observation.trace
        metrics = per_layer_metrics(cell, outcome.observation)
        device.update(busy_s=tr.busy_s(trace), window_s=trace.window_s)
        breakdown = tr.breakdown(trace)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m.name not in outcome.end_to_end:
                raise KeyError(f"system {cell.config['system']} gives no {m.name!r}")
            metrics[m.name] = (outcome.end_to_end[m.name], m.unit)
    print_compared(outcome.compared)
    return result_line(outcome, metrics, device, breakdown)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    # libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes only
    # inside its checkout and its own HOME, XDG_CACHE_HOME and TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache = enable_compile_cache(ROOT)
    try:
        device = require_tpu(cell.chips)
        peaks = peaks_for(device["kind"])
    except (NoAccelerator, UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"bench: {cell.name} seed={args.seed} seconds={args.seconds} trace={args.trace} "
          f"on {device['platform']}:{device['kind']} x{device['count']} "
          f"(found {time.perf_counter() - T_START:.2f} s after start); compile cache {cache}",
          file=sys.stderr)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), device, peaks, T_START)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
