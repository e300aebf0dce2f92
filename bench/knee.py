"""Sweep of open-loop Poisson rates for an AES cell, to find its knee: the
highest rate at which the 99th percentile of invocation latency stays
within 10 ms (the paper's Fig. 6 limit) and at least 99% of the offered
invocations complete within the window.  One process, one line per rate.

    python3 bench/knee.py --workload aes-600b-poisson --seed 7 --seconds 10 --rates 500,1000
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import device, spec, stats, traffic  # noqa: E402
from bench.systems import aes_ctr  # noqa: E402

P99_LIMIT_MS = 10.0
COMPLETED_SHARE = 0.99


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated invocations per second")
    args = ap.parse_args()
    cell = spec.load_cell(ROOT, args.workload)
    device.enable_compile_cache(ROOT)
    dev = device.require_tpu(cell.chips)
    print(json.dumps({"device": dev}), flush=True)
    fn = None
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        rng = np.random.default_rng([args.seed, k])
        due = traffic.arrival_times({"process": "poisson", "rate_per_s": rate}, args.seconds, rng)
        data = rng.integers(0, 256, (len(due), int(cell.config["payload_bytes"])), dtype=np.uint8)
        if fn is None:
            fn, *_ = aes_ctr.setup(cell, args.seed, 0.1)
        counters = np.arange(len(due), dtype=np.int64) * fn.blocks
        w = aes_ctr.open_loop(fn, due, [r.tobytes() for r in data], counters, args.seconds)
        lat = aes_ctr.latencies_ms(w, due)
        share = float((w.done <= w.t0 + args.seconds).sum()) / max(1, len(due))
        p99 = stats.percentile(lat, 99)
        print(json.dumps({"rate": rate, "offered": len(due), "completed_share": share,
                          "p50_ms": stats.percentile(lat, 50), "p95_ms": stats.percentile(lat, 95),
                          "p99_ms": p99, "dispatch_us": 1e6 * float(np.median(w.dispatch_s)),
                          "late_p99_ms": 1e3 * float(np.percentile(w.issued - (w.t0 + due), 99)),
                          "sustained": p99 <= P99_LIMIT_MS and share >= COMPLETED_SHARE}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
