"""Readings that set the limits of ``correct``: the program's and the
control's, per seed, one JSON line each.  Not part of a benchmark run.

    python3 bench/calibrate.py --workloads qwen3-1.7b-decode,qwen3-1.7b-prefill --seeds 1,2,3
    python3 bench/calibrate.py --workloads aes-600b-poisson --seeds 1,2,3 --seconds 10

Served model: per seed, the weights are made and the engine built once;
for each cell, whole batches of its traffic run until they hold as many
served tokens as a run compares; the engine is freed; the sample a run
would draw is compared with the float32 reference (the program's reading)
and the int8 and fp8 forwards' first choices at the same positions are
too (the controls').  AES: the cell's window runs and is checked as in a run (the
program's reading); the control answers every invocation with the
keystream of counter 0, a keystream reused under one key.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import device, spec  # noqa: E402
from bench.reference import aes as aes_ref  # noqa: E402
from bench.systems import aes_ctr, serving_engine as se  # noqa: E402

CONTROLS = ("int8", "fp8")


def serve_readings(cells, seed: int) -> None:
    c = cells[0].config
    weights = se.make_weights(c, seed)
    eng = se.build_engine(c, weights)
    rng = np.random.default_rng([seed, 0x5E7])
    served = {}
    for cell in cells:
        mix = cell.traffic
        reqs = []
        while sum(len(g) for _, g in reqs) < se.CHECK_TOKENS:
            prompts = rng.integers(0, c["vocab_size"], (mix["clients"], mix["prompt_tokens"])).tolist()
            reqs += list(zip(prompts, eng.generate(prompts, mix["new_tokens"])))
        served[cell.name] = reqs
    del eng
    gc.collect()
    for cell in cells:
        sample = se.sample_requests(served[cell.name], np.random.default_rng([seed, 0xC4E]))
        t = time.perf_counter()
        program = np.concatenate(se.served_gaps(c, weights, sample))
        t_ref = time.perf_counter() - t
        row = {"workload": cell.name, "seed": seed, "served_tokens": len(program),
               "reference_s": t_ref, "program": _gap_stats(program)}
        for precision in CONTROLS:
            row[precision] = _gap_stats(np.concatenate(
                se.control_gaps(c, weights, sample, precision)))
        print(json.dumps(row), flush=True)


def _gap_stats(gaps: np.ndarray) -> dict:
    return {"max": float(gaps.max()), "mean": float(gaps.mean()),
            "flipped": int((gaps > 0).sum())}


def aes_readings(cell, seed: int, seconds: float) -> None:
    fn, key, due, data, counters = aes_ctr.setup(cell, seed, seconds)
    w = aes_ctr.open_loop(fn, due, [r.tobytes() for r in data], counters, seconds)
    program = {c.name: c.value for c in aes_ctr.check(w.results, data, key, counters)}
    reused = aes_ref.ctr_encrypt(data, key, np.zeros(len(data), np.int64))
    control = {c.name: c.value for c in aes_ctr.check(
        [r.tobytes() for r in reused], data, key, counters)}
    print(json.dumps({"workload": cell.name, "seed": seed, "invocations": len(due),
                      "program": program, "control": control}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cells = [spec.load_cell(ROOT, w) for w in args.workloads.split(",")]
    device.enable_compile_cache(ROOT)
    print(json.dumps({"device": device.require_tpu(max(c.chips for c in cells))}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        if cells[0].config["system"] == "serving_engine":
            serve_readings(cells, seed)
        else:
            for cell in cells:
                aes_readings(cell, seed, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
