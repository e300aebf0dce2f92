"""Serving launcher: deploy a model endpoint behind the junctiond FaaS
runtime and drive requests through the gateway->provider->instance path.

    python -m repro.launch.serve --arch qwen3-1.7b [--backend junctiond]

serves the arch's published config, in its own dtype and with random
weights from a seed, on the first JAX device (a TPU in deployment).  The
measured decode step becomes the function body's service time.
``--reduced`` is the CPU rehearsal of the same command: the smoke-sized
float32 variant of the arch (``JAX_PLATFORMS=cpu``).
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import List

MAX_SEQ_LEN = 1024      # KV-cache positions per slot
PROMPT_LEN = 32
SEED = 0                # weights and prompts


def engine_config(arch: str, *, reduced_size: bool = False):
    from repro.config import get_arch, reduced
    cfg = get_arch(arch)
    if reduced_size:
        cfg = dataclasses.replace(reduced(cfg), dtype="float32")
    return cfg


def device_label() -> str:
    """The device measurements are taken on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return f"{devs[0].platform}:{devs[0].device_kind} x{len(devs)}"


def random_prompts(vocab_size: int, n: int, prompt_len: int, seed: int) -> List[List[int]]:
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab_size, (n, prompt_len)).tolist()


@dataclasses.dataclass
class EndpointTiming:
    prefill_first_call_s: float     # trace + compile + one run
    decode_first_call_s: float
    prefill_ms: float               # steady state, to block_until_ready
    decode_step_us: float           # mean over the timed generation
    generated: List[List[int]]


def measure_endpoint(eng, prompts: List[List[int]], max_new_tokens: int) -> EndpointTiming:
    """Warm both compiled steps up, then time one batched generation."""
    eng.reset_timers()
    eng.generate(prompts, max_new_tokens=2)
    first_prefill, first_decode = eng.prefill_s[0], eng.decode_s[0]
    eng.reset_timers()
    out = eng.generate(prompts, max_new_tokens=max_new_tokens)
    return EndpointTiming(first_prefill, first_decode, 1e3 * eng.prefill_s[0],
                          eng.mean_decode_step_us(), out)


def invoke_through(backend: str, name: str, work_us: float, n_requests: int):
    """Deploy a function of ``work_us`` behind ``backend`` and run
    ``n_requests`` sequential invocations through the simulated platform."""
    from repro.core import FaasdRuntime, FunctionSpec, Simulator, run_sequential
    rt = FaasdRuntime(Simulator(seed=0), backend=backend)
    rt.deploy_blocking(FunctionSpec(name=name, work_us=work_us,
                                    payload_bytes=2048, response_bytes=4096))
    return run_sequential(rt, name, n=n_requests)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--backend", default="junctiond",
                    choices=["junctiond", "containerd"])
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-sized float32 variant (CPU rehearsal)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.serving import ServingEngine

    cfg = engine_config(args.arch, reduced_size=args.reduced)
    dev = device_label()
    print(f"deploying {cfg.name} ({cfg.dtype}, {cfg.n_layers}L d={cfg.d_model}) "
          f"on {dev} behind {args.backend} ...")
    eng = ServingEngine(cfg, batch_slots=args.batch_slots,
                        max_seq_len=MAX_SEQ_LEN, seed=SEED)
    prompts = random_prompts(cfg.vocab_size, args.batch_slots, PROMPT_LEN, SEED)
    t = measure_endpoint(eng, prompts, args.max_new_tokens)
    print(f"[{dev}] first call incl. compile: prefill {t.prefill_first_call_s:.2f} s, "
          f"decode {t.decode_first_call_s:.2f} s")
    print(f"[{dev}] prefill {t.prefill_ms:.3f} ms, decode step "
          f"{t.decode_step_us:.1f} us/batch ({args.batch_slots} slots)")

    summary = invoke_through(args.backend, args.arch, t.decode_step_us, args.requests)
    print(f"{args.requests} invocations through the {args.backend} runtime: "
          f"median={summary.median_ms:.3f} ms  p99={summary.p99_ms:.3f} ms")
    overhead = summary.median_ms - t.decode_step_us * 1e-3
    print(f"FaaS runtime overhead at median: {overhead:.3f} ms "
          f"({100 * overhead / summary.median_ms:.1f}% of e2e)")


if __name__ == "__main__":
    main()
