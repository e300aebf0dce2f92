"""The paper's benchmark function: AES-128-CTR over a 600-byte input, for
the XLA oracle and the Pallas kernel.  On a TPU the kernel is the compiled
one; on any other device it runs in the Pallas interpreter, a correctness
mode whose times say nothing about the chip.  Every row names the device
its number was taken on."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.ops import aes_ctr

N_BLOCKS = 38   # ceil(600/16)


def _time(fn, *args, iters=50):
    fn(*args)  # warmup/compile
    # simlint: allow[wall-clock] microbenchmark times the real JAX kernel
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    # simlint: allow[wall-clock] microbenchmark times the real JAX kernel
    return (time.perf_counter() - t0) / iters * 1e6


def run(verbose=True):
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    kernel = "pallas" if on_tpu else "pallas_interpret"
    key_bytes = jnp.arange(16, dtype=jnp.int32)
    pt = jax.random.randint(jax.random.PRNGKey(0), (N_BLOCKS, 16), 0, 256)

    us_xla = _time(lambda p: aes_ctr(p, key_bytes, backend="xla"), pt)
    us_kernel = _time(lambda p: aes_ctr(p, key_bytes, backend=kernel), pt,
                      iters=50 if on_tpu else 3)
    where = f"{dev.platform}:{dev.device_kind}"
    if verbose:
        print(f"# AES-128-CTR(600B) — the deployed FaaS function body, on {where}")
        print(f"  XLA jit          : {us_xla:9.1f} us/call")
        print(f"  {kernel:16s} : {us_kernel:9.1f} us/call")
    return [(f"aes600b_xla_{dev.platform}", us_xla, f"us/call on {where}"),
            (f"aes600b_{kernel}_{dev.platform}", us_kernel, f"us/call on {where}")], {}


if __name__ == "__main__":
    run()
