"""Bring-up check: drive the system's main path once on one TPU chip.

    python chip_smoke.py

All phases run in this one process, which starts no other: a chip belongs
to one process at a time.

- device:   require a TPU; print its kind, the device count and the JAX,
            jaxlib and libtpu versions.  Any other platform is refused.
- endpoint: serve qwen3-1.7b at its published widths in bf16, random
            weights from a seed, through ServingEngine as
            ``python -m repro.launch.serve`` does.  The decode-path logits
            are checked against one full ``transformer.forward`` over the
            generated tokens; then the measured decode step is deployed as
            the function body behind junctiond and containerd.
- aes:      the compiled Pallas AES-128-CTR kernel on 600 B (38 blocks)
            and on 4096 blocks, byte for byte against ``ref.aes_ctr_ref``.

Every measured line names the device it was taken on.  Any failure raises
and exits non-zero; only when every phase passed is the last line the JSON
object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "qwen3-1.7b"
SLOTS, PROMPT_LEN, NEW_TOKENS = 4, 32, 16
N_INVOCATIONS = 40
SEED = 0
AES_BLOCKS = (38, 4096)         # 600 B, the paper's function input; and 64 KiB
AES_CALLS = 200
# Largest |decode-path logit - forward logit| allowed, in float32.  Both
# paths run the same weights in the config's dtype but attend and round in
# a different order (one cached token at a time against the whole
# sequence at once).  Random-weight logits here have a spread of about 1
# (tied embeddings of std 0.02 over d_model 2048) and reach about 5, where
# one bf16 step is 1/32.  At full width in bf16 on a host CPU the largest
# difference was 0.031, 0.041 and 0.057 at 2, 4 and 12 layers, growing
# about as the square root of depth; the bound allows 8 bf16 steps.  A
# wrong position, mask or cache slot moves logits by their whole spread.
# float32, the reduced CPU rehearsal, differs by about 1e-6.
LOGIT_TOL = {"bfloat16": 0.25, "float32": 1e-3}


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_phase() -> dict:
    import importlib.metadata

    import jax
    import jaxlib
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SmokeFailure(f"chip_smoke needs a TPU; JAX found platform "
                           f"{d.platform!r} ({d.device_kind})")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    print(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def compare_logits(dec, ref) -> dict:
    """Decode-path logits against the forward's, both (B, n, V).  Argmax
    agreement follows from the bound wherever the forward's top two logits
    are more than twice the largest difference apart, so where the argmax
    differs, report how close the forward's top two were."""
    import jax
    import jax.numpy as jnp
    dec = dec.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    top2 = jax.lax.top_k(ref, 2)[0]
    agree = jnp.argmax(dec, axis=-1) == jnp.argmax(ref, axis=-1)
    return {"max_abs": float(jnp.max(jnp.abs(dec - ref))),
            "positions": int(agree.size), "argmax_agree": int(agree.sum()),
            "widest_swapped_gap": float(jnp.max(jnp.where(
                agree, 0.0, top2[..., 0] - top2[..., 1])))}


def endpoint_phase(cfg, dev: str) -> float:
    """Serve, check against the forward pass, deploy; returns the step µs."""
    import jax
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.models import transformer as T
    from repro.serving import ServingEngine

    print(f"endpoint: {cfg.name} {cfg.dtype} {cfg.n_layers}L d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size}; {SLOTS} slots, {PROMPT_LEN}-token prompts, "
          f"{NEW_TOKENS} new tokens, cache {serve.MAX_SEQ_LEN}")
    eng = ServingEngine(cfg, batch_slots=SLOTS, max_seq_len=serve.MAX_SEQ_LEN, seed=SEED)
    prompts = serve.random_prompts(cfg.vocab_size, SLOTS, PROMPT_LEN, SEED)
    t = serve.measure_endpoint(eng, prompts, NEW_TOKENS)
    n_tok = sum(len(g) for g in t.generated)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    print(f"[{dev}] compile (first call): prefill {t.prefill_first_call_s:.3f} s, "
          f"decode {t.decode_first_call_s:.3f} s")
    print(f"[{dev}] prefill {t.prefill_ms:.3f} ms; mean decode step "
          f"{t.decode_step_us:.1f} us ({len(eng.decode_s)} steps, batch {SLOTS})")
    print(f"[{dev}] peak_bytes_in_use {peak}; tokens generated {n_tok}")
    check(n_tok == SLOTS * NEW_TOKENS, f"generated {n_tok} tokens, "
          f"expected {SLOTS * NEW_TOKENS}")

    tokens = [p + g for p, g in zip(prompts, t.generated)]
    dec = eng.replay_logits(tokens, PROMPT_LEN)
    replayed = jnp.argmax(dec.astype(jnp.float32), axis=-1)
    check(replayed.tolist() == t.generated,
          "the replayed decode path does not reproduce the served tokens")
    fwd = jax.jit(lambda p, x: T.forward(p, cfg, {"tokens": x})[0])(
        eng.params, jnp.asarray(tokens, jnp.int32)[:, :-1])
    tol = LOGIT_TOL[cfg.dtype]
    c = compare_logits(dec, fwd[:, PROMPT_LEN - 1:])
    print(f"check decode vs forward: max|dlogit|={c['max_abs']:.4g} (bound {tol}), "
          f"argmax agrees at {c['argmax_agree']}/{c['positions']} positions; where it "
          f"differs the forward's top two were <= {c['widest_swapped_gap']:.4g} apart")
    check(c["max_abs"] <= tol, "decode-path logits do not agree with the forward pass")

    medians = {}
    for backend in ("junctiond", "containerd"):
        s = serve.invoke_through(backend, ARCH, t.decode_step_us, N_INVOCATIONS)
        medians[backend] = s.median_ms
        print(f"[{dev}] {backend}: {N_INVOCATIONS} invocations of the measured "
              f"{t.decode_step_us:.1f} us step, median {s.median_ms:.3f} ms, "
              f"p99 {s.p99_ms:.3f} ms")
    check(medians["junctiond"] <= medians["containerd"],
          "junctiond median above containerd's")
    return t.decode_step_us


def median_call_us(fn) -> float:
    """Median wall time of ``AES_CALLS`` calls, each to block_until_ready."""
    times = []
    for _ in range(AES_CALLS):
        t0 = time.perf_counter()
        fn().block_until_ready()
        times.append(time.perf_counter() - t0)
    return 1e6 * statistics.median(times)


def aes_phase(dev: str) -> None:
    import jax
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.aes_ctr import aes_ctr as aes_body
    key = jax.numpy.arange(16, dtype=jax.numpy.int32)
    rk = ref.aes_key_expand(key)
    for n in AES_BLOCKS:
        pt = jax.random.randint(jax.random.PRNGKey(SEED + n), (n, 16), 0, 256)
        t0 = time.perf_counter()
        ct = ops.aes_ctr(pt, key, backend="pallas").block_until_ready()
        first_s = time.perf_counter() - t0
        want = ref.aes_ctr_ref(pt, key)
        check(np.array_equal(np.asarray(ct), np.asarray(want)),
              f"AES-CTR over {n} blocks differs from the reference")
        check(np.array_equal(np.asarray(aes_body(pt, rk)), np.asarray(want)),
              f"the AES kernel alone over {n} blocks differs from the reference")
        call_us = median_call_us(lambda: ops.aes_ctr(pt, key, backend="pallas"))
        body_us = median_call_us(lambda: aes_body(pt, rk))
        print(f"[{dev}] aes-128-ctr {n} blocks ({16 * n} B): byte-exact; "
              f"compile (first call) {first_s:.3f} s; median over {AES_CALLS} calls: "
              f"{call_us:.1f} us/call with the key schedule, "
              f"{body_us:.1f} us/call with round keys expanded once")


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    try:
        device = device_phase()
        print(f"compile cache: {cache}")
        from repro.launch import serve
        dev = serve.device_label()
        endpoint_phase(serve.engine_config(ARCH), dev)
        aes_phase(dev)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
