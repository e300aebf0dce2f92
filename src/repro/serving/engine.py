"""Serving engine: compiled prefill/decode steps + generation loop.

This is the "model endpoint" a junctiond function deploys.  It measures
its own per-step wall time, each step timed to ``block_until_ready``, so
the FaaS layer can use the measured decode step as the function body's
service time.  The first call of each step compiles it: callers that
want steady-state times warm up first and ``reset_timers()``.

``generate`` also opens ``repro.serve.*`` spans (``TraceAnnotation``) in
its host code, which a profiler session puts on its trace's clock:

- ``repro.serve.generate``: one call (one batch);
- ``repro.serve.prefill`` and ``repro.serve.decode``: the intervals that
  ``prefill_s`` and ``decode_s`` time, dispatch to ``block_until_ready``;
- ``repro.serve.sample``: the key split and ``sample`` after each step;
- ``repro.serve.readback``: one pass that reads the running slots' tokens
  to the host and records them, after each step;
- ``repro.serve.host_read``: one device-to-host read in that pass.

No span is opened inside a jitted function.
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.config import ArchConfig
from repro.models import transformer as T
from repro.serving.batcher import ContinuousBatcher
from repro.serving.kvcache import PagedKVManager
from repro.serving.sampling import sample


class ServingEngine:
    def __init__(self, cfg: ArchConfig, *, batch_slots: int = 4,
                 max_seq_len: int = 256, seed: int = 0):
        self.cfg = cfg
        self.max_seq_len = max_seq_len
        key = jax.random.PRNGKey(seed)
        self.params = T.init_params(cfg, key)
        self.kv = PagedKVManager(cfg, batch_slots, max_seq_len)
        self.batcher = ContinuousBatcher(self.kv, batch_slots)
        self.caches = None
        self._rng = jax.random.PRNGKey(seed + 1)
        self.prefill_s: List[float] = []
        self.decode_s: List[float] = []

        @jax.jit
        def _prefill(params, tokens):
            logits, caches = T.prefill(params, cfg, {"tokens": tokens},
                                       seq_len=max_seq_len)
            return logits, caches

        @jax.jit
        def _decode(params, tokens, pos, caches):
            return T.decode_step(params, cfg, tokens, pos, caches)

        self._prefill = _prefill
        self._decode = _decode

    # ------------------------------------------------------------------
    def generate(self, prompts: List[List[int]], max_new_tokens: int = 8,
                 temperature: float = 0.0) -> List[List[int]]:
        """Batched greedy/temperature generation (all prompts same length
        for the compiled shape; the batcher handles slot lifecycle)."""
        with TraceAnnotation("repro.serve.generate"):
            reqs = [self.batcher.submit(p, max_new_tokens) for p in prompts]
            self.batcher.admit_ready()
            plen = len(prompts[0])
            assert all(len(p) == plen for p in prompts), "batch requires equal prompt lengths"
            tokens = jnp.asarray(prompts, jnp.int32)
            with TraceAnnotation("repro.serve.prefill"):
                t0 = time.perf_counter()
                logits, caches = self._prefill(self.params, tokens)
                logits.block_until_ready()
                self.prefill_s.append(time.perf_counter() - t0)
            pos = plen
            next_tok = self._sample(logits, temperature)
            self._read_back(next_tok)
            while any(not r.done for r in reqs) and pos < self.max_seq_len - 1:
                with TraceAnnotation("repro.serve.decode"):
                    t0 = time.perf_counter()
                    logits, caches = self._decode(self.params, next_tok[:, None],
                                                  jnp.int32(pos), caches)
                    logits.block_until_ready()
                    self.decode_s.append(time.perf_counter() - t0)
                next_tok = self._sample(logits, temperature)
                pos += 1
                self._read_back(next_tok)
                if not self.batcher.running:
                    break
            return [r.generated for r in reqs]

    def _sample(self, logits, temperature: float) -> jnp.ndarray:
        with TraceAnnotation("repro.serve.sample"):
            self._rng, k = jax.random.split(self._rng)
            return sample(logits, k, temperature)

    def _read_back(self, next_tok) -> None:
        """Record each running slot's token, read to the host one slot at a time."""
        with TraceAnnotation("repro.serve.readback"):
            for slot in list(self.batcher.running):
                with TraceAnnotation("repro.serve.host_read"):
                    token = int(next_tok[slot])
                self.batcher.record_token(slot, token)

    # ------------------------------------------------------------------
    def replay_logits(self, tokens: List[List[int]], prompt_len: int) -> jnp.ndarray:
        """Teacher-forced run of the same compiled prefill and decode steps
        over ``tokens``: prefill the first ``prompt_len``, then decode the
        rest one at a time.  Returns (B, n - prompt_len, V) logits; row j
        predicts token ``prompt_len + j``, as ``generate`` saw them."""
        toks = jnp.asarray(tokens, jnp.int32)
        logits, caches = self._prefill(self.params, toks[:, :prompt_len])
        out = [logits[:, -1]]
        for pos in range(prompt_len, toks.shape[1] - 1):
            logits, caches = self._decode(self.params, toks[:, pos:pos + 1],
                                          jnp.int32(pos), caches)
            out.append(logits[:, -1])
        return jnp.stack(out, axis=1)

    def reset_timers(self) -> None:
        self.prefill_s.clear()
        self.decode_s.clear()

    def mean_decode_step_us(self) -> float:
        if not self.decode_s:
            return float("nan")
        return 1e6 * sum(self.decode_s) / len(self.decode_s)
