"""A model endpoint deployed as a function: ``repro.serving.ServingEngine``
serving a dense decoder at its published widths, under a closed loop.

The benchmark makes the weights from the seed, on the device, in one
jitted call and in the dtype they are served in, in its own layout; the
engine is handed the same arrays re-keyed into the program's parameter
tree.  Each client sends its next request when the last is answered; the
engine takes a batch of equal-length prompts, so the clients' requests
go in together and come back together.  The window runs whole batches:
the last starts before ``seconds`` is up and the window closes when it
completes.  Once it has closed, a sample of the finished requests drawn
from the seed is replayed through the plain float32 reference.  At each
served token, the gap by which its logit lies below the reference's best
is taken; the widest gap and the mean gap are compared with the
configuration's limits.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import List
from unittest import mock

import numpy as np

from bench import device, stats
from bench.reference import qwen3 as ref
from bench.result import Compared, Outcome

MODEL_KEYS = ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "num_hidden_layers", "vocab_size", "rms_norm_eps",
              "rope_theta", "tie_word_embeddings")
CHECK_TOKENS = 512          # served tokens the correctness sample holds at least
REFERENCE_BLOCK_TOKENS = 4096   # positions per block of the reference's forward
EMBED_STD = 0.02
NORM_JITTER = 0.1

# the program's parameter tree (repro.models.transformer, one scanned
# group of attention blocks) in terms of the reference's layout
PROGRAM_LEAVES = {
    "embed": "embed_tokens", "final_norm": "norm",
    "blocks/0/ln1": "input_layernorm", "blocks/0/ln2": "post_attention_layernorm",
    "blocks/0/attn/wq": "q_proj", "blocks/0/attn/wk": "k_proj",
    "blocks/0/attn/wv": "v_proj", "blocks/0/attn/wo": "o_proj",
    "blocks/0/attn/q_norm": "q_norm", "blocks/0/attn/k_norm": "k_norm",
    "blocks/0/mlp/w_gate": "gate_proj", "blocks/0/mlp/w_up": "up_proj",
    "blocks/0/mlp/w_down": "down_proj",
}


@dataclasses.dataclass
class Batch:
    prompt_len: int
    clients: int
    decode_steps: int
    start: float
    end: float
    traced: bool


@dataclasses.dataclass
class ServeObservation:
    config: dict
    peaks: dict
    prefill_s: List[float]
    decode_s: List[float]
    batches: List[Batch]
    trace: object = None


def model_key(c: dict) -> tuple:
    return tuple((k, c[k]) for k in MODEL_KEYS)


def weight_shapes(c: dict) -> dict:
    """Shapes of the reference layout; every layer's arrays are stacked."""
    d, hd, ff, L = c["hidden_size"], c["head_dim"], c["intermediate_size"], c["num_hidden_layers"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    layer = {"input_layernorm": (d,), "post_attention_layernorm": (d,),
             "q_proj": (d, hq * hd), "k_proj": (d, hkv * hd), "v_proj": (d, hkv * hd),
             "o_proj": (hq * hd, d), "q_norm": (hd,), "k_norm": (hd,),
             "gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d)}
    out = {"embed_tokens": (c["vocab_size"], d), "norm": (d,),
           "layers": {k: (L,) + s for k, s in layer.items()}}
    if not c["tie_word_embeddings"]:
        out["lm_head"] = (d, c["vocab_size"])
    return out


def make_weights(c: dict, seed: int):
    """Random weights from the seed, on the device, in one jitted call:
    matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales
    1 + N(0, 0.1^2)."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(c["torch_dtype"])
    shapes = weight_shapes(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, int) for i in x))
    names = [jax.tree_util.keystr(p) for p, _ in flat]

    def make(key):
        out = []
        for i, ((_, shape), name) in enumerate(zip(flat, names)):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if "norm" in name:
                v = 1.0 + NORM_JITTER * z
            elif "embed" in name:
                v = EMBED_STD * z
            else:
                v = z * shape[-2] ** -0.5
            out.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(_key32(seed)))


def _key32(seed: int) -> int:
    return int(np.random.SeedSequence([seed, 0xDEC]).generate_state(1)[0] >> 1)


def program_config(c: dict):
    """The program's ArchConfig for this configuration: the registered
    architecture with every published width and constant set from ``c``."""
    from repro.config import get_arch
    base = get_arch(c["program_arch"])
    arch = dataclasses.replace(
        base, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"], qk_norm=True, dtype=c["torch_dtype"])
    arch.validate()
    return arch


def program_params(arch, weights):
    """The benchmark's arrays, re-keyed into the program's parameter tree;
    every leaf has to be there with the program's shape and dtype."""
    import jax
    from repro.models import transformer as T
    shapes = jax.eval_shape(lambda k: T.init_params(arch, k), jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for path, s in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if name not in PROGRAM_LEAVES:
            raise KeyError(f"the program's parameter {name!r} has no counterpart")
        src = PROGRAM_LEAVES[name]
        arr = weights[src] if src in weights else weights["layers"][src]
        if arr.shape != s.shape or arr.dtype != s.dtype:
            raise ValueError(f"{name}: program wants {s.shape} {s.dtype}, "
                             f"the benchmark made {arr.shape} {arr.dtype}")
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def build_engine(c: dict, weights):
    """``ServingEngine`` at the deployment's slots and cache length, serving
    the benchmark's weights: the engine makes its own in its constructor, so
    that call is handed these instead."""
    from repro.models import transformer as T
    from repro.serving import ServingEngine
    arch = program_config(c)
    params = program_params(arch, weights)
    dep = c["deployment"]
    with mock.patch.object(T, "init_params", lambda cfg, key: params):
        eng = ServingEngine(arch, batch_slots=dep["slots"], max_seq_len=dep["max_seq_len"])
    if eng.params is not params:
        raise RuntimeError("the engine did not take the benchmark's weights")
    return eng


def run(cell, *, seed: int, seconds: float, trace_seconds: float, t_start: float,
        peaks: dict) -> Outcome:
    import jax
    c, mix = cell.config, cell.traffic
    clients, plen, gen = mix["clients"], mix["prompt_tokens"], mix["new_tokens"]
    dep = c["deployment"]
    if clients > dep["slots"] or plen + gen > dep["max_seq_len"] - 1:
        raise ValueError(f"{cell.name}: {clients} clients of {plen}+{gen} tokens do not fit "
                         f"{dep['slots']} slots of {dep['max_seq_len']} positions")
    rng = np.random.default_rng([seed, 0x5E7])
    vocab = c["vocab_size"]
    weights = make_weights(c, seed)
    jax.block_until_ready(weights)
    t_weights = time.perf_counter() - t_start
    eng = build_engine(c, weights)
    eng.generate(rng.integers(0, vocab, (clients, plen)).tolist(), 2)   # compiles both steps
    eng.reset_timers()
    print(f"bench: weights made {t_weights:.2f} s after start, engine built and warmed up "
          f"{time.perf_counter() - t_start:.2f} s", file=sys.stderr)

    tracing = trace_seconds > 0
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if tracing else None
    if tracing:
        jax.profiler.start_trace(str(trace_dir), profiler_options=device.profile_options())
    window = jax.profiler.TraceAnnotation("bench.window") if tracing else None
    requests, latency_ms, batches = [], [], []
    with device.CompileCounter() as compiles:
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        if window is not None:
            window.__enter__()
        while not batches or time.perf_counter() - t0 < seconds:
            prompts = rng.integers(0, vocab, (clients, plen)).tolist()
            steps0 = len(eng.decode_s)
            tb = time.perf_counter()
            if window is not None:
                with jax.profiler.TraceAnnotation("bench.serve.generate"):
                    out = eng.generate(prompts, gen)
            else:
                out = eng.generate(prompts, gen)
            te = time.perf_counter()
            batches.append(Batch(plen, clients, len(eng.decode_s) - steps0, tb, te,
                                 window is not None))
            requests += list(zip(prompts, out))
            latency_ms += [1e3 * (te - tb)] * clients
            if window is not None and te - t0 >= trace_seconds:
                window.__exit__(None, None, None)
                window = None
                jax.profiler.stop_trace()
    window_s = batches[-1].end - t0
    memory_peak = device.memory_peak_bytes()
    served = sum(len(g) for _, g in requests)
    short = sum(1 for _, g in requests if len(g) < gen)
    print(f"bench: {len(batches)} batches, {len(requests)} requests, {served} tokens "
          f"in {window_s:.3f} s; {compiles.count} compilations in the window", file=sys.stderr)
    e2e = {"setup_s": setup_s,
           "p50_ms": stats.percentile(latency_ms, 50),
           "p95_ms": stats.percentile(latency_ms, 95),
           "tokens_per_s": stats.rate(served, window_s)}
    obs = ServeObservation(c, peaks, list(eng.prefill_s), list(eng.decode_s), batches)
    del eng, out
    gc.collect()
    sample = sample_requests(requests, np.random.default_rng([seed, 0xC4E]))
    t_ref = time.perf_counter()
    gaps = np.concatenate(served_gaps(c, weights, sample))
    print(f"bench: reference over {len(sample)} requests, "
          f"{sum(len(g) for _, g in sample)} served tokens, "
          f"{time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    limits = c["limits"]
    compared = [Compared("short_requests", float(short), 0.0),
                Compared("max_logit_gap", float(gaps.max()), float(limits["max_logit_gap"])),
                Compared("mean_logit_gap", float(gaps.mean()), float(limits["mean_logit_gap"]))]
    if tracing:
        from bench import trace as tr
        obs.trace = tr.load(tr.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return Outcome(attempted=len(requests), failed=short, end_to_end=e2e, compared=compared,
                   memory_peak_bytes=memory_peak, observation=obs)


def sample_requests(requests, rng, tokens: int = CHECK_TOKENS):
    """Requests drawn from the seed until they hold ``tokens`` served tokens,
    a longest one first."""
    lengths = np.array([len(g) for _, g in requests])
    longest = np.flatnonzero(lengths == lengths.max())
    first = int(rng.choice(longest))
    order = [first] + [int(i) for i in rng.permutation(len(requests)) if i != first]
    out, n = [], 0
    for i in order:
        if n >= tokens:
            break
        out.append(requests[i])
        n += len(requests[i][1])
    return out


def _blocks(sample):
    """Requests grouped by length into blocks of equal size, the last
    padded with copies of its first request (whose rows are dropped)."""
    by_len = {}
    for p, g in sample:
        by_len.setdefault((len(p), len(g)), []).append((p, g))
    for (plen, glen), reqs in sorted(by_len.items()):
        seq = plen + glen - 1
        rows = max(1, min(len(reqs), REFERENCE_BLOCK_TOKENS // seq))
        for i in range(0, len(reqs), rows):
            part = reqs[i:i + rows]
            pad = part + [part[0]] * (rows - len(part))
            toks = np.array([p + g[:-1] for p, g in pad], np.int32)
            yield plen, glen, toks, np.array([g for _, g in part], np.int32)


def served_gaps(c: dict, weights, sample, precision: str = "float32"):
    """Per request, at each served token: the reference's best logit less
    the reference's logit of the served token (0 where they agree)."""
    import jax.numpy as jnp
    out = []
    for plen, glen, toks, served in _blocks(sample):
        pos = jnp.arange(plen - 1, plen + glen - 1)
        lg = np.asarray(ref.logits_at(weights, jnp.asarray(toks), pos,
                                      config=model_key(c), precision=precision))
        lg = lg[:len(served)]
        got = np.take_along_axis(lg, served[..., None], axis=-1)[..., 0]
        out += list(lg.max(axis=-1) - got)
    return out


def control_gaps(c: dict, weights, sample, precision: str = "int8"):
    """The control: at the same positions, the gap of the token that the
    lower-precision forward puts first, in the float32 reference's logits."""
    import jax.numpy as jnp
    out = []
    for plen, glen, toks, served in _blocks(sample):
        pos = jnp.arange(plen - 1, plen + glen - 1)
        key = model_key(c)
        lg = np.asarray(ref.logits_at(weights, jnp.asarray(toks), pos, config=key))
        lq = np.asarray(ref.logits_at(weights, jnp.asarray(toks), pos, config=key,
                                      precision=precision))
        lg, lq = lg[:len(served)], lq[:len(served)]
        pick = lq.argmax(axis=-1)
        got = np.take_along_axis(lg, pick[..., None], axis=-1)[..., 0]
        out += list(lg.max(axis=-1) - got)
    return out
