"""Plain float32 forward of a Qwen3 dense decoder, in ``jax.numpy`` only.

It follows the published modelling of Qwen3 (hf ``Qwen/Qwen3-1.7B``):
pre-norm blocks with RMSNorm, grouped-query attention with RMSNorm on each
query and key head before rotary embedding (rotate-half form, base
``rope_theta``), a causal softmax over all earlier positions, a SwiGLU MLP,
a final RMSNorm and the unembedding, tied to the token embedding where the
configuration says so.  Every matrix product runs at ``Precision.HIGHEST``,
so the TPU computes it in float32 and not in one bfloat16 pass.

Weights are a dict in this module's own layout (:data:`LAYER_KEYS`, each
stacked over layers, ``x @ W`` orientation), made by the benchmark from
the seed.  ``int8`` and ``fp8`` are controls: the same forward with every
weight product in int8 or float8 e4m3 (per-channel weights, per-token
activations), the steps below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj", "q_norm",
              "k_norm", "post_attention_layernorm", "gate_proj", "up_proj", "down_proj")


def _dot_f32(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


def _quantize(a, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.round(a / scale).astype(jnp.int8), scale


def _dot_int8(x, w):
    """x (..., k) @ w (k, n): int8 operands, int32 accumulation."""
    w = w.astype(jnp.float32)
    xq, xs = _quantize(x, -1)
    wq, ws = _quantize(w, 0)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def _dot_fp8(x, w):
    """x (..., k) @ w (k, n): float8 e4m3 operands (per-token and per-channel
    scales), exact products, float32 accumulation."""
    def q(a, axis):
        scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / 448.0
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale
    xq, xs = q(x, -1)
    wq, ws = q(w.astype(jnp.float32), 0)
    return jnp.matmul(xq, wq, precision=HIGHEST) * xs * ws


DOTS = {"float32": _dot_f32, "int8": _dot_int8, "fp8": _dot_fp8}


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def rope(x, positions, theta):
    """x (B, S, H, hd); rotate-half rotary embedding at ``positions`` (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(c, dot, x, w):
    B, S, _ = x.shape
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    pos = jnp.arange(S)
    h = rms_norm(x, w["input_layernorm"], eps)
    q = dot(h, w["q_proj"]).reshape(B, S, hq, hd)
    k = dot(h, w["k_proj"]).reshape(B, S, hkv, hd)
    v = dot(h, w["v_proj"]).reshape(B, S, hkv, hd)
    q = rope(rms_norm(q, w["q_norm"], eps), pos, c["rope_theta"])
    k = rope(rms_norm(k, w["k_norm"], eps), pos, c["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HIGHEST)
    x = x + dot(o.reshape(B, S, hq * hd), w["o_proj"])
    h = rms_norm(x, w["post_attention_layernorm"], eps)
    return x + dot(jax.nn.silu(dot(h, w["gate_proj"])) * dot(h, w["up_proj"]), w["down_proj"])


def _final_hidden(c, dot, weights, tokens):
    x = weights["embed_tokens"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda x, w: (_layer(c, dot, x, w), None), x, weights["layers"])
    return rms_norm(x, weights["norm"], c["rms_norm_eps"])


def _unembed_matrix(weights, c):
    return weights["embed_tokens"].T if c["tie_word_embeddings"] else weights["lm_head"]


@functools.partial(jax.jit, static_argnames=("config", "precision"))
def logits_at(weights, tokens, positions, *, config, precision="float32"):
    """Logits (B, n, V) at ``positions`` (n,) of ``tokens`` (B, S);
    ``config`` is the configuration as a sorted tuple of items."""
    c = dict(config)
    dot = DOTS[precision]
    h = _final_hidden(c, dot, weights, tokens)[:, positions]
    return dot(h, _unembed_matrix(weights, c))
