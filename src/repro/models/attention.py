"""GQA attention with RoPE, optional qk-norm and sliding-window, plus a
single-token decode path against a (ring-buffered) KV cache.

Pure jnp, lowered by XLA on every device, the TPU included: no Pallas
kernel of :mod:`repro.kernels` is on this path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ArchConfig
from repro.models.layers import apply_rope, causal_mask, dense_init, rms_norm

NEG_INF = -1e30


def attn_init(key, cfg: ArchConfig, dtype) -> dict:
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(k4, cfg.n_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(params: dict, x: jnp.ndarray, cfg: ArchConfig,
                 positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ params["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ params["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               mask: Optional[jnp.ndarray],
               mixed_precision: bool = False) -> jnp.ndarray:
    """q: (B,S,Hq,hd); k,v: (B,T,Hkv,hd); mask: (S,T) or (B,S,T) bool.

    ``mixed_precision``: feed bf16 operands straight into the dot with an
    fp32 accumulator (``preferred_element_type``) instead of materialising
    fp32 COPIES of K/V — this is exactly what the TPU MXU does natively,
    and removes the dominant ``convert`` HBM traffic the dry-run profile
    shows on the decode path (§Perf iteration 'mixed_prec').
    """
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, hd)
    if mixed_precision:
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                            preferred_element_type=jnp.float32) / (hd ** 0.5)
    else:
        scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) / (hd ** 0.5)
    if mask is not None:
        m = mask if mask.ndim == 3 else mask[None]
        scores = jnp.where(m[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    if mixed_precision:
        out = jnp.einsum("bkgst,btkd->bskgd", w.astype(q.dtype), v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(B, S, Hq, hd).astype(q.dtype)


def chunked_gqa_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                       causal: bool = True, window: Optional[int] = None,
                       q_chunk: int = 512) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure XLA: scan over query
    chunks so the (S, T) score matrix is never materialised — the HBM
    traffic drops from O(S*T*H) to O(S*H*d + chunk*T*H).  This is the
    XLA twin of the Pallas flash kernel (used where pallas can't lower),
    and the §Perf "memory-term" optimization for prefill/train.

    q: (B,S,Hq,hd); k/v: (B,T,Hkv,hd) -> (B,S,Hq,hd).
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    cq = min(q_chunk, S)
    pad = (-S) % cq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = (S + pad) // cq
    qs = q.reshape(B, n, cq, Hkv, G, hd)
    scale = hd ** -0.5
    k_pos = jnp.arange(T)

    def one_chunk(_, qi_i):
        qi, i = qi_i                                   # (B,cq,Hkv,G,hd), idx
        # bf16 dots with fp32 accumulation (MXU-native) — no fp32 K/V copies
        s = jnp.einsum("bskgd,btkd->bkgst", qi, k,
                       preferred_element_type=jnp.float32) * scale
        q_pos = i * cq + jnp.arange(cq) + (T - S)
        m = jnp.ones((cq, T), bool)
        if causal:
            m &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            m &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", p.astype(q.dtype), v,
                       preferred_element_type=jnp.float32)
        return None, o

    _, outs = jax.lax.scan(one_chunk, None,
                           (jnp.moveaxis(qs, 1, 0), jnp.arange(n)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S + pad, Hq, hd)
    return out[:, :S].astype(q.dtype)


def attention_forward(params: dict, x: jnp.ndarray, cfg: ArchConfig,
                      positions: jnp.ndarray,
                      kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                      kv_mask: Optional[jnp.ndarray] = None,
                      impl: str = "dense") -> jnp.ndarray:
    """Full-sequence self-attention (training / prefill).

    ``kv`` overrides the self-derived k/v (cross-attention for enc-dec);
    ``kv_mask``: (B, T) validity of the cross keys.
    ``impl``: "dense" (oracle; materialises scores) or "chunked"
    (flash-style, memory-optimal in XLA).
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions)
    if kv is not None:
        k, v = kv
        mask = None if kv_mask is None else jnp.broadcast_to(kv_mask[:, None, :], (B, S, k.shape[1]))
        out = gqa_attend(q, k, v, mask)
    elif impl == "chunked":
        out = chunked_gqa_attend(q, k, v, causal=True,
                                 window=cfg.sliding_window)
    else:
        mask = causal_mask(S, S, window=cfg.sliding_window)
        out = gqa_attend(q, k, v, mask)
    return out.reshape(B, S, -1) @ params["wo"]


def project_kv_for_cross(params: dict, enc: jnp.ndarray, cfg: ArchConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Project encoder output once into cross-attention K/V (no RoPE)."""
    B, T, _ = enc.shape
    hd = cfg.resolved_head_dim
    k = (enc @ params["wk"]).reshape(B, T, cfg.n_kv_heads, hd)
    v = (enc @ params["wv"]).reshape(B, T, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    return k, v


# ---------------------------------------------------------------------------
# KV cache (per layer)


def kv_cache_capacity(cfg: ArchConfig, seq_len: int) -> int:
    """SWA architectures use a ring buffer of window size."""
    if cfg.sliding_window is not None:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def init_kv_cache(cfg: ArchConfig, batch: int, seq_len: int, dtype) -> dict:
    cap = kv_cache_capacity(cfg, seq_len)
    hd = cfg.resolved_head_dim
    shape = (batch, cap, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill_into_cache(params: dict, x: jnp.ndarray, cfg: ArchConfig,
                       positions: jnp.ndarray, cache: dict,
                       impl: str = "dense") -> Tuple[jnp.ndarray, dict]:
    """Self-attention over the prompt AND write the (ring) cache."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions)
    if impl == "chunked":
        out = chunked_gqa_attend(q, k, v, causal=True, window=cfg.sliding_window)
    else:
        mask = causal_mask(S, S, window=cfg.sliding_window)
        out = gqa_attend(q, k, v, mask)
    cap = cache["k"].shape[1]
    if cap >= S:
        cache = {"k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
                 "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))}
    else:
        # ring: keep the last `cap` tokens, rolled so slot j holds pos p≡j (mod cap)
        k_tail, v_tail = k[:, S - cap:], v[:, S - cap:]
        shift = (S - cap) % cap
        cache = {"k": jnp.roll(k_tail, shift, axis=1), "v": jnp.roll(v_tail, shift, axis=1)}
    y = out.reshape(B, S, -1) @ params["wo"]
    return y, cache


def decode_step_attention(params: dict, x: jnp.ndarray, cfg: ArchConfig,
                          pos: jnp.ndarray, cache: dict,
                          cache_update: str = "dus",
                          mixed_precision: bool = False) -> Tuple[jnp.ndarray, dict]:
    """One-token decode: x (B, 1, d); pos scalar int32 (absolute position of
    the new token).  Writes k/v into the cache (ring slot for SWA) and
    attends over all valid cache entries.

    ``cache_update``: "dus" (dynamic_update_slice — natural, but SPMD must
    involuntarily REPLICATE a cache whose sequence dim is sharded, because
    the slot index is dynamic) or "select" (iota==slot masked select —
    elementwise, so the sharded layout is preserved; the §Perf fix).
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, positions)
    cap = cache["k"].shape[1]
    slot = pos % cap
    if cache_update == "select":
        sel = (jnp.arange(cap) == slot)[None, :, None, None]
        ck = jnp.where(sel, k.astype(cache["k"].dtype), cache["k"])
        cv = jnp.where(sel, v.astype(cache["v"].dtype), cache["v"])
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    # Absolute position held by slot j after the write.
    j = jnp.arange(cap)
    abs_pos = pos - ((pos - j) % cap)
    valid = abs_pos >= 0
    if cfg.sliding_window is not None:
        valid &= abs_pos > pos - cfg.sliding_window
    out = gqa_attend(q, ck, cv, jnp.broadcast_to(valid[None, None, :], (B, 1, cap)),
                     mixed_precision=mixed_precision)
    y = out.reshape(B, 1, -1) @ params["wo"]
    return y, {"k": ck, "v": cv}
