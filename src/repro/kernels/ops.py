"""Jit'd public wrappers for every Pallas kernel.  Every call names its
backend: ``pallas`` (compiled, the chip path), ``pallas_interpret`` (the
Pallas interpreter, CPU tests) or ``xla`` (the pure-jnp oracle).  Nothing
is chosen for the caller, so a run never lands on another path than the
one it asked for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.aes_ctr import aes_ctr as _aes_ctr_pallas
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.mamba_scan import mamba_scan as _mamba_pallas
from repro.kernels.moe_gmm import moe_gmm as _gmm_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv_pallas


def _resolve(backend: str) -> str:
    if backend not in ("pallas", "pallas_interpret", "xla"):
        raise ValueError(f"unknown kernel backend {backend!r}")
    return backend


def flash_attention(q, k, v, *, causal=True, window=None, backend: str):
    b = _resolve(backend)
    if b == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         interpret=(b == "pallas_interpret"))


def decode_attention(q, k, v, valid, *, backend: str):
    b = _resolve(backend)
    if b == "xla":
        return ref.decode_attention_ref(q, k, v, valid)
    return _decode_pallas(q, k, v, valid, interpret=(b == "pallas_interpret"))


def mamba_scan(dt, dtx, Bm, Cm, A, *, backend: str):
    b = _resolve(backend)
    if b == "xla":
        return ref.mamba_scan_ref(dt, dtx, Bm, Cm, A)
    return _mamba_pallas(dt, dtx, Bm, Cm, A,
                         interpret=(b == "pallas_interpret"))


def rwkv6_scan(r, k, v, w, u, *, backend: str):
    b = _resolve(backend)
    if b == "xla":
        return ref.rwkv6_scan_ref(r, k, v, w, u)
    return _rwkv_pallas(r, k, v, w, u, interpret=(b == "pallas_interpret"))


def moe_gmm(x, w, *, backend: str):
    b = _resolve(backend)
    if b == "xla":
        return ref.moe_gmm_ref(x, w)
    return _gmm_pallas(x, w, interpret=(b == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("backend",))
def aes_ctr(plaintext: jnp.ndarray, key_bytes: jnp.ndarray, *, nonce: int = 0,
            backend: str):
    """One dispatch per call: the key schedule is compiled in with the body."""
    b = _resolve(backend)
    if b == "xla":
        return ref.aes_ctr_ref(plaintext, key_bytes, nonce)
    rk = ref.aes_key_expand(key_bytes)
    return _aes_ctr_pallas(plaintext, rk, nonce=nonce,
                           interpret=(b == "pallas_interpret"))
