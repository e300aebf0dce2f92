"""Jit'd public wrappers for every Pallas kernel.  Every call names its
backend: ``pallas`` (compiled, the chip path), ``pallas_interpret`` (the
Pallas interpreter, CPU tests) or ``xla`` (the pure-jnp oracle).  Nothing
is chosen for the caller, so a run never lands on another path than the
one it asked for.
"""
from __future__ import annotations

import concurrent.futures
import functools
import operator

import jax
import numpy as np

from repro.kernels import ref
from repro.kernels.aes_ctr import aes_ctr as _aes_ctr_pallas
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.mamba_scan import mamba_scan as _mamba_pallas
from repro.kernels.moe_gmm import moe_gmm as _gmm_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv_pallas

_INT32_MIN, _INT32_MAX = -2**31, 2**31 - 1


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


def _aes_ctr(data, key_bytes, nonce, *, backend: str):
    if nonce is None:          # the counter rides in the last row of the buffer
        data, nonce = data[:-1], data[-1, 0]
    if backend == "xla":
        return ref.aes_ctr_ref(data, key_bytes, nonce)
    rk = ref.aes_key_expand(key_bytes)
    return _aes_ctr_pallas(data, rk, nonce=nonce,
                           interpret=(backend == "pallas_interpret"))


# named ``aes_ctr`` so that the device trace shows module ``jit_aes_ctr``
_aes_ctr.__name__ = _aes_ctr.__qualname__ = "aes_ctr"
aes_ctr_program = jax.jit(_aes_ctr, static_argnames=("backend",))


def aes_ctr_args(plaintext, key_bytes, nonce=0):
    """The arguments of ``aes_ctr_program`` for one invocation.

    Bytes on the host go to the device in one buffer, ``(N + 1, 16)`` int32:
    the plaintext's rows, then a row holding the counter in column 0, with
    ``None`` in the counter's place.  Bytes already on the device are passed
    as they are, and the counter beside them.
    """
    if not isinstance(plaintext, np.ndarray):
        return plaintext, key_bytes, nonce
    nonce = operator.index(nonce)
    if not _INT32_MIN <= nonce <= _INT32_MAX:
        raise OverflowError(f"AES counter {nonce} does not fit int32")
    n = plaintext.shape[0]
    packed = np.zeros((n + 1, 16), np.int32)
    packed[:n] = plaintext
    packed[n, 0] = nonce
    return packed, key_bytes, None


@functools.cache
def _compiled_on_fresh_stack(shape, dtype, packed: bool, backend: str) -> None:
    """Trace, lower and compile ``aes_ctr_program`` for one signature in a
    thread of its own, so that the work runs at the same stack depth
    whoever calls.  Lowering the kernel makes some 10^5 short Python calls,
    and CPython 3.12 keeps frames in 16 KB chunks, freeing a chunk as soon
    as the frame at its base returns: where such calls straddle a chunk
    boundary, each maps and unmaps one (a call then costs 80-90x as much in
    a microbenchmark).  On a TPU v5e host, with one Python frame added in
    front of the jitted program, the benchmark's stack put the lowering
    there: 11.3-12.1 s, against 2.6-2.8 s for the program called directly
    and 0.55 s from a fresh thread.
    """
    args = (np.zeros(shape, dtype), np.zeros(16, np.int32), None if packed else 0)
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        pool.submit(aes_ctr_program, *args, backend=backend).result()


def aes_ctr(plaintext, key_bytes, *, nonce: int = 0, backend: str):
    """AES-128-CTR of ``plaintext`` ((N, 16) byte blocks) under ``key_bytes``,
    counter blocks from ``nonce`` up: one program, the key schedule compiled
    in with the body; ciphertext (N, 16) int32 on the device.

    An invocation from host bytes costs host work, not device work.  In a
    recorded TPU v5e trace of 45 invocations of 600 B
    (``tests/bench/data/aes-window.xplane.pb``; medians) the body ran in
    3 us, and each argument taken from the host cost a ``DevicePut`` of
    166 us: an ``AllocateRawBuffer`` of 84 us and a linearisation of 62 us.
    The program's ``Execute`` took 174 us, of which the output's
    ``AllocateRawBuffer`` 94 us and the launch 60 us.  A 4-byte counter as
    an argument of its own cost as much as the payload, so host bytes
    (an ``np.ndarray``) go in one buffer with their counter: one host
    buffer in, one program, one buffer out.  Bytes already on the device
    keep the counter as an argument: packing them would cost a device
    concatenation and save no transfer.
    """
    b = _resolve(backend)
    args = aes_ctr_args(plaintext, key_bytes, nonce)
    _compiled_on_fresh_stack(args[0].shape, args[0].dtype, args[2] is None, b)
    return aes_ctr_program(*args, backend=b)
