"""AES-128-CTR Pallas kernel — the paper's benchmark function (vSwarm AES
over a 600-byte input) as a TPU micro-function.

TPU adaptation: the x86 version uses AES-NI; TPUs have no AES ISA and
Mosaic lowers no 1-D gather, so the kernel is table-free and lane-dense:

- the state is held byte-major, sixteen ``(rows, 128)`` int32 planes per
  grid step, one counter block per lane, so ShiftRows is a renaming of
  planes and MixColumns is elementwise across planes;
- SubBytes is a select tree: the 256-entry S-box is packed four bytes to
  an int32 word, bits 2..7 of the input pick one of the 64 words with 63
  selects, and bits 0..1 shift the byte out of it;
- the counter blocks are generated in the kernel from the grid position;
  round keys and the nonce are scalars in SMEM.

plaintext: (N, 16) int32 bytes; round_keys: (11, 16); -> ciphertext (N, 16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import SBOX

_LANES = 128
# rows per grid step: one int32 vreg, 8 sublanes x 128 lanes = 1024 counter
# blocks, so the sixteen planes of state stay in registers
_ROWS = 8

_SBOX_WORDS = tuple(
    int(w) for w in np.asarray(SBOX, np.uint32).reshape(64, 4)
    .dot(np.array([1, 1 << 8, 1 << 16, 1 << 24], np.uint32)).astype(np.int32))
# ShiftRows on column-major state bytes (byte 4*col + row): new[i] = old[_SHIFT[i]]
_SHIFT = tuple(4 * ((i // 4 + i % 4) % 4) + i % 4 for i in range(16))


def _sub_byte(x: jnp.ndarray) -> jnp.ndarray:
    bits = [(x & (1 << (k + 2))) != 0 for k in range(6)]

    def pick(lo: int, n: int):
        if n == 1:
            return _SBOX_WORDS[lo]
        half = n // 2
        return jnp.where(bits[half.bit_length() - 1],
                         pick(lo + half, half), pick(lo, half))

    return (pick(0, 64) >> ((x & 3) << 3)) & 0xFF


def _xtime(a: jnp.ndarray) -> jnp.ndarray:
    return (a << 1) ^ ((a >> 7) * 0x11B)


def _sub_shift(s):
    return [_sub_byte(s[_SHIFT[i]]) for i in range(16)]


def _mix(s):
    out = []
    for c in range(4):
        a0, a1, a2, a3 = s[4 * c:4 * c + 4]
        x0, x1, x2, x3 = _xtime(a0), _xtime(a1), _xtime(a2), _xtime(a3)
        out += [x0 ^ (a1 ^ x1) ^ a2 ^ a3,
                a0 ^ x1 ^ (a2 ^ x2) ^ a3,
                a0 ^ a1 ^ x2 ^ (a3 ^ x3),
                (a0 ^ x0) ^ a1 ^ a2 ^ x3]
    return out


def _aes_kernel(nonce_ref, rk_ref, pt_ref, ct_ref):
    rows = pt_ref.shape[1]
    shape = (rows, _LANES)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ctr = nonce_ref[0] + (pl.program_id(0) * rows + row) * _LANES + lane
    # counter block: 12 zero bytes then the big-endian 32-bit counter
    zero = jnp.zeros(shape, jnp.int32)
    s = [zero] * 12 + [(ctr >> sh) & 0xFF for sh in (24, 16, 8, 0)]

    def add_key(s, rnd):
        return [s[i] ^ rk_ref[rnd, i] for i in range(16)]

    s = add_key(s, 0)
    s = jax.lax.fori_loop(1, 10, lambda rnd, s: add_key(_mix(_sub_shift(s)), rnd), s)
    s = add_key(_sub_shift(s), 10)
    for i in range(16):
        ct_ref[i] = pt_ref[i] ^ s[i]


@functools.partial(jax.jit, static_argnames=("interpret",))
def aes_ctr(plaintext: jnp.ndarray, round_keys: jnp.ndarray, *,
            nonce: int = 0, interpret: bool = False) -> jnp.ndarray:
    n = plaintext.shape[0]
    rows = -(-n // (_ROWS * _LANES)) * _ROWS
    planes = jnp.pad(plaintext.astype(jnp.int32), ((0, rows * _LANES - n), (0, 0)))
    planes = planes.T.reshape(16, rows, _LANES)
    block = pl.BlockSpec((16, _ROWS, _LANES), lambda i: (0, i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    ct = pl.pallas_call(
        _aes_kernel,
        grid=(rows // _ROWS,),
        in_specs=[smem, smem, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(planes.shape, jnp.int32),
        interpret=interpret,
    )(jnp.asarray(nonce, jnp.int32).reshape(1), round_keys.astype(jnp.int32), planes)
    return ct.reshape(16, rows * _LANES).T[:n]
