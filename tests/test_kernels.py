"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle,
across shapes and dtypes, as the deliverable requires."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.aes_ctr import _sub_byte, _xtime, aes_ctr
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rwkv6_scan import rwkv6_scan

KEY = jax.random.PRNGKey(42)


def tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,S,T,d,causal,win", [
    (2, 4, 2, 128, 128, 64, True, None),
    (1, 8, 2, 96, 96, 64, True, 32),       # SWA + padding
    (2, 2, 2, 64, 192, 32, True, None),    # prefix-cache offset
    (1, 4, 4, 128, 128, 128, False, None), # bidirectional MHA
    (1, 2, 1, 257, 257, 64, True, None),   # odd lengths
])
def test_flash_attention(B, Hq, Hkv, S, T, d, causal, win, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, d), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, T, d), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, T, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=win,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,T,d", [
    (2, 8, 2, 300, 64), (1, 4, 4, 512, 128), (3, 16, 8, 257, 64),
])
def test_decode_attention(B, Hq, Hkv, T, d, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, d), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, d), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, d), dtype)
    valid = jax.random.bernoulli(ks[3], 0.8, (B, T)).at[:, 0].set(True)
    out = decode_attention(q, k, v, valid, block_k=128, interpret=True)
    expect = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,S,di,ds,bd,bs", [
    (2, 128, 64, 16, 32, 64), (1, 256, 128, 8, 128, 128), (2, 64, 32, 4, 32, 32),
])
def test_mamba_scan(B, S, di, ds, bd, bs):
    ks = jax.random.split(KEY, 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, di))) * 0.1
    dtx = jax.random.normal(ks[1], (B, S, di)) * 0.1
    Bm = jax.random.normal(ks[2], (B, S, ds))
    Cm = jax.random.normal(ks[3], (B, S, ds))
    A = -jnp.exp(jax.random.normal(ks[4], (di, ds)))
    y, h = mamba_scan(dt, dtx, Bm, Cm, A, block_d=bd, block_s=bs, interpret=True)
    yr, hr = ref.mamba_scan_ref(dt, dtx, Bm, Cm, A)
    np.testing.assert_allclose(y, yr, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h, hr, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("B,T,H,hd,bt", [
    (2, 128, 2, 64, 32), (1, 64, 4, 32, 64), (2, 96, 1, 16, 48),
])
def test_rwkv6_scan(B, T, H, hd, bt):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, hd)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd)) * 0.5 + 2)
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    o, S = rwkv6_scan(r, k, v, w, u, block_t=bt, interpret=True)
    orf, Sr = ref.rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(o, orf, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(S, Sr, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [(4, 100, 96, 130), (2, 64, 256, 64), (8, 33, 48, 72)])
def test_moe_gmm(E, C, D, F, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (E, C, D), dtype)
    w = jax.random.normal(ks[1], (E, D, F), dtype)
    y = moe_gmm(x, w, block_c=64, block_f=64, block_d=64, interpret=True)
    expect = ref.moe_gmm_ref(x, w)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(expect, np.float32),
                               atol=(5e-1 if dtype == jnp.bfloat16 else 2e-3),
                               rtol=(5e-2 if dtype == jnp.bfloat16 else 2e-4))


# ---------------------------------------------------------------------------
def test_aes_fips197_vector():
    """FIPS-197 appendix C.1 known-answer test."""
    key = jnp.arange(16, dtype=jnp.int32)
    pt = jnp.asarray([0x00, 0x11, 0x22, 0x33, 0x44, 0x55, 0x66, 0x77,
                      0x88, 0x99, 0xaa, 0xbb, 0xcc, 0xdd, 0xee, 0xff], jnp.int32)
    ct = ref.aes_encrypt_block_ref(pt, ref.aes_key_expand(key))
    expect = [0x69, 0xc4, 0xe0, 0xd8, 0x6a, 0x7b, 0x04, 0x30,
              0xd8, 0xcd, 0xb7, 0x80, 0x70, 0xb4, 0xc5, 0x5a]
    assert list(map(int, ct)) == expect


def test_aes_table_free_sbox_and_xtime():
    """The kernel's select-tree S-box and arithmetic xtime equal the
    256-entry tables on every byte."""
    x = jnp.arange(256, dtype=jnp.int32)
    np.testing.assert_array_equal(_sub_byte(x), ref.SBOX)
    np.testing.assert_array_equal(_xtime(x), ref.XTIME)


# 600B = 38 blocks; 2100 blocks take three grid steps of 1024 and a
# counter that crosses a byte boundary above the nonce
@pytest.mark.parametrize("n_blocks,nonce", [(1, 0), (38, 0), (40, 7), (2100, 65000)])
def test_aes_ctr_kernel(n_blocks, nonce):
    key_bytes = jnp.arange(16, dtype=jnp.int32)
    pt = jax.random.randint(KEY, (n_blocks, 16), 0, 256)
    rk = ref.aes_key_expand(key_bytes)
    ct = aes_ctr(pt, rk, nonce=nonce, interpret=True)
    np.testing.assert_array_equal(ct, ref.aes_ctr_ref(pt, key_bytes, nonce))


def test_aes_ctr_roundtrip():
    """CTR decryption == encryption (xor keystream twice)."""
    key_bytes = jnp.flip(jnp.arange(16, dtype=jnp.int32))
    pt = jax.random.randint(KEY, (38, 16), 0, 256)
    ct = ref.aes_ctr_ref(pt, key_bytes)
    back = ref.aes_ctr_ref(ct, key_bytes)
    np.testing.assert_array_equal(back, pt)
    assert not np.array_equal(np.asarray(ct), np.asarray(pt))
