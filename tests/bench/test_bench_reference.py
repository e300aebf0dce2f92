"""The plain references: AES against the standards' vectors, and the
float32 Qwen3 forward against the program at a tiny float32 size."""
import numpy as np
import pytest
from bench_testkit import TINY_MODEL

from bench.reference import aes


def test_fips197_appendix_c1():
    key = bytes(range(16))
    pt = np.frombuffer(bytes.fromhex("00112233445566778899aabbccddeeff"), np.uint8)[None]
    ct = aes.encrypt_blocks(pt, aes.expand_key(key))
    assert ct.tobytes().hex() == "69c4e0d86a7b0430d8cdb78070b4c55a"


def test_sp800_38a_f51_first_block():
    rk = aes.expand_key(bytes.fromhex("2b7e151628aed2a6abf7158809cf4f3c"))
    ctr = np.frombuffer(bytes.fromhex("f0f1f2f3f4f5f6f7f8f9fafbfcfdfeff"), np.uint8)[None]
    p1 = np.frombuffer(bytes.fromhex("6bc1bee22e409f96e93d7e117393172a"), np.uint8)
    assert (aes.encrypt_blocks(ctr, rk)[0] ^ p1).tobytes().hex() == \
        "874d6191b620e3261bef6864990db6ce"


def test_ctr_partial_block_and_counters():
    key = bytes(range(16, 32))
    rng = np.random.default_rng(0)
    pay = rng.integers(0, 256, (3, 600), dtype=np.uint8)
    out = aes.ctr_encrypt(pay, key, np.array([0, 38, 76]))
    whole = aes.ctr_encrypt(pay.reshape(1, -1)[:, :1800], key, np.array([0]))
    # three invocations at consecutive counter ranges = the first 600 B of each 608 B span
    ks = aes.ctr_encrypt(np.zeros((1, 3 * 608), np.uint8), key, np.array([0]))[0]
    for i in range(3):
        assert np.array_equal(out[i], pay[i] ^ ks[608 * i:608 * i + 600])
    assert whole.shape == (1, 1800)


def test_program_oracle_agrees():
    import jax.numpy as jnp

    from repro.kernels import ref
    key = bytes(range(16))
    pay = np.random.default_rng(1).integers(0, 256, (2, 600), dtype=np.uint8)
    mine = aes.ctr_encrypt(pay, key, np.array([5, 43]))
    for i, nonce in enumerate((5, 43)):
        p = np.zeros(608, np.int32)
        p[:600] = pay[i]
        theirs = ref.aes_ctr_ref(jnp.asarray(p.reshape(38, 16)),
                                 jnp.arange(16, dtype=jnp.int32), nonce=nonce)
        assert np.array_equal(np.asarray(theirs).astype(np.uint8).reshape(-1)[:600], mine[i])


@pytest.fixture(scope="module")
def tiny():
    import json

    from bench_testkit import ROOT
    from bench.systems import serving_engine as se
    c = json.loads((ROOT / "bench/configs/qwen3-1.7b.json").read_text())
    c.update(TINY_MODEL)
    return c, se.make_weights(c, 3)


def test_reference_forward_matches_program_forward(tiny):
    """Independent code, same weights: the plain forward and the program's
    full-sequence forward agree to float32 rounding."""
    import jax
    import jax.numpy as jnp

    from bench.systems import serving_engine as se
    from bench.reference import qwen3
    from repro.models import transformer as T
    c, w = tiny
    arch = se.program_config(c)
    params = se.program_params(arch, w)
    toks = jnp.asarray(np.random.default_rng(2).integers(0, c["vocab_size"], (2, 12)), jnp.int32)
    prog = jax.jit(lambda p, t: T.forward(p, arch, {"tokens": t})[0])(params, toks)
    mine = qwen3.logits_at(w, toks, jnp.arange(12), config=se.model_key(c))
    assert float(jnp.max(jnp.abs(prog - mine))) < 1e-4
    assert float(jnp.std(mine)) > 0.05          # logits are not flat


def test_int8_control_departs_from_reference(tiny):
    import jax.numpy as jnp

    from bench.systems import serving_engine as se
    from bench.reference import qwen3
    c, w = tiny
    toks = jnp.asarray(np.random.default_rng(4).integers(0, c["vocab_size"], (2, 12)), jnp.int32)
    f32 = qwen3.logits_at(w, toks, jnp.arange(12), config=se.model_key(c))
    q8 = qwen3.logits_at(w, toks, jnp.arange(12), config=se.model_key(c), precision="int8")
    assert 1e-3 < float(jnp.max(jnp.abs(f32 - q8))) < 1.0
