"""``correct`` must come out false when the timed path is broken, and the
controls must fail the comparison.  These drive the rest of a run past the
chip check at tiny sizes; the benchmark's own runs never run them."""
import numpy as np
import pytest
from bench_testkit import TINY_LIMIT, interpret_aes, make_root, run_line

from bench.reference import aes as aes_ref
from bench.systems import aes_ctr
from bench.systems import serving_engine as se


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_aes_control_reused_keystream_fails(seed):
    """The control answers with the keystream of counter 0 for every
    invocation: the guarantee that no counter block repeats is broken."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (50, 600), dtype=np.uint8)
    key = rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
    counters = np.arange(50) * 38
    right = aes_ref.ctr_encrypt(data, key, counters)
    reused = aes_ref.ctr_encrypt(data, key, np.zeros(50, np.int64))
    sound = {c.name: c.value for c in aes_ctr.check([r.tobytes() for r in right], data, key,
                                                    counters)}
    control = {c.name: c.value for c in aes_ctr.check([r.tobytes() for r in reused], data, key,
                                                      counters)}
    assert sound == {"unanswered": 0.0, "wrong_bytes": 0.0}
    assert control["wrong_bytes"] > 49 * 600 * 0.9


def test_aes_answer_altered_where_produced(tmp_path, monkeypatch):
    interpret_aes(monkeypatch)
    produce = aes_ctr.AesFunction.result
    calls = []

    def altered(self, out):
        r = produce(self, out)
        calls.append(1)
        if len(calls) == WARM + 3:                 # the third answer of the window
            r = bytes([r[0] ^ 1]) + r[1:]
        return r

    WARM = aes_ctr.WARMUP_CALLS
    monkeypatch.setattr(aes_ctr.AesFunction, "result", altered)
    doc = run_line(make_root(tmp_path), "tiny-aes", seconds=0.5)
    assert doc["correct"] is False
    assert doc["compared"]["wrong_bytes"]["value"] == 1.0


def test_aes_answer_that_never_comes(tmp_path, monkeypatch):
    interpret_aes(monkeypatch)
    monkeypatch.setattr(aes_ctr, "GRACE_S", 0.5)
    produce = aes_ctr.AesFunction.result
    calls = []

    def stuck(self, out):
        calls.append(1)
        if len(calls) > aes_ctr.WARMUP_CALLS + 5:
            import time
            time.sleep(3)
        return produce(self, out)

    monkeypatch.setattr(aes_ctr.AesFunction, "result", stuck)
    doc = run_line(make_root(tmp_path), "tiny-aes", seconds=0.5)
    assert doc["correct"] is False
    assert doc["failed"] == doc["compared"]["unanswered"]["value"] == 3


def _counter_never_advances(sound, pt, key, nonce, backend):
    """The kernel's counter state left as it was: every call starts at 0."""
    return sound(pt, key, nonce=0, backend=backend)


def _half_the_blocks_left_out(sound, pt, key, nonce, backend):
    """Only the first half of each payload's blocks go through the kernel."""
    import jax.numpy as jnp
    half = pt.shape[0] // 2
    return jnp.concatenate([sound(pt[:half], key, nonce=nonce, backend=backend), pt[half:]])


@pytest.mark.parametrize("fault", [_counter_never_advances, _half_the_blocks_left_out])
def test_aes_broken_kernel_call(tmp_path, monkeypatch, fault):
    interpret_aes(monkeypatch)
    from repro.kernels import ops
    sound = ops.aes_ctr
    monkeypatch.setattr(ops, "aes_ctr", lambda pt, key, *, nonce=0, backend: fault(
        sound, pt, key, nonce, backend))
    doc = run_line(make_root(tmp_path), "tiny-aes", seconds=0.5)
    assert doc["correct"] is False
    assert doc["compared"]["wrong_bytes"]["value"] > 100


@pytest.fixture
def root(tmp_path):
    return make_root(tmp_path)


def test_serve_token_altered_where_produced(root, monkeypatch):
    from repro.serving import engine
    sample = engine.sample
    calls = []

    def altered(logits, key, temperature=0.0, top_k=0):
        tok = sample(logits, key, temperature, top_k)
        calls.append(1)
        return tok.at[1].add(1) if len(calls) == 6 else tok    # within the window

    monkeypatch.setattr(engine, "sample", altered)
    doc = run_line(root, "tiny-serve", seconds=0.3)
    assert doc["correct"] is False
    assert doc["compared"]["max_logit_gap"]["value"] > 10 * TINY_LIMIT


def test_serve_decode_step_returns_its_cache_unchanged(root, monkeypatch):
    from repro.models import transformer as T
    step = T.decode_step

    def stale(params, cfg, tokens, pos, caches, **kw):
        return step(params, cfg, tokens, pos, caches, **kw)[0], caches

    monkeypatch.setattr(T, "decode_step", stale)
    doc = run_line(root, "tiny-serve", seconds=0.3)
    assert doc["correct"] is False
    assert doc["compared"]["max_logit_gap"]["value"] > 10 * TINY_LIMIT


def test_serve_half_of_the_batch_left_out(root, monkeypatch):
    """The engine serves the first half of a batch and hands the other half
    the same answers."""
    from repro.serving import ServingEngine
    generate = ServingEngine.generate

    def half(self, prompts, max_new_tokens=8, temperature=0.0):
        out = generate(self, prompts[:len(prompts) // 2], max_new_tokens, temperature)
        return out + out[:len(prompts) - len(out)]

    monkeypatch.setattr(ServingEngine, "generate", half)
    doc = run_line(root, "tiny-serve", seconds=0.3)
    assert doc["correct"] is False
    assert doc["compared"]["max_logit_gap"]["value"] > 10 * TINY_LIMIT


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_int8_control_fails_where_the_program_passes(seed):
    """At a tiny float32 size the served tokens lie within rounding of the
    reference's best; the int8 forward's first choices do not."""
    import json

    from bench_testkit import ROOT, TINY_MODEL
    c = json.loads((ROOT / "bench/configs/qwen3-1.7b.json").read_text())
    c.update(TINY_MODEL, deployment={"slots": 4, "max_seq_len": 64})
    w = se.make_weights(c, seed)
    eng = se.build_engine(c, w)
    prompts = np.random.default_rng(seed).integers(0, c["vocab_size"], (4, 8)).tolist()
    sample = list(zip(prompts, eng.generate(prompts, 24)))
    program = max(float(g.max()) for g in se.served_gaps(c, w, sample))
    control = max(float(g.max()) for g in se.control_gaps(c, w, sample))
    assert program <= TINY_LIMIT < control
