"""The serving engine's profiler spans, on the CPU: ``generate`` opens the
``repro.serve.*`` spans on the profiler's host plane, as many as its steps
and reads make and nested as documented, without changing what it returns
or the programs it runs."""
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.config import get_arch, reduced
from repro.serving import ServingEngine

PHASES = ("repro.serve.prefill", "repro.serve.decode", "repro.serve.sample",
          "repro.serve.readback")


def _cfg():
    return dataclasses.replace(reduced(get_arch("qwen3-1.7b")), dtype="float32")


def _engine(slots):
    return ServingEngine(_cfg(), batch_slots=slots, max_seq_len=32, seed=1)


def _prompts(slots):
    return [[1 + i, 2, 3, 4] for i in range(slots)]


def _traced(tmp_path: Path, fn):
    """``fn()`` under the profiler: its result and its ``repro.*`` spans as
    (name, start, end), sorted by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    spans = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for plane in pd.planes if plane.name == "/host:CPU"
                    for line in plane.lines for e in line.events
                    if e.name.startswith("repro.")), key=lambda s: (s[1], -s[2]))
    return out, spans


def _named(spans, name):
    return [(s, e) for n, s, e in spans if n == name]


def _within(inner, outer):
    return all(any(a <= s and e <= b for a, b in outer) for s, e in inner)


@pytest.mark.parametrize("steps,slots", [(3, 2), (1, 3)])
def test_generate_spans_count_and_nest(tmp_path, steps, slots):
    eng = _engine(slots)
    eng.generate(_prompts(slots), max_new_tokens=steps + 1)      # compiles both steps
    eng.reset_timers()
    _, spans = _traced(tmp_path, lambda: eng.generate(_prompts(slots), steps + 1))
    got = {n: _named(spans, n) for n in ("repro.serve.generate", "repro.serve.host_read")
           + PHASES}
    assert len(eng.decode_s) == steps and len(eng.prefill_s) == 1
    assert {n: len(v) for n, v in got.items()} == {
        "repro.serve.generate": 1, "repro.serve.prefill": 1, "repro.serve.decode": steps,
        "repro.serve.sample": steps + 1, "repro.serve.readback": steps + 1,
        "repro.serve.host_read": slots * (steps + 1)}
    assert len(spans) == sum(len(v) for v in got.values())
    generate = got["repro.serve.generate"]
    for name in PHASES:
        assert _within(got[name], generate), name
    assert _within(got["repro.serve.host_read"], got["repro.serve.readback"])
    for s, e in got["repro.serve.readback"]:
        assert sum(1 for hs, _ in got["repro.serve.host_read"] if s <= hs < e) == slots
    # the timers' readings lie inside their spans
    for name, timer in (("repro.serve.prefill", eng.prefill_s),
                        ("repro.serve.decode", eng.decode_s)):
        for (s, e), t in zip(got[name], timer):
            assert (e - s) / 1e9 >= t
    # the phases follow one another: prefill, then sample and read-back
    # after it and after every decode step
    order = [n for n, _, _ in spans if n in PHASES]
    assert order == ["repro.serve.prefill"] + (
        ["repro.serve.sample", "repro.serve.readback", "repro.serve.decode"] * steps
        + ["repro.serve.sample", "repro.serve.readback"])
    phase = sorted((s, e) for n in PHASES for s, e in got[n])
    assert all(e <= s2 for (_, e), (s2, _) in zip(phase, phase[1:]))


def test_generate_returns_the_same_tokens_with_the_profiler_on(tmp_path):
    plain = _engine(2)
    traced = _engine(2)
    want = plain.generate(_prompts(2), max_new_tokens=5)
    got, spans = _traced(tmp_path, lambda: traced.generate(_prompts(2), max_new_tokens=5))
    assert got == want and spans
    assert len(traced.decode_s) == len(plain.decode_s) == 4


def test_steps_lower_with_no_span_name():
    eng = _engine(2)
    tokens = jnp.ones((2, 4), jnp.int32)
    prefill = eng._prefill.lower(eng.params, tokens).as_text()
    caches = jax.eval_shape(eng._prefill, eng.params, tokens)[1]
    decode = eng._decode.lower(eng.params, tokens[:, :1], jnp.int32(4), caches).as_text()
    assert prefill.startswith("module @jit__prefill ")
    assert decode.startswith("module @jit__decode ")
    assert "repro.serve" not in prefill and "repro.serve" not in decode
