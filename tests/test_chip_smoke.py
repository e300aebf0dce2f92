"""chip_smoke.py's phases on the CPU at the reduced size.  The script
itself refuses any platform but a TPU; these tests drive its phase
functions directly, and steer the AES kernel to the Pallas interpreter
here, in the test (the script has no option for it)."""
import functools
import importlib
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.kernels import ops
from repro.launch import compile_cache, serve

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_phase_refuses_cpu(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="'cpu'"):
        chip_smoke.device_phase()


def test_script_exits_nonzero_on_cpu_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert '"ok"' not in p.stdout


def test_endpoint_phase_reduced(chip_smoke, capsys):
    step_us = chip_smoke.endpoint_phase(
        serve.engine_config(chip_smoke.ARCH, reduced_size=True), "cpu-test")
    out = capsys.readouterr().out
    assert step_us > 0
    assert "argmax agrees at 64/64" in out
    assert "[cpu-test] junctiond" in out and "[cpu-test] containerd" in out


def test_aes_phase_interpreted(chip_smoke, monkeypatch, capsys):
    compiled_path = ops.aes_ctr
    backends = set()

    def interpreted(pt, key, *, backend):
        backends.add(backend)
        return compiled_path(pt, key, backend="pallas_interpret")

    monkeypatch.setattr(ops, "aes_ctr", interpreted)
    aes_kernel = importlib.import_module("repro.kernels.aes_ctr")
    monkeypatch.setattr(aes_kernel, "aes_ctr",
                        functools.partial(aes_kernel.aes_ctr, interpret=True))
    monkeypatch.setattr(chip_smoke, "AES_CALLS", 2)
    monkeypatch.setattr(chip_smoke, "AES_BLOCKS", (38,))
    chip_smoke.aes_phase("cpu-test")
    assert backends == {"pallas"}      # the script asks for the compiled kernel
    assert "[cpu-test] aes-128-ctr 38 blocks (608 B): byte-exact" in capsys.readouterr().out


def test_result_line_shape(chip_smoke, monkeypatch, capsys):
    """main() prints the JSON result last, and only after every phase."""
    calls = []
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_phase", lambda: calls.append("device") or device)
    monkeypatch.setattr(chip_smoke, "endpoint_phase", lambda cfg, dev: calls.append("endpoint"))
    monkeypatch.setattr(chip_smoke, "aes_phase", lambda dev: calls.append("aes"))
    monkeypatch.setattr(serve, "engine_config", lambda arch: None)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "no cache")
    assert chip_smoke.main() == 0
    assert calls == ["device", "endpoint", "aes"]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": device}


def test_failed_phase_prints_no_result(chip_smoke, monkeypatch, capsys):
    def fail(dev):
        raise chip_smoke.SmokeFailure("AES-CTR over 38 blocks differs from the reference")

    monkeypatch.setattr(chip_smoke, "device_phase", lambda: {"platform": "tpu"})
    monkeypatch.setattr(chip_smoke, "endpoint_phase", lambda cfg, dev: None)
    monkeypatch.setattr(chip_smoke, "aes_phase", fail)
    monkeypatch.setattr(serve, "engine_config", lambda arch: None)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "no cache")
    assert chip_smoke.main() == 1
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_leaves_jax_setting_alone(monkeypatch, tmp_path):
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    assert compile_cache.enable_compile_cache() == before[0]
    assert (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs) == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", raising=False)
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert compile_cache.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.1
    finally:   # nothing compiles in between, so nothing is written there
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])
