"""The entry point: refusal off a TPU, the device named, and the last line."""
import json
import os
import shutil
import subprocess
import sys

import pytest
from bench_testkit import DEVICE, ROOT, interpret_aes, make_root, run_line

from bench import device, run, spec


def _no_result(p):
    return not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_cpu_is_refused_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench/run.py"), "--workload",
                        "aes-600b-poisson", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert _no_result(p)


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qwen3-1.7b-decode",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert p.returncode != 0
    assert _no_result(p)


def test_require_tpu_refuses_cpu_and_too_few_chips(monkeypatch):
    with pytest.raises(device.NoAccelerator, match="'cpu'"):
        device.require_tpu(1)

    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    import jax
    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    assert device.require_tpu(1) == DEVICE
    with pytest.raises(device.NoAccelerator, match="4 chips"):
        device.require_tpu(4)


def test_unknown_device_kind_is_an_error(monkeypatch, capsys):
    monkeypatch.setattr(run, "require_tpu",
                        lambda chips: {"platform": "tpu", "kind": "TPU v9", "count": 1})
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: "none")
    assert run.main(["--workload", "aes-600b-poisson", "--seed", "1", "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert "TPU v9" in out.err and "{" not in out.out


def test_unknown_workload():
    with pytest.raises(KeyError, match="no-such-cell"):
        spec.load_cell(ROOT, "no-such-cell")


def test_each_cell_finds_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert spec.load_system(cell).run
        names = {m.name for m in cell.end_to_end}
        assert {"setup_s", "p50_ms"} <= names and len(names) >= 3
        assert cell.per_layer
        for m in cell.per_layer:
            assert m.moves in names
            assert callable(spec.load_reader(cell, m.name))


@pytest.fixture(scope="module")
def serve_line(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("serve"))
    return run_line(root, "tiny-serve", seconds=0.5)


def test_serve_result_line(serve_line):
    doc = serve_line
    assert list(doc) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert doc["correct"] is True and doc["failed"] == 0 and doc["attempted"] >= 4
    assert set(doc["metrics"]) == {"setup_s", "p50_ms", "p95_ms", "tokens_per_s"}
    assert doc["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert all(v["value"] > 0 for v in doc["metrics"].values())
    assert doc["metrics"]["p95_ms"]["value"] >= doc["metrics"]["p50_ms"]["value"]
    assert doc["device"]["kind"] == "TPU v5 lite" and "memory_peak_bytes" in doc["device"]
    assert set(doc["compared"]) == {"short_requests", "max_logit_gap", "mean_logit_gap"}


def test_aes_result_line(tmp_path, monkeypatch, capsys):
    interpret_aes(monkeypatch)
    doc = run_line(make_root(tmp_path), "tiny-aes", seconds=0.5)
    assert doc["correct"] is True and doc["attempted"] == 8 and doc["failed"] == 0
    assert set(doc["metrics"]) == {"setup_s", "p50_ms", "p95_ms", "rps"}
    assert doc["compared"] == {"unanswered": {"value": 0.0, "limit": 0.0},
                               "wrong_bytes": {"value": 0.0, "limit": 0.0}}
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2:] == ["compared unanswered: 0.0 (limit 0.0) ok",
                        "compared wrong_bytes: 0.0 (limit 0.0) ok"]
