"""Shared by the benchmark's CPU tests: a checkout-like root that holds the
repository's ``bench/`` and a ``BENCHMARK.json`` with tiny cells added by
files and entries alone, and the AES kernel steered to the Pallas
interpreter (the benchmark has no option for it)."""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

TINY_MODEL = dict(hidden_size=64, head_dim=16, num_attention_heads=4, num_key_value_heads=2,
                  intermediate_size=128, num_hidden_layers=2, vocab_size=512,
                  torch_dtype="float32")
# float32 at this size: the served path and the reference differ by
# rounding only (about 1e-6 in the logits); a wrong token or a stale cache
# moves a logit gap by the logits' spread (about 0.1 to 1)
TINY_LIMIT = 1e-3
TINY_LIMITS = {"max_logit_gap": TINY_LIMIT, "mean_logit_gap": TINY_LIMIT / 10}


def make_root(tmp: Path, *, serve_traffic=None, aes_traffic=None) -> Path:
    """A root with the repository's benchmark and two added cells,
    ``tiny-serve`` (config ``tiny-qwen``) and ``tiny-aes`` (``aes-600b``)."""
    root = Path(tmp) / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "bench/configs/qwen3-1.7b.json").read_text())
    cfg.update(TINY_MODEL, deployment={"slots": 4, "max_seq_len": 64},
               limits=TINY_LIMITS)
    (root / "bench/configs/tiny-qwen.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-closed.json").write_text(json.dumps(
        serve_traffic or {"loop": "closed", "clients": 4, "prompt_tokens": 8, "new_tokens": 6}))
    (root / "bench/traffic/tiny-open.json").write_text(json.dumps(
        aes_traffic or {"loop": "open", "arrivals": {"process": "poisson", "rate_per_s": 16}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.setdefault("workloads", [w["name"] for w in bench["workloads"]])
    bench["configs"].append({"name": "tiny-qwen", "source": "test", "reduced": [],
                             "file": "bench/configs/tiny-qwen.json", "why": "test"})
    bench["workloads"] += [
        {"name": "tiny-serve", "config": "tiny-qwen", "traffic": "tiny-closed", "chips": 1,
         "why": "test"},
        {"name": "tiny-aes", "config": "aes-600b", "traffic": "tiny-open", "chips": 1,
         "why": "test"}]
    for cell, metrics in TINY_METRICS.items():
        for name, (unit, moves) in metrics.items():
            kind = "end_to_end" if moves is None else "per_layer"
            entry = next((m for m in bench[kind] if m["name"] == name), None)
            if entry is None:
                entry = {"name": name, "unit": unit, "better": "lower", "source": "host_clock",
                         "workloads": []}
                entry.update({"bound": 0.1} if moves is None else {"layer": "test", "moves": moves})
                bench[kind].append(entry)
            if "workloads" in entry:
                entry["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


# the metrics each tiny cell reports: name -> (unit, the end-to-end metric a
# per-layer one moves, or None for an end-to-end metric)
_E2E = {"setup_s": ("s", None), "p50_ms": ("ms", None), "p95_ms": ("ms", None)}
TINY_METRICS = {
    "tiny-serve": dict(_E2E, tokens_per_s=("tokens/s", None), prefill_ms=("ms", "p50_ms"),
                       decode_step_ms=("ms", "tokens_per_s"),
                       decode_roofline=("%", "tokens_per_s"),
                       **{"mfu.serve": ("%", "tokens_per_s"),
                          "idle_share.serve": ("%", "tokens_per_s")}),
    "tiny-aes": dict(_E2E, rps=("1/s", None), aes_kernel_us=("us", "p50_ms"),
                     **{"dispatch_us.aes": ("us", "p50_ms"), "idle_share.aes": ("%", "p50_ms")}),
}


def interpret_aes(monkeypatch):
    """Send ``ops.aes_ctr``'s compiled-kernel calls to the Pallas interpreter."""
    from repro.kernels import ops
    compiled_path = ops.aes_ctr

    def interpreted(pt, key, *, nonce=0, backend):
        assert backend == "pallas"
        return compiled_path(pt, key, nonce=nonce, backend="pallas_interpret")

    monkeypatch.setattr(ops, "aes_ctr", interpreted)


def run_line(root: Path, workload: str, *, seed=2**33 + 5, seconds=0.5) -> dict:
    """Drive a cell past the chip check and parse its result line."""
    import time

    from bench import run, spec
    from bench.peaks import peaks_for
    cell = spec.load_cell(root, workload)
    line = run.run_cell(cell, seed, seconds, False, DEVICE, peaks_for(DEVICE["kind"]),
                        time.perf_counter())
    return json.loads(line)
