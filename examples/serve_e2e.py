"""End-to-end serving driver (the paper is a serving paper, so this is the
required e2e example): a REAL reduced qwen3 model served with batched
requests through the full junctiond pipeline —

  continuous batcher -> prefill -> decode loop (real JAX compute)
  measured per-step service times -> junctiond vs containerd invocation
  path -> latency report.

    PYTHONPATH=src python examples/serve_e2e.py
"""
from repro.core import FaasdRuntime, FunctionSpec, Simulator, run_sequential
from repro.launch.serve import device_label, engine_config, measure_endpoint
from repro.serving import ServingEngine

cfg = engine_config("qwen3-1.7b", reduced_size=True)
print(f"model: {cfg.name} ({cfg.n_layers}L d={cfg.d_model}, qk_norm={cfg.qk_norm})")

# 1) real model serving: batched requests through the continuous batcher
engine = ServingEngine(cfg, batch_slots=4, max_seq_len=48)
prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [2, 4, 6, 8], [9, 7, 5, 3]]
timing = measure_endpoint(engine, prompts, max_new_tokens=8)
outs = timing.generated
print(f"generated {sum(len(o) for o in outs)} tokens across {len(outs)} requests")
svc_us = timing.decode_step_us
print(f"measured decode step: {svc_us:.0f} us ({device_label()}, reduced model)")

# 2) deploy the endpoint as a junctiond function; drive the FaaS path
for backend in ("containerd", "junctiond"):
    sim = Simulator(seed=1)
    rt = FaasdRuntime(sim, backend=backend)
    rt.deploy_blocking(FunctionSpec(name="qwen3", work_us=svc_us,
                                    payload_bytes=2048, response_bytes=4096))
    s = run_sequential(rt, "qwen3", n=50)
    overhead_pct = 100 * (s.median_ms - svc_us / 1e3) / s.median_ms
    print(f"{backend:11s}: e2e median={s.median_ms:.3f} ms "
          f"(runtime overhead {overhead_pct:.1f}% of e2e), p99={s.p99_ms:.3f} ms")
