"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

--smoke trains the reduced variant on the host CPU (the runnable path in
this container); without it, the full config's distributed train step is
built with the production-mesh shardings (requires the pod, or the
dry-run harness for compile-only validation).
"""
from __future__ import annotations

import argparse
import dataclasses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on host CPU")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.config import get_arch, reduced
    from repro.train import AdamWConfig, DataConfig, SyntheticLM, train

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(reduced(cfg), dtype="float32")
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    batch_size=args.batch)
    res = train(cfg, SyntheticLM(dc).batches(), steps=args.steps,
                opt_cfg=AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                                    total_steps=args.steps),
                checkpoint_path=args.checkpoint,
                checkpoint_every=50 if args.checkpoint else 0)
    h = res["history"]
    print(f"\nfinal: loss {h[0]['loss']:.4f} -> {h[-1]['loss']:.4f} over "
          f"{args.steps} steps ({h[-1]['elapsed_s']:.1f}s)")


if __name__ == "__main__":
    main()
