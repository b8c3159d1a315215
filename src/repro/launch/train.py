"""Training CLI:  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b
   [--smoke] [--steps N] [--batch B] [--seq L] [--variant topo] ...

With --smoke a reduced config runs end-to-end on local devices; the full
configs are what the multi-pod dry-run lowers for the production mesh (this
CLI accepts them unchanged when pointed at real hardware).
"""
from __future__ import annotations

import argparse

from repro.configs.base import get_config, get_smoke_config
from repro.launch import compile_cache
from repro.optim.adamw import AdamWConfig
from repro.train.loop import TrainLoopConfig, run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--variant", default=None,
                    choices=[None, "full", "performer", "topo"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.configure()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    overrides = {"dtype": "float32"} if args.smoke else {}
    if args.variant:
        overrides["attention_variant"] = args.variant
        overrides["topo_dist_scale"] = 1.0 / args.seq
    if overrides:
        cfg = cfg.replace(**overrides)

    loop = TrainLoopConfig(
        steps=args.steps, batch_size=args.batch, seq_len=args.seq,
        microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, seed=args.seed,
        compress_grads=args.compress_grads)
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(1, args.steps // 20))
    res = run_training(cfg, loop, opt)
    print(f"final loss: {res['losses'][-1]:.4f} "
          f"(first: {res['losses'][0]:.4f}); "
          f"stragglers flagged: {len(res['straggler_events'])}")


if __name__ == "__main__":
    main()
