"""Serving CLI:  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b
   --smoke --requests 8 --max-new 16"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config, get_smoke_config
from repro.launch import compile_cache
from repro.models import api
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--variant", default=None)
    ap.add_argument("--plan", default=None,
                    help="ftfi.save_plan artifact (.npz) to serve with — "
                         "loads the integration plan instead of rebuilding "
                         "the IT at startup")
    ap.add_argument("--prefill-mode", choices=("fused", "replay"),
                    default="fused",
                    help="fused: one prefill-into-cache call per admission "
                         "group (mid-wave admission); replay: legacy "
                         "token-by-token prompt replay through decode")
    args = ap.parse_args()
    compile_cache.configure()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    if args.variant:
        cfg = cfg.replace(attention_variant=args.variant,
                          topo_dist_scale=1.0 / args.max_len)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=args.slots,
                      max_len=args.max_len, plan=args.plan,
                      prefill_mode=args.prefill_mode)
    print(f"serving {args.arch} | slots={args.slots} max_len={args.max_len} "
          f"variant={cfg.attention_variant} prefill={eng.prefill_mode}")
    print(eng.plan_banner())
    rng = np.random.default_rng(0)
    reqs = []
    for r in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=8).tolist()
        reqs.append(Request(rid=r, prompt=prompt,
                            max_new_tokens=args.max_new))
        eng.submit(reqs[-1])
    t0 = time.time()
    ticks = eng.run()
    dt = time.time() - t0
    # report what was actually generated (evicted retries, truncation, and
    # failures all mean the old `requests * max_new` figure over-reports)
    st = eng.stats()
    gen_tokens = sum(len(r.out) for r in reqs)
    print(f"served {st['completed']}/{args.requests} requests "
          f"({st['failed']} failed, {st['truncated']} truncated) / "
          f"{gen_tokens} generated tokens in {ticks} ticks, {dt:.2f}s "
          f"({gen_tokens / dt:.1f} tok/s generated; "
          f"prefill {st['prefill_tokens'] / dt:.1f} tok/s, "
          f"decode {st['decode_tokens'] / dt:.1f} tok/s)")
    print(eng.health_banner())


if __name__ == "__main__":
    main()
