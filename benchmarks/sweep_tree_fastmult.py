"""Dense against plan mask FastMult on grid-MST trees: where N_DENSE lies.

    python benchmarks/sweep_tree_fastmult.py --sides 14,24,32,48,64 \
        --width 4096 [--reps 20] [--out chiprun_out/sweep_tree_fastmult.jsonl]

For each side s, the MST of the s x s unit grid (n = s^2, the TopoViT patch
grid's tree) gets a degree-2 exp mask with traced coefficients, as in a
train step, and `masks.make_tree_fastmult` is compiled once on each of its
paths: `dense` (one f32 HIGHEST product by f(D)) and `plan` (the plan
executor, forced by N_DENSE = 0). Each compiled product runs `--reps` times
on an (n, width) f32 field after a warm-up; a line per (n, path) gives the
median and quartiles of the host-clock time to `block_until_ready`, the
device it ran on, and the largest difference between the two paths'
outputs relative to the largest output.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import ftfi  # noqa: E402
from repro.core import masks  # noqa: E402
from repro.graphs.graph import grid_graph  # noqa: E402
from repro.graphs.mst import minimum_spanning_tree  # noqa: E402

PATHS = {"dense": 1 << 30, "plan": 0}  # the N_DENSE that selects each path


def measure(side: int, width: int, reps: int, seed: int = 0) -> list[dict]:
    mst = minimum_spanning_tree(grid_graph(side, side))
    plan = ftfi.build(mst, leaf_size=16)
    n = side * side
    X = jax.random.normal(jax.random.PRNGKey(seed), (n, width), jnp.float32)
    coeffs = jnp.asarray([0.0, -1.0, 0.0], jnp.float32)
    dev = jax.devices()[0]
    rows, outs = [], {}
    n_dense = masks.N_DENSE
    try:
        for path, cap in PATHS.items():
            masks.N_DENSE = cap
            fm = jax.jit(lambda c, x: masks.make_tree_fastmult(
                plan, "exp", c, 0.0625, tree=mst)(x))
            t0 = time.perf_counter()
            compiled = fm.lower(coeffs, X).compile()
            compile_s = time.perf_counter() - t0
            outs[path] = np.asarray(compiled(coeffs, X))
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                compiled(coeffs, X).block_until_ready()
                times.append((time.perf_counter() - t0) * 1e3)
            q1, med, q3 = statistics.quantiles(times, n=4)
            rows.append({"n": n, "width": width, "path": path,
                         "median_ms": med, "q1_ms": q1, "q3_ms": q3,
                         "reps": reps, "compile_s": compile_s,
                         "platform": dev.platform,
                         "device_kind": dev.device_kind})
    finally:
        masks.N_DENSE = n_dense
    gap = float(np.max(np.abs(outs["dense"] - outs["plan"]))
                / np.max(np.abs(outs["plan"])))
    for r in rows:
        r["rel_gap"] = gap
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sides", default="14,24,32,48,64")
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    lines = []
    for side in (int(s) for s in args.sides.split(",")):
        for row in measure(side, args.width, args.reps):
            line = json.dumps(row)
            print(line, flush=True)
            lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
