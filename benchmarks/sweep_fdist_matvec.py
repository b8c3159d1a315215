"""The fdist_matvec kernel's two paths by field width: where D_VPU lies.

    python benchmarks/sweep_fdist_matvec.py --shapes 2,43316,43316 \
        --widths 1,3,8,16,32,64 [--reps 5] [--out chiprun_out/sweep.jsonl]

At each shape (B, a, b) of `--shapes` (`;` between shapes; by default the
largest cross bucket of the mesh cell's plan, icosphere(7) at leaf size
64), for each field width d,
`fdist_matvec_batched_pallas` is compiled once on each of its paths: `vpu`
(the narrow-field contraction, forced by D_VPU = 2**30) and `mxu` (forced
by D_VPU = 0), for the rational f of the mesh cell. Each compiled call
runs `--reps` times after a warm-up; a line per (d, path) gives the median
and quartiles of the host-clock time to `block_until_ready`, the device it
ran on, and the largest difference between the two paths' outputs
relative to the largest output.
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

from repro.kernels.fdist_matvec import kernel  # noqa: E402

PATHS = {"vpu": 1 << 30, "mxu": 0}  # the D_VPU that selects each path


def measure(B: int, a: int, b: int, d: int, reps: int,
            seed: int = 0) -> list[dict]:
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.uniform(k[0], (B, a), jnp.float32, 0.0, 3.0)
    y = jax.random.uniform(k[1], (B, b), jnp.float32, 0.0, 3.0)
    v = jax.random.normal(k[2], (B, b, d), jnp.float32)
    coeffs = jnp.asarray([0.8], jnp.float32)
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    rows, outs = [], {}
    d_vpu = kernel.D_VPU
    try:
        for path, cap in PATHS.items():
            kernel.D_VPU = cap
            jax.clear_caches()  # the path is chosen while tracing
            fm = jax.jit(lambda x, y, v, c: kernel.fdist_matvec_batched_pallas(
                x, y, v, c, mode="rational", interpret=interpret))
            t0 = time.perf_counter()
            compiled = fm.lower(x, y, v, coeffs).compile()
            compile_s = time.perf_counter() - t0
            outs[path] = np.asarray(compiled(x, y, v, coeffs))
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                compiled(x, y, v, coeffs).block_until_ready()
                times.append((time.perf_counter() - t0) * 1e3)
            q1, med, q3 = statistics.quantiles(times, n=4)
            rows.append({"B": B, "a": a, "b": b, "d": d, "path": path,
                         "median_ms": med, "q1_ms": q1, "q3_ms": q3,
                         "reps": reps, "compile_s": compile_s,
                         "platform": dev.platform,
                         "device_kind": dev.device_kind})
    finally:
        kernel.D_VPU = d_vpu
    gap = float(np.max(np.abs(outs["vpu"] - outs["mxu"]))
                / np.max(np.abs(outs["mxu"])))
    for r in rows:
        r["rel_gap"] = gap
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="2,43316,43316")
    ap.add_argument("--widths", default="1,3,8,16,32,64")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    lines = []
    for shape in args.shapes.split(";"):
        B, a, b = (int(s) for s in shape.split(","))
        for d in (int(s) for s in args.widths.split(",")):
            for r in measure(B, a, b, d, args.reps):
                print(json.dumps(r), flush=True)
                lines.append(r)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
