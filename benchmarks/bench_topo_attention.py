"""Paper Table 1 proxy: Topological Performer attention.

(ImageNet-scale accuracy cannot be reproduced offline; this measures the
three claims that transfer: (a) Algorithm-1 masked attention is numerically
exact vs brute force, (b) it scales near-linearly in L vs the O(L^2)
materialized mask, (c) the 3-parameter learnable mask gives a quality gain
over the unmasked Performer on a controlled task — see also
examples/train_topological_lm.py for the end-to-end version.)"""
from __future__ import annotations

import argparse
import pathlib
import sys

import numpy as np
import jax
import jax.numpy as jnp

if __package__ in (None, ""):  # `python benchmarks/bench_topo_attention.py`
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmarks.common import emit, timeit
from repro.core import masks as MK
from repro import ftfi
from repro.core.toeplitz import toeplitz_dense


def impl_sweep(rng, quick=False):
    """The cfg.topo_attn_impl axis on the sequence path -> JSON rows.

    Default bench config: the TopoViT mask family (g=exp, degree 2 — the
    general low-degree-polynomial path, i.e. the fft CHUNK-LOOP vs the fused
    kernel), B=2, H=4, m=hd=64, causal. `fft` is the exact Toeplitz-FFT
    column-chunk path; `pallas` is the fused kernels/topo_linear_attention
    step (compiled Pallas on TPU, its XLA chunked-scan twin elsewhere —
    measured steady-state after jit warmup). rel_err is vs the dense ref
    oracle where it fits, vs the exact fft path at large L.
    """
    import types

    import jax.numpy as jnp

    from repro.kernels.topo_linear_attention.ops import topo_linear_attention
    from repro.kernels.topo_linear_attention.ref import (
        topo_linear_attention_ref)
    from repro.models.attention import _topo_fft_attention

    B, H, m, hd = 2, 4, 64, 64
    g, degree = "exp", 2
    rows = []
    for L in (512, 1024) if quick else (512, 4096):
        s = 1.0 / L
        cfg = types.SimpleNamespace(topo_g=g, topo_dist_scale=s)
        cs = jnp.asarray([[0.0, -0.5, -0.25]] * H, jnp.float32)
        qf = jnp.asarray(np.abs(rng.normal(size=(B, L, H, m))), jnp.float32)
        kf = jnp.asarray(np.abs(rng.normal(size=(B, L, H, m))), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, L, H, hd)), jnp.float32)
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (qf, kf, v))
        fft_fn = jax.jit(
            lambda q, k, w: _topo_fft_attention(cfg, q, k, w, cs, True))
        fused_fn = jax.jit(
            lambda q, k, w: topo_linear_attention(
                q, k, w, cs, g=g, dist_scale=s, causal=True))
        out_fft = jax.block_until_ready(fft_fn(qf, kf, v)).transpose(0, 2, 1, 3)
        out_fused = jax.block_until_ready(fused_fn(qt, kt, vt))
        if L <= 512:
            anchor = topo_linear_attention_ref(qt, kt, vt, cs, g=g,
                                               dist_scale=s, causal=True)
        else:
            anchor = out_fft  # the fft path is exact at any L
        nrm = float(jnp.max(jnp.abs(anchor)))
        t_fft = timeit(lambda: jax.block_until_ready(fft_fn(qf, kf, v)))
        t_fused = timeit(lambda: jax.block_until_ready(fused_fn(qt, kt, vt)))
        for impl, t, out in (("fft", t_fft, out_fft),
                             ("pallas", t_fused, out_fused)):
            err = float(jnp.max(jnp.abs(out - anchor))) / nrm
            rows.append({"case": "seq_topo", "L": L, "impl": impl,
                         "g": g, "degree": degree, "causal": True,
                         "t_s": t, "rel_err": err,
                         "speedup_vs_fft": t_fft / t})
            emit(f"tab1/impl/L{L}/{impl}", t,
                 f"rel_err={err:.2e} speedup_vs_fft={t_fft/t:.2f}x")
    return rows


def exactness(rng):
    L, d, m = 128, 16, 8
    qf = jnp.asarray(np.abs(rng.normal(size=(2, L, m))), jnp.float32)
    kf = jnp.asarray(np.abs(rng.normal(size=(2, L, m))), jnp.float32)
    V = jnp.asarray(rng.normal(size=(2, L, d)), jnp.float32)
    for g, coeffs in [("exp", [0.0, -0.4]), ("exp", [0.0, -0.3, -0.2]),
                      ("identity", [1.0, 0.5, 0.1])]:
        cs = jnp.asarray(coeffs, jnp.float32)
        fm = MK.make_sequence_fastmult(g, cs, L, causal=True, dist_scale=1 / L)
        got = MK.masked_linear_attention(qf, kf, V, fm)
        Fv = MK.sequence_mask_values(g, cs, L, 1 / L)
        ref = MK.masked_attention_bruteforce(qf, kf, V,
                                             toeplitz_dense(Fv, L, True))
        err = float(jnp.max(jnp.abs(got - ref)))
        emit(f"tab1/exactness/{g}_t{len(coeffs)-1}", 0.0, f"maxerr={err:.2e}")


def scaling(rng):
    d, m = 32, 16
    cs = jnp.asarray([0.0, -0.3, -0.1], jnp.float32)
    for L in (512, 2048, 8192):
        qf = jnp.asarray(np.abs(rng.normal(size=(1, L, m))), jnp.float32)
        kf = jnp.asarray(np.abs(rng.normal(size=(1, L, m))), jnp.float32)
        V = jnp.asarray(rng.normal(size=(1, L, d)), jnp.float32)
        fm = MK.make_sequence_fastmult("exp", cs, L, causal=True,
                                       dist_scale=1 / L)
        fast = jax.jit(lambda q, k, v: MK.masked_linear_attention(q, k, v, fm))
        t_fast = timeit(lambda: jax.block_until_ready(fast(qf, kf, V)))
        if L <= 2048:
            Fv = MK.sequence_mask_values("exp", cs, L, 1 / L)
            mask = toeplitz_dense(Fv, L, True)
            brute = jax.jit(lambda q, k, v: MK.masked_attention_bruteforce(
                q, k, v, mask))
            t_brute = timeit(lambda: jax.block_until_ready(brute(qf, kf, V)))
            emit(f"tab1/latency/L{L}/alg1_fft", t_fast,
                 f"brute={t_brute*1e6:.0f}us speedup={t_brute/t_fast:.2f}x")
        else:
            emit(f"tab1/latency/L{L}/alg1_fft", t_fast, "brute=OOM-skip")


def tree_attention(rng, backends=("plan",), side=8):
    """Grid-MST topological masking (the ViT path) per executor backend:
    exactness vs the dense mask and per-call latency of Algorithm 1."""
    from repro.graphs.graph import grid_graph
    from repro.graphs.mst import minimum_spanning_tree
    from repro.graphs.traverse import tree_all_pairs

    L, d, m = side * side, 16, 8
    g, coeffs = "exp", jnp.asarray([0.0, -0.25, -0.05], jnp.float32)
    mst = minimum_spanning_tree(grid_graph(side, side))
    D = tree_all_pairs(mst)
    qf = jnp.asarray(np.abs(rng.normal(size=(2, L, m))), jnp.float32)
    kf = jnp.asarray(np.abs(rng.normal(size=(2, L, m))), jnp.float32)
    V = jnp.asarray(rng.normal(size=(2, L, d)), jnp.float32)
    mask = MK.mask_f(g, coeffs, 1.0 / L)(jnp.asarray(D))
    ref = MK.masked_attention_bruteforce(qf, kf, V, mask)
    for backend in backends:
        plan = ftfi.build(mst, leaf_size=16)
        fm = MK.make_tree_fastmult(plan, g, coeffs, 1.0 / L,
                                   backend=backend, tree=mst)
        attn = lambda: jax.block_until_ready(
            MK.masked_linear_attention(qf, kf, V, fm))
        got = attn()
        err = float(jnp.max(jnp.abs(got - ref)))
        t = timeit(attn)
        engine = ("dense" if MK.tree_fastmult_path(plan) == "dense" else
                  ftfi.describe(plan[0], MK.mask_f(g, coeffs, 1.0 / L),
                                backend)["cross_engine"])
        emit(f"tab1/tree/L{L}/{backend}", t,
             f"maxerr={err:.2e} engine={engine}")


def run(backends=("plan",), quick=False):
    """Returns the impl-sweep rows (written to BENCH_topo_attention.json by
    benchmarks.run) after the exactness/scaling/tree sections print."""
    rng = np.random.default_rng(0)
    exactness(rng)
    scaling(rng)
    tree_attention(rng, backends=backends)
    return impl_sweep(rng, quick=quick)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="plan",
                    help="comma list of plan,pallas (tree-mask section)")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(backends=tuple(args.backend.split(",")), quick=args.quick)


if __name__ == "__main__":
    main()
