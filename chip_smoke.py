"""Drive the system's main paths once on a TPU, at real size, and fail loudly.

    python chip_smoke.py             # one chip: phases A, B and C
    python chip_smoke.py --chips 4   # four chips: phase S only

A  FTFI integrate (`ftfi.apply`) over the MST of icosphere(7), 163,842
   vertices, field (n, 64) f32: plan and pallas backends, f = exp(-0.5 s) and
   f = 1 / (1 + c s^2), each checked on sampled rows against a float64 host
   oracle built from exact tree distances.
B  TopoViT-B/16 (`configs/topovit_b16.py`, published widths) train steps at
   batch 128 in bf16 through `vit.forward` + `adamw_update`, depth cut to
   VIT_LAYERS; then a float32 forward of the `pallas` grid path against the
   dense tree-mask `ref` impl at the published 12 layers.
C  The fused `topo_linear_attention` kernel, decay and rank modes, causal and
   bidirectional, at L=4096 against its dense reference.
S  (--chips 4 only) `ftfi.apply_sharded` of the phase-A plan on a 4-device
   mesh against single-device `ftfi.apply`, with the exact collective census
   of the traced and the compiled program.

Phases run in the order A, C, B: B is the longest, and a run cut short still
reports the others.

Each phase prints one JSON line of smoke readings: one cold run per case,
timings from the host clock around `block_until_ready`. They are not
benchmark numbers. The last line is `{"ok": true, "device": {...}}`. No
exception is caught: any fault, gate miss or ladder demotion exits non-zero
before that line is printed. Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import warnings

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
NOTE = "smoke reading: one cold run, not a benchmark number"

# f = 1 / (1 + RATIONAL_C s^2): smooth enough over the mesh MST's distance
# range that the plan backend's Chebyshev engine stays far inside the gate
RATIONAL_C = 0.25
# batch 128 as 32 microbatches of 4 images: Alg. 1's FFT temporaries for one
# 8-image microbatch already need ~15 GiB of the v5e's 16 GB
VIT_MICROBATCHES = 32
# a batch-128 train step takes ~14.3 s per layer on a v5e (43.05 s at 3
# layers, 57.36 s at 4), so 6 steps at 12 layers would take ~17 minutes:
# the train depth is cut to keep the whole smoke inside its 20-minute budget
VIT_LAYERS = 3


class SmokeFailure(AssertionError):
    """A gate of the smoke was missed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(record: dict) -> None:
    print(json.dumps(dict(record, note=NOTE), default=float), flush=True)


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def run_compiled(fn, *args):
    """AOT-compile `fn` for `args`, run it once, and time both. Returns
    (output, record) with compile/wall seconds and whether a Pallas TPU
    kernel (`tpu_custom_call`) is in the compiled program."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    return out, {"compile_s": t1 - t0, "wall_s": t2 - t1,
                 "tpu_custom_call": "tpu_custom_call" in compiled.as_text()}


def rel_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


# ----------------------------------------------------------------------------
# phase A: FTFI integrate at mesh scale
# ----------------------------------------------------------------------------


def mesh_plan(subdiv: int):
    """(tree, spec, params, build seconds) for the MST of icosphere(subdiv)."""
    from repro import ftfi
    from repro.graphs.meshes import icosphere, mesh_graph
    from repro.graphs.mst import minimum_spanning_tree

    t0 = time.perf_counter()
    tree = minimum_spanning_tree(mesh_graph(*icosphere(subdiv)))
    spec, params = ftfi.build(tree, seed=SEED)
    return tree, spec, params, time.perf_counter() - t0


def mesh_field(n: int, d: int) -> np.ndarray:
    return np.random.default_rng(SEED).standard_normal((n, d)).astype(
        np.float32)


def integrand_fns():
    """(name, f for ftfi, the same f in float64 numpy) pairs."""
    from repro.core import cordial as C

    c = RATIONAL_C
    return [("exp", C.Exponential(-0.5), lambda s: np.exp(-0.5 * s)),
            ("rational", C.Rational((1.0,), (1.0, 0.0, c)),
             lambda s: 1.0 / (1.0 + c * s * s))]


def phase_ftfi(subdiv: int = 7, d: int = 64, rows: int = 32,
               gate: float = 1e-4) -> dict:
    import jax
    from repro import ftfi
    from repro.graphs.traverse import tree_distances_from

    tree, spec, params, build_s = mesh_plan(subdiv)
    X = mesh_field(spec.n, d)
    rows_ = np.random.default_rng(SEED + 1).choice(spec.n, rows,
                                                   replace=False)
    fns = integrand_fns()
    t0 = time.perf_counter()
    X64 = X.astype(np.float64)
    refs = {name: np.empty((rows, d)) for name, _, _ in fns}
    for k, i in enumerate(rows_):
        dist = tree_distances_from(tree, int(i))
        for name, _, f_np in fns:
            refs[name][k] = f_np(dist) @ X64
    oracle_s = time.perf_counter() - t0
    Xd = jax.device_put(X)
    runs = []
    for backend in ("plan", "pallas"):
        for name, fn, _ in fns:
            Y, rec = run_compiled(
                lambda p, x, fn=fn, b=backend: ftfi.apply(spec, p, fn, x,
                                                          backend=b),
                params, Xd)
            err = rel_err(np.asarray(Y)[rows_], refs[name])
            rec.update(backend=backend, f=name, rel_err=err,
                       engine=ftfi.describe(spec, fn, backend)["cross_engine"],
                       peak_bytes=_peak_bytes())
            runs.append(rec)
            check(err <= gate, f"phase A {backend}/{name}: rel_err {err:.3e} "
                               f"> {gate:g} vs the float64 oracle")
    return {"phase": "A", "n": spec.n, "d": d, "rows": rows,
            "build_s": build_s, "oracle_s": oracle_s, "runs": runs}


# ----------------------------------------------------------------------------
# phase B: TopoViT-B/16 train steps + f32 logits parity
# ----------------------------------------------------------------------------


def make_vit_train_step(cfg, integ, opt_cfg, microbatches: int):
    """One jitted-step body: softmax cross-entropy of `vit.forward` on
    (patches, labels), gradients averaged over `microbatches` slices of the
    batch, one `adamw_update`. Returns (params, opt_state, loss)."""
    import jax
    import jax.numpy as jnp
    from repro.models import vit
    from repro.optim.adamw import adamw_update

    def loss_fn(params, patches, labels):
        logits = vit.forward(cfg, params, patches, integ).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=1) - picked)

    grad_fn = jax.value_and_grad(loss_fn)

    def step(params, opt_state, patches, labels):
        def split(a):
            return a.reshape((microbatches, -1) + a.shape[1:])

        def acc(carry, mb):
            loss, grads = grad_fn(params, *mb)
            return jax.tree.map(jnp.add, carry, (loss, grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))
        (loss, grads), _ = jax.lax.scan(
            acc, zero, (split(patches), split(labels)))
        loss = loss / microbatches
        grads = jax.tree.map(lambda g, p: (g / microbatches).astype(p.dtype),
                             grads, params)
        params, opt_state, _ = adamw_update(grads, opt_state, params, opt_cfg)
        return params, opt_state, loss

    return step


def vit_batch(cfg, batch: int, patch_dim: int, num_classes: int, seed: int):
    rng = np.random.default_rng(seed)
    patches = rng.standard_normal(
        (batch, cfg.num_prefix_embeddings, patch_dim)).astype(np.float32)
    return patches, rng.integers(0, num_classes, batch).astype(np.int32)


def mask_engine(cfg, integ, params) -> str:
    """What runs layer 0's mask: "dense" (the small-tree product by f(D)),
    else the cross engine of the grid plan `integ` on the config's
    backend."""
    from repro import ftfi
    from repro.core.masks import mask_f, tree_fastmult_path
    from repro.models import attention as A

    if tree_fastmult_path(integ) == "dense":
        return "dense"

    p_topo = {k: v[0] for k, v in params["blocks"]["topo"].items()}
    coeffs = np.asarray(A.topo_mask_coeffs(cfg, p_topo)[0])
    f = mask_f(cfg.topo_g, coeffs, cfg.topo_dist_scale)
    return ftfi.describe(integ[0], f,
                         A.resolve_topo_backend(cfg))["cross_engine"]


def phase_topovit(cfg, *, train_layers: int | None = None, batch: int = 128,
                  steps: int = 5, microbatches: int = VIT_MICROBATCHES,
                  parity_batch: int = 8, patch_dim: int = 768,
                  num_classes: int = 1000, gate: float = 1e-4) -> dict:
    """Train steps at `train_layers` (default: `cfg`'s depth), then the f32
    logits parity at `cfg`'s own depth."""
    import jax
    import jax.numpy as jnp
    from repro.models import vit
    from repro.optim.adamw import AdamWConfig, adamw_init

    # --- bf16 train steps through the config's own topo impl ---
    tcfg = cfg.replace(num_layers=train_layers or cfg.num_layers)
    integ = vit.build_grid_integrator(tcfg)
    params = vit.init_params(tcfg, jax.random.PRNGKey(SEED), num_classes,
                             patch_dim)
    opt_state = adamw_init(params)
    patches, labels = vit_batch(tcfg, batch, patch_dim, num_classes, SEED)
    patches, labels = jax.device_put(patches), jax.device_put(labels)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=steps + 1,
                          weight_decay=0.0)
    step = make_vit_train_step(tcfg, integ, opt_cfg, microbatches)
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, patches, labels).compile()
    compile_s = time.perf_counter() - t0
    print(f"phase B: train step compiled in {compile_s:.1f} s",
          file=sys.stderr, flush=True)
    train_engine = mask_engine(tcfg, integ, params)
    losses, step_s = [], []
    for _ in range(steps + 1):  # step 0 is the warm-up
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, patches, labels)
        losses.append(float(jax.block_until_ready(loss)))
        step_s.append(time.perf_counter() - t0)
        print(f"phase B: step {len(losses) - 1} loss {losses[-1]:.4f} in "
              f"{step_s[-1]:.1f} s", file=sys.stderr, flush=True)
    check(all(np.isfinite(losses)), f"phase B: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"phase B: loss did not fall over {steps} steps: {losses}")
    train = {"layers": tcfg.num_layers, "batch": batch,
             "microbatches": microbatches, "dtype": tcfg.dtype,
             "engine": train_engine,
             "compile_s": compile_s, "step_s": step_s, "losses": losses,
             "peak_bytes": _peak_bytes()}
    del params, opt_state, compiled

    # --- f32 logits: pallas grid path vs the dense tree-mask oracle ---
    cfg32 = cfg.replace(dtype="float32")
    params32 = vit.init_params(cfg32, jax.random.PRNGKey(SEED + 1),
                               num_classes, patch_dim)
    x, _ = vit_batch(cfg, parity_batch, patch_dim, num_classes, SEED + 1)
    logits, runs = {}, {}
    with jax.default_matmul_precision("highest"):
        for impl in ("pallas", "ref"):
            c = cfg32.replace(topo_attn_impl=impl)
            integ = vit.build_grid_integrator(c)
            logits[impl], rec = run_compiled(
                lambda p, xx, c=c, integ=integ: vit.forward(c, p, xx, integ),
                params32, jnp.asarray(x))
            rec["engine"] = (mask_engine(c, integ, params32)
                             if impl != "ref" else "dense_tree_mask")
            runs[impl] = rec
    err = rel_err(logits["pallas"], logits["ref"])
    check(err <= gate, f"phase B: pallas vs ref logits rel_err {err:.3e} "
                       f"> {gate:g}")
    return {"phase": "B", "d_model": cfg.d_model, "train": train,
            "parity": {"layers": cfg.num_layers, "batch": parity_batch,
                       "rel_err": err, **{
                f"{k}_{m}": v for k, r in runs.items()
                for m, v in r.items()}},
            "peak_bytes": _peak_bytes()}


# ----------------------------------------------------------------------------
# phase C: the topo_linear_attention kernel
# ----------------------------------------------------------------------------


def phase_topo_kernel(B: int = 2, H: int = 12, L: int = 4096, m: int = 64,
                      hd: int = 64, gate: float = 1e-3) -> dict:
    """Decay mode (g=exp, degree 1) and rank mode (degree 2), causal and
    bidirectional, against the dense O(L^2) reference. `gate` is the
    tolerance of tests/test_topo_attention.py."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.topo_linear_attention.ops import topo_linear_attention
    from repro.kernels.topo_linear_attention.ref import (
        topo_linear_attention_ref)

    rng = np.random.default_rng(SEED)
    qf = jnp.asarray(np.abs(rng.standard_normal((B, H, L, m))), jnp.float32)
    kf = jnp.asarray(np.abs(rng.standard_normal((B, H, L, m))), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, L, hd)), jnp.float32)
    runs = []
    for degree, mode in ((1, "decay"), (2, "rank")):
        cs = rng.uniform(-0.5, 0.5, (H, degree + 1)).astype(np.float32)
        cs[:, 0] = rng.uniform(1.5, 2.5, H)
        cs = jnp.asarray(cs)
        for causal in (True, False):
            kw = dict(g="exp", dist_scale=1.0 / L, causal=causal)
            with jax.default_matmul_precision("highest"):
                out, rec = run_compiled(
                    lambda q, k, vv, c, kw=kw: topo_linear_attention(
                        q, k, vv, c, **kw), qf, kf, v, cs)
                ref = topo_linear_attention_ref(qf, kf, v, cs, **kw)
            err = rel_err(out, ref)
            rec.update(mode=mode, causal=causal, rel_err=err,
                       peak_bytes=_peak_bytes())
            runs.append(rec)
            check(err <= gate, f"phase C {mode} causal={causal}: rel_err "
                               f"{err:.3e} > {gate:g}")
    return {"phase": "C", "B": B, "H": H, "L": L, "m": m, "hd": hd,
            "runs": runs}


# ----------------------------------------------------------------------------
# phase S (four chips): the sharded integrate
# ----------------------------------------------------------------------------


# The v5e compile of phase S at full size (icosphere(7), d=64, replicated
# inputs and output). The integrate's own two collectives are the halo
# all-to-all and the partial-output reduce-scatter; XLA's TPU backend emits
# the latter as a `kind=kCustom, calls=%all-reduce-scatter` fusion (emitter
# SingleInputAllReduceScatterFusion: an all-reduce of the padded
# f32[164352,64] buffer, then a dynamic-slice of this device's 41088 rows),
# counted here as the reduce-scatter it is. The collective-permute (381 rows
# to the next device) and the all-gather (f32[4,40961,64]) deliver the
# sliced (n, d) result replicated. Inputs left unpinned let XLA shard X,
# which costs one more collective-permute (a row of the input scatter).
TPU_COLLECTIVES = {"all-to-all": 1, "reduce-scatter": 1,
                   "collective-permute": 1, "all-gather": 1}


def compiled_collectives(hlo: str) -> dict:
    """Collective op counts of a compiled program, with each TPU
    `all-reduce-scatter` fusion counted as one reduce-scatter instead of
    the all-reduce inside it."""
    import re
    from repro.roofline.analysis import collective_breakdown

    counts = collective_breakdown(hlo)["counts"]
    fused = len(re.findall(r"calls=%all-reduce-scatter\b", hlo))
    if fused:
        counts["all-reduce"] -= fused
        counts["reduce-scatter"] = counts.get("reduce-scatter", 0) + fused
    return {k: v for k, v in counts.items() if v}


def phase_sharded(devices: int = 4, subdiv: int = 7, d: int = 64,
                  gate: float = 1e-5,
                  expect_compiled: dict = TPU_COLLECTIVES) -> dict:
    """`apply_sharded` on replicated inputs, with a replicated result,
    against single-device `apply`. The traced program must ask for exactly
    one all_to_all (halo rows) and one reduce_scatter (partial outputs); the
    compiled one must hold exactly `expect_compiled`."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import ftfi
    from repro.analysis.jaxpr_audit import collective_census
    from repro.core import cordial as C
    from repro.launch.mesh import make_mesh

    _, spec, params, build_s = mesh_plan(subdiv)
    X = jax.device_put(mesh_field(spec.n, d))
    fn = C.Exponential(-0.5)
    mesh = make_mesh((devices,), ("data",))
    Y1, single = run_compiled(lambda p, x: ftfi.apply(spec, p, fn, x),
                              params, X)
    rep = NamedSharding(mesh, P())
    params_r, X_r = jax.device_put((params, X), rep)
    sharded = jax.jit(
        lambda p, x: ftfi.apply_sharded(spec, p, fn, x, mesh=mesh),
        in_shardings=(rep, rep), out_shardings=rep)
    asked = collective_census(jax.make_jaxpr(sharded)(params_r, X_r))
    t0 = time.perf_counter()
    compiled = sharded.lower(params_r, X_r).compile()
    t1 = time.perf_counter()
    Ys = jax.block_until_ready(compiled(params_r, X_r))
    t2 = time.perf_counter()
    got = compiled_collectives(compiled.as_text())
    err = rel_err(Ys, Y1)
    check(err <= gate, f"phase S: sharded vs single-device rel_err "
                       f"{err:.3e} > {gate:g}")
    check(asked == {"all_to_all": 1, "reduce_scatter": 1},
          f"phase S: the program should ask for one all_to_all and one "
          f"reduce_scatter, asks for {asked}")
    check(got == expect_compiled,
          f"phase S: compiled collectives {got}, expected {expect_compiled}")
    return {"phase": "S", "devices": devices, "n": spec.n, "d": d,
            "build_s": build_s, "rel_err": err,
            "shard_stats": ftfi.shard_stats(spec, devices),
            "collectives_traced": asked, "collectives_compiled": got,
            "single": single,
            "sharded": {"compile_s": t1 - t0, "wall_s": t2 - t1},
            "peak_bytes": _peak_bytes()}


# ----------------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------------


@contextlib.contextmanager
def no_fallback():
    """Turn every ladder demotion into an error and keep the disk plan
    cache off, so each phase runs the path it names, built from seeds."""
    from repro.core import ladder, plan_cache

    plan_cache.configure(None)
    with warnings.catch_warnings():
        warnings.simplefilter("error", ladder.BackendDemotionWarning)
        yield


def check_ladder_clean() -> dict:
    from repro.core import ladder

    st = ladder.stats()
    check(st["demotions"] == 0 and st["errors"] == 0
          and st["nonfinite"] == 0 and not st["blocked"],
          f"degradation ladder was used: {st}")
    return st


def check_kernel_ran(rec: dict, what: str) -> None:
    check(rec["tpu_custom_call"],
          f"{what}: no tpu_custom_call in the compiled program")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded integrate (phase S)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    check(len(jax.devices()) >= args.chips,
          f"--chips {args.chips}: only {len(jax.devices())} devices")
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.configs.topovit_b16 import CONFIG
    from repro.launch import compile_cache

    cache_dir = compile_cache.configure()
    print(json.dumps({"device_kind": dev.device_kind,
                      "devices": len(jax.devices()),
                      "jax": jax.__version__, "compile_cache": cache_dir}),
          flush=True)
    with no_fallback():
        if args.chips == 4:
            emit(phase_sharded(devices=4))
        else:
            rec = phase_ftfi()
            emit(rec)
            for r in rec["runs"]:
                if r["backend"] == "pallas":
                    check_kernel_ran(r, f"phase A pallas/{r['f']}")
            rec = phase_topo_kernel()
            emit(rec)
            for r in rec["runs"]:
                check_kernel_ran(r, f"phase C {r['mode']}/causal="
                                    f"{r['causal']}")
            emit(phase_topovit(CONFIG, train_layers=VIT_LAYERS))
        check_ladder_clean()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
