"""Paper Fig. 3 / Sec 5: BTFI vs FTFI runtime (preprocessing + integration)
as a function of N, on synthetic path+random-edge graphs and mesh graphs —
with a --backend axis so the BTFI-vs-host-vs-plan-vs-pallas speedup is
reproducible from one command:

  PYTHONPATH=src python benchmarks/bench_ftfi_runtime.py \
      --backend host,plan,pallas --sizes 1000,4000

Methodology:
  * a tiny jitted op runs before any timing so one-time JAX/XLA backend
    initialization never leaks into the first cold-build number (it used to
    inflate pre_it_s of whichever row ran first by ~40ms);
  * the disk plan cache is disabled for the duration of the run — cold
    numbers must measure compilation, not npz reads;
  * pre_it_s / pre_plan_s are COLD builds: every round clears the flat-IT
    and plan caches and the minimum over `repeat` rounds is reported, so a
    stray GC pause can't masquerade as a compile regression;
  * int_s is measured after a jit warmup call, so compile time never leaks
    into the steady-state integration number;
  * plan-backend rows additionally time the incremental-update path
    (`ftfi.update_plan`, single leaf insert) against a cold reweightable
    recompile: upd_s / upd_rebuild_s / upd_speedup.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

import numpy as np

if __package__ in (None, ""):  # `python benchmarks/bench_ftfi_runtime.py`
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmarks.common import emit, timeit
from repro import ftfi
from repro.core import (BTFI, FTFI, Exponential, Forest, build_flat_it,
                        clear_flat_cache, clear_plan_cache)
from repro.core.itree_flat import build_flat_forest
from repro.graphs.graph import random_tree, synthetic_graph
from repro.graphs.meshes import icosphere, mesh_graph
from repro.graphs.mst import minimum_spanning_tree


def _jax_warmup():
    """Absorb one-time JAX/XLA initialization before any timed region."""
    import jax.numpy as jnp

    (jnp.zeros(8) + 1).block_until_ready()


def _cold(fn, rounds: int, clear=None):
    """Min wall-clock over `rounds` cold runs; `clear` resets caches first."""
    best = float("inf")
    for _ in range(max(1, rounds)):
        if clear is not None:
            clear()
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _clear_all():
    clear_flat_cache()
    clear_plan_cache()


def _update_stats(tree, leaf_size: int, repeat: int):
    """(upd_s, upd_rebuild_s): warm single-leaf `ftfi.update_plan` vs a cold
    reweightable recompile — the number the incremental path exists for."""
    spec, pp = ftfi.build(tree, leaf_size=leaf_size, reweightable=True)
    ops = [("insert_leaf", tree.num_vertices // 2, 1.0)]
    t_upd = timeit(lambda: ftfi.update_plan(spec, pp, ops),
                   repeat=max(repeat, 3), warmup=1)
    t_reb = _cold(lambda: ftfi.build(tree, leaf_size=leaf_size,
                                     reweightable=True),
                  rounds=repeat, clear=_clear_all)
    return t_upd, t_reb


def run(sizes=(1000, 4000, 10000), mesh_subdiv=(3, 4), repeat=2,
        backends=("host", "plan", "pallas"), leaf_size=256):
    from repro.core import plan_cache

    _jax_warmup()
    plan_cache.configure(None)  # cold numbers must measure compilation
    try:
        return _run(sizes, mesh_subdiv, repeat, backends, leaf_size)
    finally:
        plan_cache.reset_to_env()


def _run(sizes, mesh_subdiv, repeat, backends, leaf_size):
    rng = np.random.default_rng(0)
    fn = Exponential(-0.5)
    rows = []
    cases = [("synthetic", n, lambda n=n: minimum_spanning_tree(
        synthetic_graph(n, n // 2, seed=1))) for n in sizes]
    for sub in mesh_subdiv:
        verts, faces = icosphere(sub)
        cases.append((f"mesh_ico{sub}", verts.shape[0],
                      lambda v=verts, f=faces: minimum_spanning_tree(
                          mesh_graph(v, f))))
    for name, n, mk in cases:
        tree = mk()
        X = rng.normal(size=(tree.num_vertices, 4))
        t_pre_btfi = timeit(lambda: BTFI(tree, dtype=np.float32), repeat=1,
                            warmup=0)
        btfi = BTFI(tree, dtype=np.float32)
        t_int_btfi = timeit(lambda: btfi.integrate(fn, X), repeat=repeat)
        ref = btfi.integrate(fn, X)
        emit(f"fig3/{name}/n{n}/btfi_pre", t_pre_btfi)
        emit(f"fig3/{name}/n{n}/btfi_int", t_int_btfi)
        for backend in backends:
            # host: the recursive numpy FTFI (the paper's algorithm: the IT
            # walk, no ExpMP shortcut for exp f); plan / pallas / ftfi: the
            # plan, jitted pure (params, X) -> Y on that executor backend
            # ("ftfi" is the plan backend under its API name)
            if backend == "host":
                mk_pre = lambda: FTFI(tree, leaf_size=leaf_size)
            else:
                mk_pre = lambda: ftfi.build(tree, leaf_size=leaf_size)
            # cold IT build, then backend assembly on the now-warm IT cache:
            # the two add up to a full cold preprocessing pass
            t_pre_it = _cold(
                lambda: build_flat_it(tree, leaf_size=leaf_size),
                rounds=repeat, clear=_clear_all)
            build_flat_it(tree, leaf_size=leaf_size)  # warm the IT cache
            t_pre_plan = _cold(mk_pre, rounds=repeat,
                               clear=clear_plan_cache)
            t_pre = t_pre_it + t_pre_plan
            if backend == "host":
                integ = mk_pre()
                engine = "recursive_ftfi"
                run_once = lambda: np.asarray(integ.integrate(fn, X))
            else:
                plan = mk_pre()
                be = "pallas" if backend == "pallas" else "plan"
                engine = ftfi.describe(plan[0], fn, be)["cross_engine"]
                import jax

                fm = jax.jit(ftfi.fastmult(plan[0], fn, backend=be))
                run_once = lambda: np.asarray(fm(plan[1], X))
            # timeit's warmup call absorbs jit compilation before timing
            t_int = timeit(run_once, repeat=repeat, warmup=1)
            got = run_once()
            err = (np.max(np.abs(got - ref))
                   / max(np.max(np.abs(ref)), 1e-9))
            total_f = t_pre + t_int
            total_b = t_pre_btfi + t_int_btfi
            emit(f"fig3/{name}/n{n}/{backend}_pre", t_pre,
                 f"it={t_pre_it*1e3:.1f}ms plan={t_pre_plan*1e3:.1f}ms")
            emit(f"fig3/{name}/n{n}/{backend}_int", t_int,
                 f"speedup_total={total_b/total_f:.2f}x "
                 f"speedup_int={t_int_btfi/t_int:.2f}x relerr={err:.1e} "
                 f"engine={engine}")
            row = {
                "case": name, "n": n, "backend": backend, "engine": engine,
                "pre_s": t_pre, "pre_it_s": t_pre_it,
                "pre_plan_s": t_pre_plan, "int_s": t_int,
                "btfi_pre_s": t_pre_btfi, "btfi_int_s": t_int_btfi,
                "speedup_total": total_b / total_f,
                "speedup_int": t_int_btfi / t_int, "rel_err": float(err),
            }
            if backend == "plan":
                t_upd, t_reb = _update_stats(tree, leaf_size, repeat)
                row["upd_s"] = t_upd
                row["upd_rebuild_s"] = t_reb
                row["upd_speedup"] = t_reb / t_upd
                emit(f"fig3/{name}/n{n}/plan_update", t_upd,
                     f"rebuild={t_reb*1e3:.1f}ms "
                     f"upd_speedup={t_reb/t_upd:.1f}x")
            rows.append(row)
    # the forest row exercises the fused plan path: skip it for host-only
    # runs (e.g. jax-free debugging) that asked for no jit backend at all
    if set(backends) & {"plan", "pallas", "forest", "ftfi"}:
        rows.append(_forest_row(rng, fn, repeat=repeat))
    return rows


def _forest_row(rng, fn, num_trees=90, repeat=2):
    """Forest row: one fused plan over a mixed-size forest vs the per-tree
    host loop (the baseline occupies the btfi_* columns)."""
    del rng  # dedicated stream: the row must not depend on which other
    rng = np.random.default_rng(90)  # cases ran (stable case/n for --baseline)
    trees = [random_tree(int(s), seed=i)
             for i, s in enumerate(rng.integers(24, 96, size=num_trees))]
    forest = Forest(trees)
    n = forest.num_vertices
    X = rng.normal(size=(n, 4))
    # baseline: per-tree host loop (no ExpMP: measure the IT walk, as above)
    loops = []
    mk_loop = lambda: loops.append([FTFI(t, leaf_size=64)
                                    for t in forest.trees])
    _clear_all()
    t_pre_loop = timeit(mk_loop, repeat=1, warmup=0)
    off = forest.offsets

    def loop():
        return np.concatenate([
            f.integrate(fn, X[off[i]:off[i + 1]])
            for i, f in enumerate(loops[-1])], axis=0)

    t_int_loop = timeit(loop, repeat=repeat)
    ref = loop()
    emit(f"fig3/forest{num_trees}/n{n}/loop_pre", t_pre_loop)
    emit(f"fig3/forest{num_trees}/n{n}/loop_int", t_int_loop)
    # fused forest plan, with the same cold pre_it / pre_plan split as the
    # single-tree rows: forest flat-IT build, then fused-plan assembly on
    # the warm IT cache
    mk_forest = lambda: ftfi.build(forest)
    t_pre_it = _cold(lambda: build_flat_forest(forest.trees, leaf_size=64),
                     rounds=repeat, clear=_clear_all)
    build_flat_forest(forest.trees, leaf_size=64)  # warm the IT cache
    t_pre_plan = _cold(mk_forest, rounds=repeat, clear=clear_plan_cache)
    t_pre = t_pre_it + t_pre_plan
    plan = mk_forest()
    engine = ftfi.describe(plan[0], fn)["cross_engine"]
    import jax

    fm = jax.jit(ftfi.fastmult(plan[0], fn))
    run_once = lambda: np.asarray(fm(plan[1], X))
    t_int = timeit(run_once, repeat=repeat, warmup=1)
    got = run_once()
    err = np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-9)
    total_f, total_b = t_pre + t_int, t_pre_loop + t_int_loop
    emit(f"fig3/forest{num_trees}/n{n}/forest_pre", t_pre,
         f"it={t_pre_it*1e3:.1f}ms plan={t_pre_plan*1e3:.1f}ms")
    emit(f"fig3/forest{num_trees}/n{n}/forest_int", t_int,
         f"speedup_total={total_b/total_f:.2f}x "
         f"speedup_int={t_int_loop/t_int:.2f}x relerr={err:.1e} "
         f"engine={engine}")
    return {
        "case": f"forest{num_trees}", "n": n, "backend": "forest",
        "engine": engine, "pre_s": t_pre, "pre_it_s": t_pre_it,
        "pre_plan_s": t_pre_plan, "int_s": t_int, "btfi_pre_s": t_pre_loop,
        "btfi_int_s": t_int_loop, "speedup_total": total_b / total_f,
        "speedup_int": t_int_loop / t_int, "rel_err": float(err),
    }


def run_sharding(sizes=(4000,), devices=(1, 2, 4, 8), repeat=3,
                 leaf_size=256):
    """Weak-scaling rows for the shard_map plan executor: one jitted
    integrate per device count on a 1-D data submesh over the first D
    visible devices, parity-checked against the single-device jitted plan
    executor. Rows carry a `devices` column plus the partition's
    halo/per-device-work stats (`check_bench --suite sharding` gates
    rel_err and the per-device work reduction). Device counts beyond
    `jax.device_count()` are skipped WITH a printed note — never silently
    (force 8 host devices via
    XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    import jax
    from jax.sharding import Mesh

    _jax_warmup()
    rng = np.random.default_rng(0)
    fn = Exponential(-0.5)
    avail = jax.device_count()
    rows = []
    for n in sizes:
        tree = minimum_spanning_tree(synthetic_graph(n, n // 2, seed=1))
        spec, pp = ftfi.build(tree, leaf_size=leaf_size)
        engine = ftfi.describe(spec, fn)["cross_engine"]
        X = rng.normal(size=(spec.n, 4)).astype(np.float32)
        fm1 = jax.jit(ftfi.fastmult(spec, fn))
        ref = np.asarray(fm1(pp, X))
        refmax = max(float(np.max(np.abs(ref))), 1e-9)
        t1 = None
        for D in devices:
            if D > avail:
                print(f"# sharding: devices={D} skipped — only {avail} "
                      "visible (set XLA_FLAGS="
                      "--xla_force_host_platform_device_count=8)")
                continue
            if D == 1:
                run_once = lambda: np.asarray(fm1(pp, X))
                stats = {"block": spec.n, "halo_width": 0, "halo_total": 0,
                         "src_rows": int(spec.src_gather.size),
                         "tgt_rows": int(spec.tgt_gather.size)}
            else:
                mesh = Mesh(np.asarray(jax.devices()[:D]).reshape(D),
                            ("data",))
                fms = jax.jit(ftfi.sharded_fastmult(spec, fn, mesh=mesh))
                run_once = lambda: np.asarray(fms(pp, X))
                stats = ftfi.shard_stats(spec, D)
            t_int = timeit(run_once, repeat=repeat, warmup=1)
            err = float(np.max(np.abs(run_once() - ref)) / refmax)
            if D == 1:
                t1 = t_int
            scaling = (t1 / t_int) if t1 else 1.0
            emit(f"sharding/synthetic/n{n}/d{D}_int", t_int,
                 f"scaling={scaling:.2f}x relerr={err:.1e} "
                 f"block={stats['block']} halo={stats['halo_total']}")
            rows.append({
                "case": "synthetic", "n": n, "backend": "sharded",
                "engine": engine, "devices": D, "int_s": t_int,
                "rel_err": err, "scaling": scaling,
                "block": int(stats["block"]),
                "halo_width": int(stats["halo_width"]),
                "halo_total": int(stats["halo_total"]),
                "device_rows": int(stats["src_rows"] + stats["tgt_rows"]),
                "global_rows": int(spec.src_gather.size
                                   + spec.tgt_gather.size),
            })
    return rows


def _merge_sharding_rows(path: str, rows: list) -> None:
    """Replace the sharded rows of an existing BENCH_ftfi_runtime.json (or
    start a fresh artifact) so `--devices` runs compose with the fig3 suite
    instead of clobbering it."""
    import json

    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        doc = {"suite": "fig3", "rows": []}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("backend") != "sharded"] + rows
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1)
    print(f"# wrote {len(rows)} sharded rows to {path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="host,plan,pallas,ftfi",
                    help="comma list of host,plan,pallas,ftfi (ftfi = the "
                         "functional plan API: jitted pure (params, X) -> Y)")
    ap.add_argument("--sizes", default="1000,4000")
    ap.add_argument("--mesh-subdiv", default="3")
    ap.add_argument("--repeat", type=int, default=2)
    ap.add_argument("--devices", default=None,
                    help="comma list of device counts (e.g. 1,2,4,8): run "
                         "ONLY the weak-scaling shard_map rows and merge "
                         "them into --json")
    ap.add_argument("--json", default="BENCH_ftfi_runtime.json",
                    help="artifact the --devices rows merge into")
    args = ap.parse_args()
    if args.devices:
        devices = tuple(int(s) for s in args.devices.split(",") if s)
        # force enough fake host devices BEFORE the jax backend initializes
        # (safe: nothing above touched a device; plain import does not)
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{max(devices)}").strip()
        print("name,us_per_call,derived")
        rows = run_sharding(
            sizes=tuple(int(s) for s in args.sizes.split(",") if s),
            devices=devices, repeat=args.repeat)
        _merge_sharding_rows(args.json, rows)
        return
    print("name,us_per_call,derived")
    run(sizes=tuple(int(s) for s in args.sizes.split(",") if s),
        mesh_subdiv=tuple(int(s) for s in args.mesh_subdiv.split(",") if s),
        repeat=args.repeat,
        backends=tuple(args.backend.split(",")))


if __name__ == "__main__":
    main()
