"""CI smoke gate over the benchmark JSON artifacts.

--suite ftfi (default) gates BENCH_ftfi_runtime.json + IT-build wall clock +
the fused forest plan. Fails (exit 1) when:
  * any exact-engine row reports rel_err > --max-rel-err (default 1e-4) —
    chebyshev rows are approximate by design and only get a loose sanity
    bound;
  * the flat IT build at n=2000 on path / star / caterpillar / synthetic-MST
    topologies exceeds --it-ceiling seconds (a deliberately generous bound:
    the vectorized builder runs in tens of milliseconds, so tripping it
    means the hot path got re-pythonized) or loses Lemma-3.1 balance;
  * the fused forest plan diverges from the per-tree host loop by more than
    --forest-rel-err (default 1e-5) on a small mixed-size forest.

  * a plan-backend row's cold plan assembly (pre_plan_s) exceeds its
    --plan-ceiling (vectorized assembly runs in single-digit milliseconds;
    tripping the ceiling means the per-node Python loop came back), or the
    incremental-update speedup (upd_speedup, warm `ftfi.update_plan` vs a
    cold reweightable recompile) falls under --upd-speedup;
  * the disk plan cache fails its live round-trip: a cold-process rebuild
    (memory caches cleared) with a populated FTFI_PLAN_CACHE directory must
    hit the cache, return a digest-identical plan, and stay under
    --cache-warm-ceiling seconds.

--suite topo gates BENCH_topo_attention.json: every topo_attn_impl row must
stay within --topo-rel-err (default 1e-3) of its exactness anchor, and the
fused impl must not be slower than the fft chunk-loop path it replaces.

--suite robustness runs the live fault matrix (no input JSON) and writes it
to --robustness-json (default BENCH_robustness.json). Fails when:
  * plan-guard validation of a warm n=4000 plan costs more than
    --guard-overhead (default 5%) of the warm-IT plan assembly time
    (pre_plan_s), with a small absolute floor against timer noise;
  * the degradation ladder's fallback output (pallas rung forced to fail)
    diverges from the host oracle by more than --ladder-rel-err (1e-5);
  * any fault-matrix row — corrupt artifact (truncated / bit-flipped),
    flipped index, NaN field, kernel raise, non-finite kernel output,
    post-write disk-cache corruption, serve slot/step crash — fails to
    recover or degrade to the host-exact output.

--suite serve gates BENCH_serve.json (written by bench_serve): every row
must carry a recorded p99 (end-to-end AND time-to-first-token) and pass the
fused-vs-replay greedy parity cross-check, and at every prompt length >=
--serve-gate-len (default 32) the fused engine's prompt-processing
throughput (prefill_tok_s) must be at least --serve-min-speedup x replay's
— tripping it means the fused prefill-into-cache path regressed to (or
below) token-by-token replay.

--suite sharding gates the weak-scaling rows bench_ftfi_runtime --devices
wrote into BENCH_ftfi_runtime.json: every sharded row's parity rel_err vs
the single-device jitted executor must stay under --sharding-rel-err
(default 1e-5), and every multi-device partition must reduce per-device
work (padded per-device gather length under --max-work-frac of the global
plan's flat entries).

  PYTHONPATH=src python -m benchmarks.check_bench BENCH_ftfi_runtime.json
  PYTHONPATH=src python -m benchmarks.check_bench --suite topo BENCH_topo_attention.json
  PYTHONPATH=src python -m benchmarks.check_bench --suite robustness
  PYTHONPATH=src python -m benchmarks.check_bench --suite sharding BENCH_ftfi_runtime.json
  PYTHONPATH=src python -m benchmarks.check_bench --suite serve BENCH_serve.json
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

APPROX_ENGINES = {"chebyshev"}
APPROX_REL_ERR = 1e-2


def check_json(path: str, max_rel_err: float) -> list[str]:
    with open(path) as fh:
        rows = json.load(fh)["rows"]
    errors = []
    if not rows:
        errors.append(f"{path}: no benchmark rows")
    for r in rows:
        bound = (APPROX_REL_ERR if r["engine"] in APPROX_ENGINES
                 else max_rel_err)
        if r["rel_err"] > bound:
            errors.append(
                f"{r['case']}/n{r['n']}/{r['backend']} ({r['engine']}): "
                f"rel_err {r['rel_err']:.2e} > {bound:.0e}")
    return errors


def check_it_build(n: int, ceiling: float) -> list[str]:
    import numpy as np  # noqa: F401  (env sanity before heavy imports)
    from repro.core import build_flat_it, clear_flat_cache, flat_stats
    from repro.graphs.graph import (caterpillar_tree, path_graph, star_tree,
                                    synthetic_graph)
    from repro.graphs.mst import minimum_spanning_tree

    cases = {
        "path": path_graph(n),
        "star": star_tree(n, seed=0),
        "caterpillar": caterpillar_tree(n, seed=0),
        "synthetic_mst": minimum_spanning_tree(
            synthetic_graph(n, n // 2, seed=1)),
    }
    errors = []
    for name, tree in cases.items():
        clear_flat_cache()
        t0 = time.perf_counter()
        flat = build_flat_it(tree, leaf_size=64)
        dt = time.perf_counter() - t0
        stats = flat_stats(flat)
        if dt > ceiling:
            errors.append(f"IT build {name} n={n}: {dt:.2f}s > {ceiling}s "
                          "ceiling (re-pythonized hot path?)")
        if not stats["balance_ok"]:
            errors.append(f"IT build {name} n={n}: balance_ok=False")
    return errors


def check_forest(max_rel_err: float) -> list[str]:
    """Forest smoke: the fused forest plan must equal the per-tree host loop
    on a small mixed-size forest, for an exact family AND a general f."""
    import numpy as np
    from repro import ftfi
    from repro.core import FTFI, AnyFn, Exponential, Forest
    from repro.graphs.graph import (caterpillar_tree, path_graph, random_tree,
                                    star_tree)

    rng = np.random.default_rng(0)
    trees = [random_tree(int(s), seed=i)
             for i, s in enumerate(rng.integers(8, 48, size=12))]
    trees += [path_graph(40), star_tree(30, seed=1),
              caterpillar_tree(36, seed=2)]
    forest = Forest(trees)
    X = rng.normal(size=(forest.num_vertices, 3))
    off = forest.offsets
    spec, params = ftfi.build(forest, leaf_size=16)
    errors = []
    for fn, label in ((Exponential(-0.6, 1.2), "exp"),
                      (AnyFn(lambda z: 1.0 / (1.0 + z)), "anyfn")):
        ref = np.concatenate([FTFI(t).integrate(fn, X[off[i]:off[i + 1]])
                              for i, t in enumerate(forest.trees)])
        got = np.asarray(ftfi.apply(spec, params, fn, X))
        err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        if err > max_rel_err:
            errors.append(
                f"forest plan vs per-tree loop ({label}): rel_err "
                f"{err:.2e} > {max_rel_err:.0e}")
    return errors


def check_cold_compile(path: str, plan_ceiling: float,
                       upd_speedup: float) -> list[str]:
    """Plan-compile latency gate over the benchmark JSON: cold vectorized
    assembly must stay in the milliseconds, and the incremental-update path
    must beat a cold recompile by a wide margin (it exists for nothing
    else). Rows without the update columns (non-plan backends, forest) are
    skipped."""
    with open(path) as fh:
        rows = json.load(fh)["rows"]
    errors = []
    for r in rows:
        if r["backend"] not in ("plan", "pallas", "ftfi", "forest"):
            continue
        if r["pre_plan_s"] > plan_ceiling:
            errors.append(
                f"{r['case']}/n{r['n']}/{r['backend']}: cold pre_plan_s "
                f"{r['pre_plan_s']*1e3:.1f}ms > {plan_ceiling*1e3:.0f}ms "
                "ceiling (plan assembly re-pythonized?)")
        if "upd_speedup" in r and r["upd_speedup"] < upd_speedup:
            errors.append(
                f"{r['case']}/n{r['n']}/{r['backend']}: upd_speedup "
                f"{r['upd_speedup']:.1f}x < {upd_speedup:.0f}x (incremental "
                "update no longer beats recompiling)")
    if not any("upd_speedup" in r for r in rows):
        errors.append(f"{path}: no rows carry upd_speedup — bench suite "
                      "predates the incremental-update columns; regenerate")
    return errors


def check_disk_cache(warm_ceiling: float) -> list[str]:
    """Live disk-cache round trip: populate a temp FTFI_PLAN_CACHE via one
    build, clear the in-memory caches (simulating a fresh process), and
    require the rebuild to hit the disk cache, match digests, and come back
    well under compile cost."""
    import tempfile

    import numpy as np  # noqa: F401
    from repro import ftfi
    from repro.core import clear_flat_cache, clear_plan_cache, plan_cache
    from repro.graphs.graph import synthetic_graph
    from repro.graphs.mst import minimum_spanning_tree

    tree = minimum_spanning_tree(synthetic_graph(2000, 1000, seed=1))
    errors = []
    with tempfile.TemporaryDirectory() as d:
        plan_cache.configure(d, max_mb=64)
        try:
            spec1, _ = ftfi.build(tree, leaf_size=64, reweightable=True)
            st = plan_cache.stats()
            if st["stores"] < 1 or st["entries"] < 1:
                errors.append(f"disk cache: build did not populate the "
                              f"cache dir ({st})")
            clear_flat_cache()
            clear_plan_cache()
            t0 = time.perf_counter()
            spec2, _ = ftfi.build(tree, leaf_size=64, reweightable=True)
            dt = time.perf_counter() - t0
            st = plan_cache.stats()
            if st["hits"] < 1:
                errors.append(f"disk cache: cold-process rebuild missed "
                              f"the populated cache ({st})")
            if spec1.digest != spec2.digest:
                errors.append("disk cache: cached plan digest differs from "
                              "the freshly compiled one")
            if dt > warm_ceiling:
                errors.append(
                    f"disk cache: warm rebuild took {dt:.2f}s > "
                    f"{warm_ceiling}s ceiling (cache load slower than "
                    "recompiling?)")
        finally:
            plan_cache.reset_to_env()
            clear_flat_cache()
            clear_plan_cache()
    return errors


def check_sharding_json(path: str, max_rel_err: float,
                        max_work_frac: float) -> list[str]:
    """Weak-scaling gate over the sharded rows of BENCH_ftfi_runtime.json
    (`bench_ftfi_runtime --devices 1,2,4,8`): every row's parity rel_err
    against the single-device jitted executor must stay under
    --sharding-rel-err, and every multi-device partition must actually
    reduce per-device work — the padded per-device gather length under
    --max-work-frac of the global plan's flat entries."""
    with open(path) as fh:
        rows = json.load(fh)["rows"]
    rows = [r for r in rows if r.get("backend") == "sharded"]
    errors = []
    if not rows:
        errors.append(f"{path}: no sharded rows — run "
                      "bench_ftfi_runtime --devices 1,2,4,8 first")
    if not any(r["devices"] > 1 for r in rows):
        errors.append(f"{path}: sharded rows cover only 1 device — the "
                      "weak-scaling sweep did not run (too few visible "
                      "devices?)")
    for r in rows:
        where = f"{r['case']}/n{r['n']}/devices{r['devices']}"
        if r["rel_err"] > max_rel_err:
            errors.append(f"{where}: sharded parity rel_err "
                          f"{r['rel_err']:.2e} > {max_rel_err:.0e}")
        if r["devices"] > 1:
            frac = r["device_rows"] / max(r["global_rows"], 1)
            if frac > max_work_frac:
                errors.append(
                    f"{where}: per-device work {r['device_rows']} rows is "
                    f"{frac:.0%} of the global plan ({r['global_rows']}) > "
                    f"{max_work_frac:.0%} — the partition is not reducing "
                    "work")
            n_pad = r["block"] * r["devices"]
            if n_pad < r["n"]:
                errors.append(f"{where}: block {r['block']} x {r['devices']}"
                              f" devices < n={r['n']} (vertices dropped)")
    return errors


def check_topo_json(path: str, max_rel_err: float) -> list[str]:
    """Topo-attention impl parity gate: every impl row within max_rel_err of
    its anchor, and the fused impl at least as fast as the fft chunk-loop."""
    with open(path) as fh:
        rows = json.load(fh)["rows"]
    errors = []
    if not rows:
        errors.append(f"{path}: no benchmark rows")
    for r in rows:
        if r["rel_err"] > max_rel_err:
            errors.append(
                f"{r['case']}/L{r['L']}/{r['impl']}: rel_err "
                f"{r['rel_err']:.2e} > {max_rel_err:.0e}")
        if r["impl"] == "pallas" and r["speedup_vs_fft"] < 1.0:
            errors.append(
                f"{r['case']}/L{r['L']}/pallas: fused path slower than the "
                f"fft chunk-loop ({r['speedup_vs_fft']:.2f}x)")
    return errors


def check_serve_json(path: str, gate_len: int,
                     min_speedup: float) -> list[str]:
    """Serving gate over bench_serve rows: latency percentiles recorded,
    fused==replay greedy parity, and fused prompt throughput >= min_speedup
    x replay's at every prompt length >= gate_len."""
    with open(path) as fh:
        rows = json.load(fh)["rows"]
    errors = []
    if not rows:
        errors.append(f"{path}: no benchmark rows")
    by = {}
    for r in rows:
        where = f"{r['mode']}/pl{r['prompt_len']}"
        by[(r["mode"], r["prompt_len"])] = r
        for k in ("p99_ms", "ttft_p99_ms", "p50_ms", "ttft_p50_ms"):
            if r.get(k) is None:
                errors.append(f"{where}: {k} not recorded")
        if not r.get("parity_ok", False):
            errors.append(f"{where}: fused-vs-replay greedy parity failed "
                          "(or was not cross-checked)")
        if r.get("failed", 0):
            errors.append(f"{where}: {r['failed']} requests failed")
    gated = [pl for (m, pl) in by if m == "fused" and pl >= gate_len
             and ("replay", pl) in by]
    if not gated:
        errors.append(f"{path}: no fused/replay pair at prompt_len >= "
                      f"{gate_len} — the throughput gate did not run")
    for pl in gated:
        f, rp = by[("fused", pl)], by[("replay", pl)]
        if f["prefill_tok_s"] < min_speedup * rp["prefill_tok_s"]:
            errors.append(
                f"fused/pl{pl}: prefill {f['prefill_tok_s']:.0f} tok/s < "
                f"{min_speedup:.1f}x replay's {rp['prefill_tok_s']:.0f} "
                "tok/s (fused prefill-into-cache regressed to replay "
                "speed)")
    return errors


def check_robustness(out_path: str, guard_overhead: float,
                     ladder_rel_err: float) -> list[str]:
    """Live robustness gate + fault-matrix artifact. Every row must either
    recover (retry reproduces the answer) or degrade to the host-exact
    output; the artifact records what happened for each fault class."""
    import tempfile
    import warnings

    import numpy as np
    from repro import ftfi
    from repro.core import clear_flat_cache, clear_plan_cache
    from repro.core import cordial as C
    from repro.core import ladder, plan_cache, plan_guard
    from repro.core.itree_flat import build_flat_it
    from repro.core.plan_guard import PlanValidationError
    from repro.graphs.graph import synthetic_graph
    from repro.graphs.mst import minimum_spanning_tree
    from repro.testing import faults

    errors: list[str] = []
    rows: list[dict] = []

    def row(fault: str, recovered: bool, outcome: str,
            rel_err: float | None = None, **extra) -> None:
        rows.append({"fault": fault, "recovered": bool(recovered),
                     "outcome": outcome, "rel_err": rel_err, **extra})
        if not recovered:
            errors.append(f"robustness matrix: {fault}: {outcome}")

    # -- validation overhead vs warm plan assembly (the pre_plan_s analogue:
    # cold plan assembly on a warm flat-IT cache, min over rounds)
    tree = minimum_spanning_tree(synthetic_graph(4000, 2000, seed=1))
    build_flat_it(tree, leaf_size=256)
    t_plan = float("inf")
    for _ in range(3):
        clear_plan_cache()
        t0 = time.perf_counter()
        ftfi.build(tree, leaf_size=256)
        t_plan = min(t_plan, time.perf_counter() - t0)
    spec, params = ftfi.build(tree, leaf_size=256)
    t_val = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        issues = plan_guard.check_spec(spec, params)
        t_val = min(t_val, time.perf_counter() - t0)
    if issues:
        errors.append(f"robustness: healthy n=4000 plan failed validation: "
                      f"{issues[:3]}")
    budget = max(guard_overhead * t_plan, 2e-3)  # 2ms timer-noise floor
    if t_val > budget:
        errors.append(
            f"robustness: plan-guard validation {t_val*1e3:.2f}ms > "
            f"{guard_overhead:.0%} of warm pre_plan_s "
            f"({t_plan*1e3:.2f}ms)")
    rows.append({"fault": "none (overhead)", "recovered": t_val <= budget,
                 "outcome": f"validation {t_val*1e3:.3f}ms on warm "
                            f"pre_plan_s {t_plan*1e3:.2f}ms",
                 "rel_err": None, "validate_s": t_val, "pre_plan_s": t_plan})

    fn = C.Exponential(-0.5)
    X = np.random.default_rng(0).normal(size=(spec.n, 4)).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        ref = np.asarray(ftfi.apply(spec, params, fn, X, backend="host"))

        # -- ladder: forced kernel raise -> fallback parity vs host oracle
        ladder.reset_stats()
        with faults.injected("ladder.pallas", faults.always_raise(
                RuntimeError, "injected kernel launch failure")):
            got = np.asarray(ftfi.apply_resilient(spec, params, fn, X,
                                                  backend="pallas"))
        err = float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)),
                                                    1e-12))
        st = ladder.stats()
        ok = err <= ladder_rel_err and st["demotions"] >= 1
        row("kernel raise (ladder.pallas)", ok,
            f"demoted {st['demotions']}x, rel_err {err:.1e} vs host",
            rel_err=err)
        if err > ladder_rel_err:
            errors.append(f"robustness: ladder fallback rel_err {err:.2e} > "
                          f"{ladder_rel_err:.0e} vs host oracle")

        # -- ladder: non-finite kernel output -> demotes through to parity
        with faults.injected("ladder.out.pallas", faults.nan_output()):
            got = np.asarray(ftfi.apply_resilient(spec, params, fn, X,
                                                  backend="pallas"))
        err = float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)),
                                                    1e-12))
        row("non-finite kernel output (ladder.out.pallas)",
            err <= ladder_rel_err,
            f"rel_err {err:.1e} vs host after demotion", rel_err=err)

    # -- corrupt artifact: truncated / bit-flipped npz must be rejected
    with tempfile.TemporaryDirectory() as d:
        p = pathlib.Path(d) / "plan.npz"
        ftfi.save_plan(p, spec, params)
        blob = p.read_bytes()
        for fault, corrupt in (
                ("truncated artifact",
                 lambda: faults.corrupt_file(p, truncate_to=len(blob) // 2)),
                ("bit-flipped artifact",
                 lambda: faults.corrupt_file(p, flip_bytes=64, seed=7))):
            p.write_bytes(blob)
            corrupt()
            try:
                ftfi.load_plan(p)
                row(fault, False, "load_plan accepted a damaged artifact")
            except PlanValidationError as e:
                row(fault, True, f"rejected: {str(e)[:80]}")
            except Exception as e:  # anything else is an unhandled leak
                row(fault, False,
                    f"unstructured {type(e).__name__}: {str(e)[:80]}")

    # -- flipped index / NaN field caught by the guard before dispatch
    bad = faults.flip_index(spec, field="src_gather")
    try:
        ftfi.validate(bad, params)
        row("flipped index (src_gather)", False, "guard missed OOB index")
    except PlanValidationError as e:
        row("flipped index (src_gather)", True, f"rejected: {str(e)[:80]}")
    import dataclasses
    dists = list(params.cross_src_d)
    if dists:
        d0 = np.array(dists[0], copy=True)
        d0.reshape(-1)[:1] = np.nan
        nan_params = dataclasses.replace(
            params, cross_src_d=(d0,) + tuple(dists[1:]))
        try:
            ftfi.validate(spec, nan_params)
            row("NaN field (cross_src_d)", False, "guard missed NaN params")
        except PlanValidationError as e:
            row("NaN field (cross_src_d)", True, f"rejected: {str(e)[:80]}")

    # -- disk cache post-write corruption: strict reject -> rebuild
    with tempfile.TemporaryDirectory() as d:
        plan_cache.configure(d, max_mb=64)
        try:
            clear_flat_cache()
            clear_plan_cache()
            ftfi.build(tree, leaf_size=64)
            [artifact] = list(pathlib.Path(d).glob("ftfi-plan-*.npz"))
            faults.corrupt_file(artifact, flip_bytes=48, seed=3)
            clear_flat_cache()
            clear_plan_cache()
            before = plan_cache.stats()
            spec2, pp2 = ftfi.build(tree, leaf_size=64)
            after = plan_cache.stats()
            ok = (after["misses"] > before["misses"]
                  and after["errors"] > before["errors"]
                  and plan_guard.check_spec(spec2, pp2) == [])
            row("disk-cache post-write corruption", ok,
                f"hit rejected -> rebuilt (errors +"
                f"{after['errors'] - before['errors']})")
        except Exception as e:
            row("disk-cache post-write corruption", False,
                f"unhandled {type(e).__name__}: {str(e)[:80]}")
        finally:
            plan_cache.reset_to_env()
            clear_flat_cache()
            clear_plan_cache()

    # -- serving: slot crash at tick k and a whole-step crash must both
    # complete every request with retries recorded, zero exceptions
    try:
        import jax
        from repro.configs.base import get_smoke_config
        from repro.models import api
        from repro.serve.engine import Request, ServeEngine

        cfg = get_smoke_config("qwen2_1_5b").replace(dtype="float32")
        mp = api.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=k).tolist()
                   for k in (3, 5)]
        for fault, point, handler in (
                ("serve slot crash (NaN logits row @ tick 2)",
                 "serve.logits", faults.nan_slot_at_tick(slot=1, k=2)),
                ("serve step crash (raise @ tick 3)",
                 "serve.step", faults.raise_at_tick(3))):
            eng = ServeEngine(cfg, mp, batch_slots=2, max_len=64)
            reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                eng.submit(r)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with faults.injected(point, handler):
                    eng.run()
            st = eng.stats()
            ok = (all(r.done and r.error is None for r in reqs)
                  and st["retries"] >= 1 and st["failed"] == 0)
            row(fault, ok,
                f"completed={st['completed']} retries={st['retries']} "
                f"evictions={st['evictions']}", engine_stats={
                    k: st[k] for k in ("completed", "failed", "retries",
                                       "evictions", "step_failures",
                                       "slot_faults")})
    except Exception as e:
        row("serve fault rows", False,
            f"unhandled {type(e).__name__}: {str(e)[:120]}")

    with open(out_path, "w") as fh:
        json.dump({"suite": "robustness", "rows": rows}, fh, indent=2)
    print(f"wrote {out_path} ({len(rows)} fault-matrix rows)")
    return errors


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("json", nargs="?", default="BENCH_ftfi_runtime.json")
    ap.add_argument("--suite",
                    choices=("ftfi", "topo", "robustness", "sharding",
                             "serve"),
                    default="ftfi")
    ap.add_argument("--max-rel-err", type=float, default=1e-4)
    ap.add_argument("--it-n", type=int, default=2000)
    ap.add_argument("--it-ceiling", type=float, default=5.0)
    ap.add_argument("--forest-rel-err", type=float, default=1e-5)
    ap.add_argument("--topo-rel-err", type=float, default=1e-3)
    ap.add_argument("--plan-ceiling", type=float, default=0.25,
                    help="max cold pre_plan_s for jit-backend rows (s); "
                    "generous vs the ~ms vectorized assembly, trips when "
                    "the per-node Python loop comes back")
    ap.add_argument("--upd-speedup", type=float, default=5.0,
                    help="min upd_speedup (warm update_plan vs cold "
                    "reweightable recompile) on rows that carry it")
    ap.add_argument("--cache-warm-ceiling", type=float, default=2.0,
                    help="max seconds for a cold-process rebuild served "
                    "from a populated disk plan cache")
    ap.add_argument("--guard-overhead", type=float, default=0.05,
                    help="max plan-guard validation time as a fraction of "
                    "the warm-IT plan assembly time (pre_plan_s)")
    ap.add_argument("--ladder-rel-err", type=float, default=1e-5,
                    help="max rel_err of a ladder fallback output vs the "
                    "host oracle")
    ap.add_argument("--robustness-json", default="BENCH_robustness.json",
                    help="fault-matrix artifact written by "
                    "--suite robustness")
    ap.add_argument("--sharding-rel-err", type=float, default=1e-5,
                    help="max parity rel_err of a sharded row vs the "
                    "single-device jitted executor")
    ap.add_argument("--max-work-frac", type=float, default=0.75,
                    help="max per-device flat work as a fraction of the "
                    "global plan on multi-device sharded rows")
    ap.add_argument("--serve-gate-len", type=int, default=32,
                    help="prompt length from which fused prefill must beat "
                    "replay throughput (--suite serve)")
    ap.add_argument("--serve-min-speedup", type=float, default=1.0,
                    help="min fused/replay prefill tok/s ratio at gated "
                    "prompt lengths (--suite serve)")
    args = ap.parse_args()

    if args.suite == "serve":
        errors = check_serve_json(args.json, args.serve_gate_len,
                                  args.serve_min_speedup)
    elif args.suite == "robustness":
        errors = check_robustness(args.robustness_json, args.guard_overhead,
                                  args.ladder_rel_err)
    elif args.suite == "sharding":
        errors = check_sharding_json(args.json, args.sharding_rel_err,
                                     args.max_work_frac)
    elif args.suite == "topo":
        errors = check_topo_json(args.json, args.topo_rel_err)
    else:
        errors = check_json(args.json, args.max_rel_err)
        errors += check_it_build(args.it_n, args.it_ceiling)
        errors += check_forest(args.forest_rel_err)
        errors += check_cold_compile(args.json, args.plan_ceiling,
                                     args.upd_speedup)
        errors += check_disk_cache(args.cache_warm_ceiling)
    if errors:
        for e in errors:
            print(f"GATE FAIL: {e}", file=sys.stderr)
        sys.exit(1)
    print("perf smoke gate: OK")


if __name__ == "__main__":
    main()
