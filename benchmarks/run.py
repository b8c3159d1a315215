"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines; the fig3 suite additionally
writes BENCH_ftfi_runtime.json, the fig5 suite writes
BENCH_graph_classification.json, the fig6 suite writes
BENCH_learnable_f.json (incl. the ftfi.reweight --train-edges rows) and the
tab1 suite writes BENCH_topo_attention.json so the perf trajectory
accumulates across PRs.

  python -m benchmarks.run [--quick] [--only fig3,fig4,...]
          [--backend host,plan,pallas] [--baseline prev_BENCH.json]
"""
import argparse
import json
import sys
import traceback


def _load_baseline(baseline_path):
    """Read the baseline rows up front — BENCH_ftfi_runtime.json is a valid
    baseline path, and fig3 overwrites it before the deltas print."""
    try:
        with open(baseline_path) as fh:
            return json.load(fh)["rows"]
    except (OSError, KeyError, json.JSONDecodeError) as e:
        print(f"# --baseline: cannot read {baseline_path}: {e}",
              file=sys.stderr)
        return None


def _print_baseline_deltas(rows, base_rows, baseline_path):
    """Per-row deltas of the fig3 suite against a previous
    BENCH_ftfi_runtime.json (rows matched by case/n/backend)."""
    base = {(r["case"], r["n"], r["backend"]): r for r in base_rows}
    print(f"# deltas vs {baseline_path} (negative = faster now)")
    print("case,n,backend,pre_s_old,pre_s_new,pre_x,int_s_old,int_s_new,"
          "int_x,speedup_total_old,speedup_total_new")
    for r in rows:
        b = base.get((r["case"], r["n"], r["backend"]))
        if b is None:
            print(f"{r['case']},{r['n']},{r['backend']},<no baseline row>")
            continue
        pre_x = b["pre_s"] / max(r["pre_s"], 1e-12)
        int_x = b["int_s"] / max(r["int_s"], 1e-12)
        print(f"{r['case']},{r['n']},{r['backend']},"
              f"{b['pre_s']:.4f},{r['pre_s']:.4f},{pre_x:.2f}x,"
              f"{b['int_s']:.5f},{r['int_s']:.5f},{int_x:.2f}x,"
              f"{b['speedup_total']:.2f},{r['speedup_total']:.2f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes for CI-speed runs")
    ap.add_argument("--only", default=None)
    ap.add_argument("--backend", default="host",
                    help="comma list of backends (host,plan,pallas,ftfi) for "
                         "fig3/tab1")
    ap.add_argument("--fig5-backend", default="host,forest",
                    help="comma list of host,plan,pallas,forest for the "
                         "graph-classification suite (plan/pallas are "
                         "per-graph jit loops: slow by design)")
    ap.add_argument("--baseline", default=None,
                    help="previous BENCH_ftfi_runtime.json to diff fig3 "
                         "rows against")
    args = ap.parse_args()
    from repro.launch import compile_cache

    compile_cache.configure()
    backends = tuple(args.backend.split(","))
    baseline_rows = _load_baseline(args.baseline) if args.baseline else None

    from benchmarks import (bench_ftfi_runtime, bench_graph_classification,
                            bench_gw, bench_learnable_f,
                            bench_mesh_interpolation, bench_roofline,
                            bench_topo_attention)

    suites = {
        "fig3": lambda: bench_ftfi_runtime.run(
            sizes=(1000, 4000) if args.quick else (1000, 4000, 10000, 20000),
            mesh_subdiv=(3,) if args.quick else (3, 4),
            backends=backends),
        "fig4": lambda: bench_mesh_interpolation.run(),
        "fig5": lambda: bench_graph_classification.run(
            n_per_class=15 if args.quick else 30,
            backends=tuple(b for b in args.fig5_backend.split(",") if b),
            repeat=3 if args.quick else 6),
        "fig6": lambda: bench_learnable_f.run(
            steps=150 if args.quick else 300, train_edges=True),
        "tab1": lambda: bench_topo_attention.run(
            backends=tuple(b for b in backends if b != "host") or ("plan",),
            quick=args.quick),
        "fig10": lambda: bench_gw.run(n=800 if args.quick else 5000),
        "roofline": lambda: bench_roofline.run(),
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if name not in only:
            continue
        try:
            result = fn()
            if name == "fig3":
                with open("BENCH_ftfi_runtime.json", "w") as fh:
                    json.dump({"suite": "fig3", "rows": result}, fh, indent=1)
                if baseline_rows is not None:
                    _print_baseline_deltas(result, baseline_rows,
                                           args.baseline)
            elif name == "fig5":
                with open("BENCH_graph_classification.json", "w") as fh:
                    json.dump({"suite": "fig5", "rows": result}, fh, indent=1)
            elif name == "fig6":
                with open("BENCH_learnable_f.json", "w") as fh:
                    json.dump({"suite": "fig6", "rows": result}, fh, indent=1)
            elif name == "tab1":
                with open("BENCH_topo_attention.json", "w") as fh:
                    json.dump({"suite": "tab1", "rows": result}, fh, indent=1)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
