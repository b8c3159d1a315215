"""Assemble the full static-analysis run: audits + lint + trace guard,
diffed against ``ANALYSIS_BUDGETS.json``.

Used by the CLI (``python -m repro.analysis``) and by tests — both consume
the same ``run_*`` functions so the CI gate and the test suite can't
drift.
"""
from __future__ import annotations

import json
import os
from pathlib import Path

BUDGETS_FILENAME = "ANALYSIS_BUDGETS.json"


def find_budgets_path(explicit: str | None = None) -> Path:
    if explicit:
        return Path(explicit)
    env = os.environ.get("ANALYSIS_BUDGETS")
    if env:
        return Path(env)
    here = Path.cwd()
    for d in (here, *here.parents):
        cand = d / BUDGETS_FILENAME
        if cand.exists():
            return cand
    # package-relative fallback: src/repro/analysis -> repo root
    return Path(__file__).resolve().parents[3] / BUDGETS_FILENAME


def load_budgets(path: str | None = None) -> dict:
    p = find_budgets_path(path)
    with open(p) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# audits
# ---------------------------------------------------------------------------


def run_audits(budgets: dict, names: list[str] | None = None,
               sections: list[str] | None = None) -> dict:
    """Trace + audit every registered entry point (or the named subset).

    Returns ``{"reports": [...], "skipped": [...], "issues": [...]}`` where
    each report is an ``AuditReport.to_dict()``.  An entry name present in
    the registry but missing from the budgets file is itself an issue —
    budgets must cover every registered surface.
    """
    from repro.analysis import entry_points, jaxpr_audit

    entry_budgets = budgets.get("entry_points", {})
    todo = list(entry_points.REGISTRY.values())
    if sections:
        todo = [e for e in todo if e.section in sections]
    if names:
        todo = [e for e in todo if e.name in names]
        missing = set(names) - {e.name for e in todo}
        if missing:
            raise KeyError(f"unknown entry point(s): {sorted(missing)}; "
                           f"known: {sorted(entry_points.REGISTRY)}")

    reports, skipped, issues = [], [], []
    for ep in todo:
        if ep.name not in entry_budgets:
            issues.append(f"audit: no budget declared for registered entry "
                          f"point '{ep.name}' in {BUDGETS_FILENAME}")
            continue
        try:
            fn, args = ep.build()
        except entry_points.SkipEntry as e:
            skipped.append({"name": ep.name, "reason": str(e)})
            continue
        rep = jaxpr_audit.audit(fn, *args, name=ep.name,
                                budget=entry_budgets[ep.name])
        reports.append(rep.to_dict())
        issues.extend(f"audit[{ep.name}]: {f['kind']} at {f['where']}: "
                      f"{f['detail']}" for f in rep.to_dict()["findings"])
    return {"reports": reports, "skipped": skipped, "issues": issues}


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------


def run_lint(paths: list[str] | None = None) -> dict:
    from repro.analysis import lint

    if not paths:
        root = find_budgets_path().parent
        paths = [str(root / "src")]
    errors = lint.check_paths(paths)
    return {"paths": [str(p) for p in paths],
            "issues": [str(e) for e in errors]}


# ---------------------------------------------------------------------------
# trace guard workload
# ---------------------------------------------------------------------------


def run_trace_guard(budgets: dict) -> dict:
    """Exercise every memoized jit-closure layer twice and assert the
    second pass is compile-free, then diff total compile counts against the
    ``trace_guard`` budget section."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.ftfi as ftfi
    from repro.analysis import trace_guard as tg
    from repro.core import cordial as C
    from repro.core import masks
    from repro.graphs.graph import random_tree

    tg.reset()
    issues: list[str] = []
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((64, 2)), jnp.float32)

    def stable(*sites, max_compiles=0):
        return tg.expect_stable(*sites, max_compiles=max_compiles)

    # 1. functional fastmult under an outer jit
    tree = random_tree(64, seed=0)
    spec, params = ftfi.build(tree)
    fm = jax.jit(ftfi.fastmult(spec, C.Exponential(-0.5)))
    fm(params, X)
    try:
        with stable("ftfi.fastmult"):
            fm(params, X)
    except tg.RetraceError as e:
        issues.append(f"trace_guard[ftfi-fastmult]: {e}")

    # 2. mask-closure LRU (serving / eval rebuild path) over the pair, on
    # the plan executor, whose compiles count under `masks.tree_fastmult`:
    # the 64-vertex tree would take the dense path, which compiles no
    # executor for the counters to watch
    coeffs = np.asarray([1.0, -0.5], np.float32)
    F = jnp.asarray(rng.standard_normal((2, 64, 3)), jnp.float32)
    n_dense, masks.N_DENSE = masks.N_DENSE, 0
    try:
        mfm = masks.make_tree_fastmult((spec, params), "exp", coeffs, 1.0)
        mfm(F)  # new f family -> exactly one compile
        hits0 = tg.compiles("masks.tree_fastmult:hit")
        with stable("masks.tree_fastmult", "ftfi.fastmult"):
            masks.make_tree_fastmult((spec, params), "exp", coeffs, 1.0)(F)
            mfm(F)
            mfm(F)
    except tg.RetraceError as e:
        issues.append(f"trace_guard[mask-memo]: {e}")
    finally:
        masks.N_DENSE = n_dense
    if tg.compiles("masks.tree_fastmult:hit") <= hits0:
        issues.append("trace_guard[mask-memo]: rebuilding an identical mask "
                      "closure missed the _TREE_FM_CACHE")

    # 3. serve decode / prefill buckets
    try:
        from repro.configs.base import get_smoke_config
        from repro.models import api
        from repro.serve.engine import ServeEngine

        cfg = get_smoke_config("llama3_2_1b").replace(dtype="float32")
        sparams = api.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, sparams, batch_slots=2, max_len=32)
        tok = jnp.zeros((2, 1), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        _, _ = eng._decode(sparams, eng.cache, tok, pos)
        toks = jnp.zeros((2, 8), jnp.int32)
        lengths = jnp.asarray([8, 5], jnp.int32)
        eng._prefill(sparams, eng.cache, toks, lengths)
        with stable("serve.decode", "serve.prefill"):
            eng._decode(sparams, eng.cache, tok, pos)
            eng._prefill(sparams, eng.cache, toks, lengths)
        with stable("serve.prefill", max_compiles=1):
            # a new pow2 bucket is ONE new compile, then stable
            big = jnp.zeros((2, 16), jnp.int32)
            eng._prefill(sparams, eng.cache, big, lengths)
            eng._prefill(sparams, eng.cache, big, lengths)
    except tg.RetraceError as e:
        issues.append(f"trace_guard[serve-buckets]: {e}")

    issues.extend(tg.check(budgets.get("trace_guard")))
    return {"stats": tg.stats(), "issues": issues}


# ---------------------------------------------------------------------------
# the full run
# ---------------------------------------------------------------------------


def run_all(budgets_path: str | None = None,
            lint_paths: list[str] | None = None,
            names: list[str] | None = None,
            sections: list[str] | None = None,
            do_audit: bool = True, do_lint: bool = True,
            do_trace: bool = True) -> dict:
    budgets = load_budgets(budgets_path)
    out: dict = {"budgets_file": str(find_budgets_path(budgets_path)),
                 "issues": []}
    if do_audit:
        out["audit"] = run_audits(budgets, names=names, sections=sections)
        out["issues"] += out["audit"]["issues"]
    if do_lint:
        out["lint"] = run_lint(lint_paths)
        out["issues"] += out["lint"]["issues"]
    if do_trace:
        out["trace_guard"] = run_trace_guard(budgets)
        out["issues"] += out["trace_guard"]["issues"]
    out["ok"] = not out["issues"]
    return out
