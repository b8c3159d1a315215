"""The analyzer analyzed: every pass must flag its synthetic known-bad
program and pass every registered clean entry point.

Five violation fixtures (the acceptance matrix):
  1. hidden all_gather on a shard_map path      -> jaxpr_audit collective
  2. f64 constant / f64 compute                 -> jaxpr_audit wide_dtype
  3. ~12 MB float array baked into the trace    -> jaxpr_audit big_const
  4. int64 PlanSpec index array                 -> plan_guard dtype check
  5. retracing closure on a stable entry point  -> trace_guard RetraceError
plus the AST lint's frozen-field mutation (and friends).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.ftfi as ftfi
from repro.analysis import jaxpr_audit, lint, trace_guard
from repro.analysis import entry_points, runner
from repro.core import cordial as C
from repro.core import plan_guard
from repro.graphs.graph import random_tree
from repro.launch.mesh import make_mesh


def _kinds(rep):
    return {f.kind for f in rep.findings}


# ---------------------------------------------------------------------------
# fixture 1: hidden collective
# ---------------------------------------------------------------------------


def test_hidden_all_gather_flagged():
    """An all_gather smuggled into a shard_map body is a structured
    collective finding naming the primitive — even on a 1-device mesh,
    where the string would also appear but wall-clock tests never notice."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((1,), ("i",))

    def fwd(x):
        def body(xs):
            return jax.lax.all_gather(xs, "i", tiled=True)

        return shard_map(body, mesh=mesh, in_specs=P("i"), out_specs=P(),
                         check_vma=False)(x)

    rep = jaxpr_audit.audit(fwd, jnp.ones((8, 2)), name="bad.allgather",
                            budget={"collectives": {}})
    assert not rep.ok
    assert "collective" in _kinds(rep)
    assert any("all_gather" in f.detail for f in rep.findings), rep.summary()
    # the declared-budget path: the same program is CLEAN if the gather is
    # budgeted, so intentional collectives never fight the gate
    rep2 = jaxpr_audit.audit(fwd, jnp.ones((8, 2)), name="ok.allgather",
                             budget={"collectives": {"all_gather": 1}})
    assert rep2.ok, rep2.summary()


def test_wrong_collective_count_flagged():
    """A second psum where the budget declares one is a count mismatch, not
    a pass — exact census, both directions."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((1,), ("i",))

    def fwd(x):
        def body(xs):
            a = jax.lax.psum(xs, "i")
            return a + jax.lax.psum(xs * 2, "i")

        return shard_map(body, mesh=mesh, in_specs=P("i"), out_specs=P("i"))(x)

    rep = jaxpr_audit.audit(fwd, jnp.ones((8,)), name="bad.count",
                            budget={"collectives": {"psum": 1}})
    assert not rep.ok and "collective" in _kinds(rep), rep.summary()


# ---------------------------------------------------------------------------
# fixture 2: f64 leak
# ---------------------------------------------------------------------------


def test_f64_leak_flagged():
    """Under x64, a float64 constant (and the f64 compute it forces) is a
    wide_dtype finding; the same program audits clean in f32."""
    with jax.enable_x64(True):
        big = jnp.asarray(np.random.default_rng(0).standard_normal((4, 64)))
        assert big.dtype == jnp.float64

        def fwd(x):
            return (x @ big.T).sum()

        rep = jaxpr_audit.audit(fwd, jnp.ones((2, 64), jnp.float64),
                                name="bad.f64", budget={})
    assert not rep.ok
    assert "wide_dtype" in _kinds(rep), rep.summary()
    assert any("float64" in f.detail for f in rep.findings)


def test_int64_compute_flagged():
    with jax.enable_x64(True):
        def fwd(x):
            return x.astype(jnp.int64) + 1

        rep = jaxpr_audit.audit(fwd, jnp.ones((4,), jnp.int32),
                                name="bad.i64", budget={})
    assert not rep.ok and "wide_dtype" in _kinds(rep), rep.summary()


# ---------------------------------------------------------------------------
# fixture 3: weights traced as constants
# ---------------------------------------------------------------------------


def test_captured_big_array_flagged():
    """A ~12 MB float array riding the closure (instead of the arg list) is
    the classic silent retrace/memory bug; the report names the size."""
    W = jnp.asarray(np.zeros((3_000_000,), np.float32))  # 12 MB

    def fwd(x):
        return x * W.sum()

    rep = jaxpr_audit.audit(fwd, jnp.ones((4,)), name="bad.const", budget={})
    assert not rep.ok
    assert "big_const" in _kinds(rep), rep.summary()
    assert any("12000000" in f.detail for f in rep.findings), rep.summary()
    # int32 plan index arrays of the same size are NOT the weights bug:
    # only the float-const gate fires at this threshold
    idx = jnp.asarray(np.zeros((3_000_000,), np.int32))
    rep2 = jaxpr_audit.audit(lambda x: x * idx.sum(), jnp.ones((4,), jnp.int32),
                             name="ok.idxconst", budget={})
    assert rep2.ok, rep2.summary()


def test_callback_flagged():
    def fwd(x):
        jax.debug.print("x={}", x)
        return x + 1

    rep = jaxpr_audit.audit(fwd, jnp.ones((2,)), name="bad.debug", budget={})
    assert not rep.ok and "callback" in _kinds(rep), rep.summary()


# ---------------------------------------------------------------------------
# fixture 4: int64 index arrays (the day-one violation, now fixed)
# ---------------------------------------------------------------------------


def test_plan_spec_index_arrays_are_int32():
    """Freshly built plans (incl. the update/reweight tables) carry int32
    indices end-to-end — the auditor's day-one finding, fixed at source."""
    spec, params = ftfi.build(random_tree(64, seed=0), reweightable=True,
                              use_cache=False)
    assert plan_guard.check_index_dtypes(spec) == []
    assert spec.children.dtype == np.int32
    assert spec.root_refs.dtype == np.int32
    assert plan_guard.check_spec(spec, params) == []


def test_int64_index_array_flagged_and_coerced(tmp_path):
    # > leaf_size vertices so the plan has cross jobs (non-empty src_gather)
    spec, params = ftfi.build(random_tree(200, seed=1), use_cache=False)
    bad = dataclasses.replace(spec,
                              src_gather=spec.src_gather.astype(np.int64))
    issues = plan_guard.check_spec(bad)
    assert any("src_gather" in i and "int64" in i for i in issues), issues

    fixed, coerced = plan_guard.coerce_index_dtypes(bad)
    assert coerced == ["src_gather"]
    assert fixed.src_gather.dtype == np.int32
    assert plan_guard.check_spec(fixed) == []

    # an out-of-range value is a corrupt artifact, never a silent wrap
    evil = dataclasses.replace(
        spec, src_gather=spec.src_gather.astype(np.int64) + 2**40)
    with pytest.raises(plan_guard.PlanValidationError, match="int32"):
        plan_guard.coerce_index_dtypes(evil)


def test_load_plan_canonicalizes_old_int64_artifacts(tmp_path):
    """Artifacts saved before schema 4 carried int64 update tables;
    load_plan downcasts them (bounds-guarded) so every consumer sees the
    canonical int32 layout."""
    spec, params = ftfi.build(random_tree(48, seed=2), use_cache=False)
    old = dataclasses.replace(spec,
                              children=spec.children.astype(np.int64),
                              root_refs=spec.root_refs.astype(np.int64))
    path = tmp_path / "old.npz"
    ftfi.save_plan(path, old, params)
    spec2, params2 = ftfi.load_plan(path)
    assert spec2.children.dtype == np.int32
    assert spec2.root_refs.dtype == np.int32
    X = np.random.default_rng(0).standard_normal((48, 2)).astype(np.float32)
    a = ftfi.apply(spec, params, C.Exponential(-0.5), X)
    b = ftfi.apply(spec2, params2, C.Exponential(-0.5), X)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ---------------------------------------------------------------------------
# fixture 5: retracing closure
# ---------------------------------------------------------------------------


def test_retrace_sentinel_fires():
    """A closure that retraces per call (shape-keyed here) trips
    expect_stable with the per-site compile delta in the error."""
    site = "test.retrace_fixture"

    @jax.jit
    def f(x):
        trace_guard.record(site)
        return x * 2

    f(jnp.ones((4,)))
    with pytest.raises(trace_guard.RetraceError, match=site):
        with trace_guard.expect_stable(site):
            f(jnp.ones((5,)))  # new shape -> retrace

    # stable workload passes the same gate
    with trace_guard.expect_stable(site):
        f(jnp.ones((4,)))
        f(jnp.ones((5,)))


def test_retrace_budget_check():
    site = "test.budgeted_fixture"
    for _ in range(3):
        trace_guard.record(site)
    issues = trace_guard.check({site: 2})
    assert issues and "3x" in issues[0] and site in issues[0], issues
    assert trace_guard.check({site: 3}) == []


def test_ftfi_fastmult_declared_stable():
    """The instrumented production site: repeated jitted calls with stable
    shapes never retrace; a changed field width is one (allowed) recompile."""
    spec, params = ftfi.build(random_tree(48, seed=3), use_cache=False)
    fm = jax.jit(ftfi.fastmult(spec, C.Exponential(-0.5)))
    rng = np.random.default_rng(0)
    X = rng.standard_normal((48, 2)).astype(np.float32)
    fm(params, X)
    with trace_guard.expect_stable("ftfi.fastmult"):
        for _ in range(3):
            fm(params, X)
    with trace_guard.expect_stable("ftfi.fastmult", max_compiles=1):
        X3 = rng.standard_normal((48, 3)).astype(np.float32)
        fm(params, X3)
        fm(params, X3)


# ---------------------------------------------------------------------------
# lint fixtures
# ---------------------------------------------------------------------------


def test_lint_frozen_mutation_flagged():
    src = (
        "def patch(spec, x):\n"
        "    spec.pivots = x\n"
        "    object.__setattr__(spec, 'src_gather', x)\n"
    )
    errs = lint.check_source(src, "src/repro/core/patcher.py")
    rules = [e.rule for e in errs]
    assert rules.count("frozen-mutation") == 2, errs
    assert errs[0].line == 2

    # noqa suppresses, and plan_api.py itself may __setattr__ (digest memo)
    src_ok = src.replace("spec.pivots = x",
                         "spec.pivots = x  # noqa: repro-lint")
    errs2 = lint.check_source(src_ok, "src/repro/core/plan_api.py")
    assert errs2 == [], errs2


def test_lint_legacy_np_random_flagged():
    errs = lint.check_source(
        "import numpy as np\n"
        "a = np.random.randn(4)\n"
        "rng = np.random.default_rng(0)\n"
        "b = rng.standard_normal(4)\n",
        "src/repro/models/foo.py")
    assert [e.rule for e in errs] == ["legacy-np-random"], errs
    assert errs[0].line == 2


def test_lint_traced_host_read_flagged():
    src = (
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    s = float(jnp.sum(x))\n"
        "    t = x.item()\n"
        "    return s + t\n"
    )
    errs = lint.check_source(src, "src/repro/core/bad.py")
    assert [e.rule for e in errs] == ["traced-host-read"] * 2, errs
    # the same host reads are legal outside the traced subpackages
    assert lint.check_source(src, "src/repro/launch/ok.py") == []


def test_lint_x64_flip_flagged():
    errs = lint.check_source(
        "import jax\n"
        "jax.config.update('jax_enable_x64', True)\n",
        "src/repro/core/bad64.py")
    assert [e.rule for e in errs] == ["x64-flip"], errs
    # the context-manager spelling (jax >= 0.9: jax.enable_x64(True))
    errs = lint.check_source(
        "import jax\n"
        "with jax.enable_x64(True):\n"
        "    pass\n",
        "src/repro/core/bad64.py")
    assert [e.rule for e in errs] == ["x64-flip"], errs
    # tests may flip freely
    assert lint.check_source(
        "import jax\njax.config.update('jax_enable_x64', True)\n",
        "tests/test_something.py") == []


def test_lint_clean_on_repo_src():
    out = runner.run_lint()
    assert out["issues"] == [], out["issues"][:10]


# ---------------------------------------------------------------------------
# clean entry points + budget coverage
# ---------------------------------------------------------------------------


def test_budgets_cover_every_registered_entry_point():
    budgets = runner.load_budgets()
    declared = set(budgets["entry_points"])
    registered = set(entry_points.REGISTRY)
    assert registered <= declared, (
        f"entries missing from ANALYSIS_BUDGETS.json: "
        f"{sorted(registered - declared)}")


@pytest.mark.parametrize("section", ["core", "kernels", "serve"])
def test_clean_entry_points_pass(section):
    """Every registered entry point audits clean against its declared
    budget (sharded/models sections ride the CI static-analysis job and the
    subprocess distribution tests — too slow for tier-1)."""
    budgets = runner.load_budgets()
    out = runner.run_audits(budgets, sections=[section])
    assert out["issues"] == [], out["issues"]
    assert out["reports"], f"no entry points audited for section {section}"
    for rep in out["reports"]:
        assert rep["ok"], rep


def test_audit_walks_nested_call_eqns():
    """The walker recurses through pjit/scan/cond rather than reading the
    pretty-printed string: a collective hidden two levels down is found."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((1,), ("i",))

    def fwd(x):
        def body(xs):
            def step(c, t):
                return c + jax.lax.psum(t, "i"), t

            out, _ = jax.lax.scan(step, jnp.zeros_like(xs[0]), xs)
            return out

        return shard_map(body, mesh=mesh, in_specs=P(None, "i"),
                         out_specs=P("i"), check_vma=False)(x)

    rep = jaxpr_audit.audit(jax.jit(fwd), jnp.ones((4, 1)),
                            name="nested", budget={"collectives": {}})
    assert rep.collectives.get("psum", 0) >= 1, rep.prim_counts
    assert not rep.ok and "collective" in _kinds(rep)
