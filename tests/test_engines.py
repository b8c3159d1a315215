"""host references == plan == pallas == BTFI oracle: the numpy FTFI /
ExpMP and `ftfi.apply` on both executor backends, engine auto-selection
(Pallas families, Hankel on grids), grid_h surfacing, ITNode immutability,
and jit-ability of `ftfi.fastmult`."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import ftfi
from repro.analysis import trace_guard
from repro.core import cordial as C
from repro.core import masks as MK
from repro.core.cordial import spec_of
from repro.core.integrate import BTFI, FTFI, ExpMP
from repro.core.integrator_tree import build_integrator_tree
from repro.graphs.graph import (caterpillar_tree, path_graph, random_tree,
                                star_tree)

BACKENDS = ["host", "plan", "pallas"]

# one fn per in-kernel family + one general f (chebyshev/hankel fallback)
KERNEL_FAMILY_FNS = [
    C.Polynomial((0.5, -0.2, 0.1)),
    C.Exponential(-0.7, 1.3),
    C.ExpQuadratic(-0.05, -0.2, 0.1),
    C.Rational((2.0,), (1.0, 0.0, 0.8)),
]
GENERAL_FNS = [
    C.ExpPoly(-0.5, (1.0, 0.3)),
    C.AnyFn(lambda z: (z + 1.0) ** -0.5),
]


def integrate(tree, backend, fn, X, leaf_size=16):
    """M_f X on one backend: "host" is the numpy reference (ExpMP for exp
    f, else the recursive FTFI); "plan" / "pallas" run `ftfi.apply`."""
    if backend == "host":
        if isinstance(fn, C.Exponential):
            return ExpMP(tree).integrate(fn.lam, X, scale=fn.scale)
        return FTFI(tree, leaf_size=leaf_size).integrate(fn, X)
    spec, params = ftfi.build(tree, leaf_size=leaf_size)
    return np.asarray(ftfi.apply(spec, params, fn, X, backend=backend))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("fn", KERNEL_FAMILY_FNS + GENERAL_FNS,
                         ids=lambda f: type(f).__name__)
def test_integrator_equals_btfi(backend, fn, rng):
    tree = random_tree(157, seed=1)
    X = rng.normal(size=(157, 3))
    ref = BTFI(tree).integrate(fn, X)
    got = integrate(tree, backend, fn, X)
    scale = max(np.max(np.abs(ref)), 1e-12)
    assert np.max(np.abs(got - ref)) / scale < 1e-5


@pytest.mark.parametrize("fn", KERNEL_FAMILY_FNS,
                         ids=lambda f: type(f).__name__)
def test_pallas_backend_uses_fdist_kernel(fn):
    spec, _ = ftfi.build(random_tree(60, seed=2), leaf_size=16)
    engine = ftfi.describe(spec, fn, "pallas")["cross_engine"]
    assert engine.startswith("fdist_matvec:"), engine
    mode = spec_of(fn).mode
    assert engine == f"fdist_matvec:{mode}"


def test_backends_agree_pairwise(rng):
    """host == plan == pallas on the same field (tighter than vs-oracle)."""
    tree = caterpillar_tree(90, seed=3)
    X = rng.normal(size=(90, 2))
    fn = C.ExpQuadratic(-0.03, -0.1, 0.0)
    outs = [integrate(tree, b, fn, X) for b in BACKENDS]
    for o in outs[1:]:
        assert np.max(np.abs(o - outs[0])) / np.max(np.abs(outs[0])) < 1e-5


# ---------------------------------------------------------------------------
# grid_h surfacing: unit-weight trees auto-select the exact Hankel/FFT engine
# ---------------------------------------------------------------------------


def test_grid_h_on_unit_weight_path(rng):
    tree = path_graph(64)  # unit weights -> integer distance grid
    general = C.AnyFn(lambda z: np.sin(z) * np.exp(-0.1 * z) + 1.0 / (1 + z))
    X = rng.normal(size=(64, 2))
    ref = BTFI(tree).integrate(general, X)
    spec, _ = ftfi.build(tree, leaf_size=8)
    assert spec.grid_h == pytest.approx(1.0)
    assert C.detect_grid(tree.weights, np.zeros(1)) == pytest.approx(1.0)
    for backend in BACKENDS:
        if backend in ("plan", "pallas"):
            assert (ftfi.describe(spec, general, backend)["cross_engine"]
                    == "hankel_fft")
        got = integrate(tree, backend, general, X, leaf_size=8)
        assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5


def test_grid_h_none_on_irrational_weights():
    tree = random_tree(50, seed=5)  # uniform random weights: no common grid
    spec, _ = ftfi.build(tree, leaf_size=8)
    assert spec.grid_h is None
    assert ftfi.describe(spec, C.AnyFn(np.cos))["cross_engine"] == "chebyshev"


# ---------------------------------------------------------------------------
# ExpMP vs the BTFI oracle (the host reference for exp f)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mk", [lambda: random_tree(157, seed=1),
                                lambda: star_tree(80, seed=3),
                                lambda: path_graph(100)])
@pytest.mark.parametrize("lam,scale", [(-0.4, 0.7), (-1.1, 1.0), (0.2, 0.3)])
def test_expmp_equals_btfi(mk, lam, scale, rng):
    tree = mk()
    n = tree.num_vertices
    X = rng.normal(size=(n, 3))
    ref = BTFI(tree).integrate(lambda z: scale * np.exp(lam * z), X)
    got = ExpMP(tree).integrate(lam, X, scale=scale)
    # growing exponentials (lam > 0) span ~9 decades on long paths; 1e-7
    # relative still certifies exactness up to float64 cancellation
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-7
    # and the host path of this suite routes Exponential through it
    got2 = integrate(tree, "host", C.Exponential(lam, scale), X)
    np.testing.assert_array_equal(got2, got)


# ---------------------------------------------------------------------------
# immutability + jit
# ---------------------------------------------------------------------------


def test_itnode_is_immutable():
    root = build_integrator_tree(random_tree(80, seed=7), leaf_size=16)
    with pytest.raises(dataclasses.FrozenInstanceError):
        root.pivot = 0
    # segment layouts are precomputed at build time on internal nodes
    assert root.left_sorted_ids is not None
    assert root.left_seg_starts is not None
    assert root.left_seg_starts[0] == 0
    assert set(root.left_sorted_ids) == set(root.left_ids)


@pytest.mark.parametrize("backend", ["plan", "pallas"])
def test_fastmult_cache_hit_no_retrace(backend, rng, monkeypatch):
    """The mask memo over a (spec, params) pair: the second
    make_tree_fastmult returns the same jitted closure (even for an
    equal-valued new coeffs array), and back-to-back calls do not
    re-trace the mask executor (`masks.tree_fastmult`) on either backend."""
    monkeypatch.setattr(MK, "N_DENSE", 0)  # the plan executor, not dense
    tree = random_tree(70, seed=4)
    X = jnp.asarray(rng.normal(size=(70, 3)), jnp.float32)
    plan = ftfi.build(tree, leaf_size=16)
    fm1 = MK.make_tree_fastmult(plan, "exp", np.array([0.2, -0.7]),
                                backend=backend)
    fm2 = MK.make_tree_fastmult(plan, "exp", np.array([0.2, -0.7]),
                                backend=backend)  # equal, distinct object
    assert fm1 is fm2
    site = "masks.tree_fastmult"
    n0 = trace_guard.compiles(site)
    np.asarray(fm1(X))
    assert trace_guard.compiles(site) == n0 + 1
    with trace_guard.expect_stable(site):
        np.asarray(fm1(X))  # same shapes: cache hit, no retrace
    with trace_guard.expect_stable(site):
        np.asarray(MK.make_tree_fastmult(plan, "exp", np.array([0.2, -0.7]),
                                         backend=backend)(X))
    # different coefficients or backend -> different compiled closure
    assert MK.make_tree_fastmult(plan, "exp", np.array([0.2, -0.2]),
                                 backend=backend) is not fm1
    other = "plan" if backend == "pallas" else "pallas"
    assert MK.make_tree_fastmult(plan, "exp", np.array([0.2, -0.7]),
                                 backend=other) is not fm1


@pytest.mark.parametrize("backend", ["plan", "pallas"])
def test_fastmult_is_jittable_and_differentiable(backend, rng):
    tree = random_tree(60, seed=9)
    X = jnp.asarray(rng.normal(size=(60, 2)), jnp.float32)
    spec, params = ftfi.build(tree, leaf_size=16)
    coeffs = jnp.asarray([0.3, -0.1, 0.05])

    def apply(c, X):
        fm = ftfi.fastmult(spec, lambda z: c[0] + c[1] * z + c[2] * z * z,
                           backend=backend)
        return fm(params, X)

    got = np.asarray(jax.jit(apply)(coeffs, X))
    ref = BTFI(tree).integrate(C.Polynomial((0.3, -0.1, 0.05)),
                               np.asarray(X))
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5

    g = jax.grad(lambda c: jnp.sum(apply(c, X) ** 2))(coeffs)
    assert np.all(np.isfinite(np.asarray(g)))
    assert float(jnp.sum(jnp.abs(g))) > 0.0
