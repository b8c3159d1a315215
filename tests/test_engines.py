"""The pluggable-backend Integrator: host == plan == pallas == BTFI oracle,
engine auto-selection (Pallas families, Hankel on grids), grid_h surfacing,
ITNode immutability, and jit-ability of fastmult."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.analysis import trace_guard
from repro.core import cordial as C
from repro.core.engines import Integrator, available_backends, spec_of
from repro.core.integrate import BTFI, ExpMP
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


def test_backend_registry():
    for b in BACKENDS:
        assert b in available_backends()
    with pytest.raises(ValueError, match="unknown backend"):
        Integrator(random_tree(20, seed=0), backend="nope")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("fn", KERNEL_FAMILY_FNS + GENERAL_FNS,
                         ids=lambda f: type(f).__name__)
def test_integrator_equals_btfi(backend, fn, rng):
    tree = random_tree(157, seed=1)
    X = rng.normal(size=(157, 3))
    ref = BTFI(tree).integrate(fn, X)
    integ = Integrator(tree, backend=backend, leaf_size=16)
    got = np.asarray(integ.integrate(fn, X))
    scale = max(np.max(np.abs(ref)), 1e-12)
    assert np.max(np.abs(got - ref)) / scale < 1e-5


@pytest.mark.parametrize("fn", KERNEL_FAMILY_FNS,
                         ids=lambda f: type(f).__name__)
def test_pallas_backend_uses_fdist_kernel(fn):
    tree = random_tree(60, seed=2)
    integ = Integrator(tree, backend="pallas", leaf_size=16)
    engine = integ.describe(fn)["cross_engine"]
    assert engine.startswith("fdist_matvec:"), engine
    mode = spec_of(fn).mode
    assert engine == f"fdist_matvec:{mode}"


def test_backends_agree_pairwise(rng):
    """host == plan == pallas on the same field (tighter than vs-oracle)."""
    tree = caterpillar_tree(90, seed=3)
    X = rng.normal(size=(90, 2))
    fn = C.ExpQuadratic(-0.03, -0.1, 0.0)
    outs = [np.asarray(Integrator(tree, backend=b, leaf_size=16)
                       .integrate(fn, X)) for b in BACKENDS]
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
    for backend in BACKENDS:
        integ = Integrator(tree, backend=backend, leaf_size=8)
        assert integ.grid_h == pytest.approx(1.0)
        if backend in ("plan", "pallas"):
            assert integ.describe(general)["cross_engine"] == "hankel_fft"
        got = np.asarray(integ.integrate(general, X))
        assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5


def test_grid_h_none_on_irrational_weights():
    tree = random_tree(50, seed=5)  # uniform random weights: no common grid
    integ = Integrator(tree, backend="plan", leaf_size=8)
    assert integ.grid_h is None
    assert integ.describe(C.AnyFn(np.cos))["cross_engine"] == "chebyshev"


# ---------------------------------------------------------------------------
# ExpMP vs the BTFI oracle (host backend dispatches exp to it)
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
    # and the host backend routes Exponential through it
    integ = Integrator(tree, backend="host", leaf_size=16)
    fn = C.Exponential(lam, scale)
    assert integ.describe(fn)["cross_engine"] == "exp_message_passing"
    got2 = integ.integrate(fn, X)
    assert np.max(np.abs(got2 - ref)) / np.max(np.abs(ref)) < 1e-7


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


# the facade's fastmult is the deprecated closure-capturing path (asserted
# in test_plan_api); these tests cover its caching semantics, so silence it
@pytest.mark.filterwarnings("ignore::DeprecationWarning")
@pytest.mark.parametrize("backend", ["plan", "pallas"])
def test_fastmult_cache_hit_no_retrace(backend, rng):
    """Satellite: the jitted fastmult closure is cached per family spec —
    the second fastmult() returns the same object (even for an equal-valued
    new fn instance) and back-to-back integrate calls do not re-trace."""
    tree = random_tree(70, seed=4)
    X = rng.normal(size=(70, 3))
    integ = Integrator(tree, backend=backend, leaf_size=16)
    fm1 = integ.fastmult(C.Exponential(-0.7, 1.3))
    fm2 = integ.fastmult(C.Exponential(-0.7, 1.3))  # equal, distinct object
    assert fm1 is fm2
    assert fm1.jitted
    site = "engines.plan.fastmult"
    n0 = trace_guard.compiles(site)
    np.asarray(fm1(X))
    assert trace_guard.compiles(site) == n0 + 1
    with trace_guard.expect_stable(site):
        np.asarray(fm1(X))  # same shapes: cache hit, no retrace
    with trace_guard.expect_stable(site):
        np.asarray(integ.integrate(C.Exponential(-0.7, 1.3), X))
    # different family spec -> different compiled closure
    assert integ.fastmult(C.Exponential(-0.2)) is not fm1


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
@pytest.mark.parametrize("backend", ["plan", "pallas"])
def test_fastmult_is_jittable_and_differentiable(backend, rng):
    tree = random_tree(60, seed=9)
    X = jnp.asarray(rng.normal(size=(60, 2)), jnp.float32)
    integ = Integrator(tree, backend=backend, leaf_size=16)
    coeffs = jnp.asarray([0.3, -0.1, 0.05])

    def apply(c, X):
        fm = integ.fastmult(lambda z: c[0] + c[1] * z + c[2] * z * z)
        return fm(X)

    got = np.asarray(jax.jit(apply)(coeffs, X))
    ref = BTFI(tree).integrate(C.Polynomial((0.3, -0.1, 0.05)),
                               np.asarray(X))
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5

    g = jax.grad(lambda c: jnp.sum(apply(c, X) ** 2))(coeffs)
    assert np.all(np.isfinite(np.asarray(g)))
    assert float(jnp.sum(jnp.abs(g))) > 0.0
