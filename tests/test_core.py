"""FTFI core: exactness vs the dense oracle — the paper's central claim."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro import ftfi
from repro.core import cordial as C
from repro.core.integrate import BTFI, FTFI
from repro.core.plan_api import _execute, polynomial_batched_matvec
from repro.core.integrator_tree import build_integrator_tree, it_stats
from repro.core import approx
from repro.graphs.graph import (caterpillar_tree, grid_graph, path_graph,
                                random_tree, star_tree)
from repro.graphs.mst import minimum_spanning_tree

TREES = [
    lambda: random_tree(157, seed=1),
    lambda: caterpillar_tree(120, seed=2),
    lambda: star_tree(80, seed=3),
    lambda: path_graph(100),
    lambda: minimum_spanning_tree(grid_graph(10, 10, seed=4)),
]

FNS = [
    C.Polynomial((0.5, -0.2, 0.1)),
    C.Exponential(-0.7),
    C.ExpPoly(-0.5, (1.0, 0.3)),
    C.Trigonometric(0.9, 0.1, "cos"),
    C.Trigonometric(1.3, 0.0, "sin"),
    C.Rational((1.0,), (1.0, 0.0, 0.5)),
    C.ExpQuadratic(-0.02, -0.1, 0.0),
    C.ExpRational(-0.3, 0.8),
    C.AnyFn(lambda z: np.log1p(z) * np.exp(-0.2 * z)),
]


@pytest.mark.parametrize("mk", TREES)
@pytest.mark.parametrize("fn", FNS, ids=[type(f).__name__ for f in FNS])
def test_ftfi_equals_btfi(mk, fn, rng):
    tree = mk()
    n = tree.num_vertices
    X = rng.normal(size=(n, 3))
    ref = BTFI(tree).integrate(fn, X)
    got = FTFI(tree, leaf_size=16).integrate(fn, X)
    scale = max(np.max(np.abs(ref)), 1e-12)
    assert np.max(np.abs(got - ref)) / scale < 1e-8


def test_integrator_tree_invariants():
    tree = random_tree(400, seed=7)
    root = build_integrator_tree(tree, leaf_size=16)
    stats = it_stats(root)
    assert stats["balance_ok"]
    assert stats["max_depth"] <= 4 * int(np.ceil(np.log2(400)))

    # pivot sharing + vertex partition at every node
    def walk(node):
        if node.is_leaf:
            return
        assert node.left_ids[0] == node.pivot == node.right_ids[0]
        both = set(node.left_ids) & set(node.right_ids)
        assert both == {node.pivot}
        assert (set(node.left_ids) | set(node.right_ids)
                == set(node.vertex_ids))
        assert node.left_d[0] == 0.0 and node.right_d[0] == 0.0
        walk(node.left)
        walk(node.right)

    walk(root)


def test_plan_matches_recursive_and_grad(rng):
    tree = random_tree(150, seed=5)
    X = rng.normal(size=(150, 2))
    fn = C.Polynomial((0.3, -0.1, 0.05))
    ref = BTFI(tree).integrate(fn, X)
    spec, params = ftfi.build(tree, leaf_size=16)
    coeffs = jnp.array([0.3, -0.1, 0.05])
    bm = lambda i, *a: polynomial_batched_matvec(coeffs, *a)
    f_eval = lambda z: coeffs[0] + coeffs[1] * z + coeffs[2] * z * z
    got = np.asarray(_execute(spec, params, f_eval, bm, jnp.asarray(X)))
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5

    # gradient wrt coefficients matches finite differences
    def loss(c):
        bmv = lambda i, *a: polynomial_batched_matvec(c, *a)
        fe = lambda z: c[0] + c[1] * z + c[2] * z * z
        return jnp.sum(_execute(spec, params, fe, bmv,
                                jnp.asarray(X, jnp.float32)) ** 2)

    g = jax.grad(loss)(coeffs)
    eps = 1e-3
    for i in range(3):
        fd = (loss(coeffs.at[i].add(eps)) - loss(coeffs.at[i].add(-eps))) / (2 * eps)
        assert abs(float(fd) - float(g[i])) / (abs(float(fd)) + 1e-3) < 5e-2


def test_chebyshev_engine_spectral(rng):
    tree = random_tree(120, seed=9)
    X = rng.normal(size=(120, 2))
    f_np = lambda z: np.exp(-0.4 * z) / (1 + 0.3 * z)
    f_j = lambda z: jnp.exp(-0.4 * z) / (1 + 0.3 * z)
    ref = BTFI(tree).integrate(f_np, X)
    spec, params = ftfi.build(tree, leaf_size=16)
    assert ftfi.describe(spec, f_j)["cross_engine"] == "chebyshev"
    got = np.asarray(ftfi.apply(spec, params, f_j, jnp.asarray(X),
                                degree=32))
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5


# ---------------------------------------------------------------------------
# property-based: structured multiplies == dense, arbitrary inputs
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(a=st.integers(2, 40), b=st.integers(2, 40), seed=st.integers(0, 10**6),
       deg=st.integers(0, 4))
def test_polynomial_matvec_property(a, b, seed, deg):
    r = np.random.default_rng(seed)
    x = r.uniform(0, 5, a)
    y = r.uniform(0, 5, b)
    V = r.normal(size=(b, 2))
    coeffs = r.normal(size=deg + 1)
    got = C.polynomial_matvec(coeffs, x, y, V)
    f = lambda z: sum(c * z**t for t, c in enumerate(coeffs))
    ref = C.dense_matvec(f, x, y, V)
    assert np.allclose(got, ref, rtol=1e-8, atol=1e-8 * max(1, np.abs(ref).max()))


@settings(max_examples=25, deadline=None)
@given(a=st.integers(2, 40), b=st.integers(2, 40), seed=st.integers(0, 10**6),
       lam=st.floats(-2.0, 0.5))
def test_exponential_matvec_property(a, b, seed, lam):
    r = np.random.default_rng(seed)
    x = r.uniform(0, 4, a)
    y = r.uniform(0, 4, b)
    V = r.normal(size=(b, 3))
    got = C.exponential_matvec(lam, x, y, V)
    ref = C.dense_matvec(lambda z: np.exp(lam * z), x, y, V)
    assert np.allclose(got, ref, rtol=1e-9, atol=1e-9 * max(1, np.abs(ref).max()))


@settings(max_examples=15, deadline=None)
@given(a=st.integers(2, 30), b=st.integers(2, 30), seed=st.integers(0, 10**6),
       q=st.integers(1, 4))
def test_hankel_fft_property(a, b, seed, q):
    r = np.random.default_rng(seed)
    h = 1.0 / q
    x = r.integers(0, 30, a) * h
    y = r.integers(0, 30, b) * h
    V = r.normal(size=(b, 2))
    f = lambda z: np.cos(z) / (1 + z)  # arbitrary f: exact on grids
    got = C.hankel_fft_matvec(f, x, y, V, h)
    ref = C.dense_matvec(f, x, y, V)
    assert np.allclose(got, ref, rtol=1e-9, atol=1e-9)


def test_unit_weight_tree_any_f_exact(rng):
    """Paper A.2.3: unit weights -> Hankel -> exact for ANY f."""
    tree = random_tree(200, seed=11, weight_range=(1.0, 1.0))
    X = rng.normal(size=(200, 2))
    fn = C.AnyFn(lambda z: np.sin(z) * np.exp(-0.1 * z) + 1.0 / (1 + z))
    ref = BTFI(tree).integrate(fn, X)
    got = FTFI(tree, leaf_size=16).integrate(fn, X)
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-9


def test_cauchy_matvec(rng):
    p = rng.uniform(0.5, 4, 80)
    q = rng.uniform(0.5, 4, 70)
    V = rng.normal(size=(70, 2))
    got = C.cauchy_matvec(p, q, V)
    ref = (1.0 / (p[:, None] + q[None, :])) @ V
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-8


def test_rff_and_nufft(rng):
    a, b = 150, 140
    x = rng.uniform(0, 3, a)
    y = rng.uniform(0, 3, b)
    V = rng.normal(size=(b, 2))
    f = lambda z: np.exp(-0.5 * z * z)
    ref = f(x[:, None] + y[None, :]) @ V
    got_nufft = approx.nufft_integrate(f, x, y, V, n_quad=256)
    assert np.max(np.abs(got_nufft - ref)) / np.max(np.abs(ref)) < 1e-6
    got_rff = approx.gaussian_rff_matvec(x, y, V, sigma=1.0, m=4000, seed=1)
    assert np.max(np.abs(got_rff - ref)) / np.max(np.abs(ref)) < 0.1


def test_exp_message_passing_integrator(rng):
    """Beyond-paper: two-pass message passing == BTFI for exponential f."""
    from repro.core.integrate import ExpMP

    for mk in TREES[:3]:
        tree = mk()
        n = tree.num_vertices
        X = rng.normal(size=(n, 3))
        ref = BTFI(tree).integrate(lambda z: 0.7 * np.exp(-0.4 * z), X)
        got = ExpMP(tree).integrate(-0.4, X, scale=0.7)
        assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-10
