"""Functional plan API: PlanSpec/PlanParams pytree registration, jitted
`ftfi.fastmult` parity on trees and forests against the eager executor
(plan / pallas) and the numpy host references, differentiable
`ftfi.reweight` (exact under new weights, finite-difference gradcheck),
save/load round trip with zero IT rebuild, and the plan LRU's
clear/miss accounting."""
import dataclasses
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import ftfi
from repro.analysis import trace_guard
from repro.core import clear_plan_cache
from repro.core import cordial as C
from repro.core.integrate import FTFI, ExpMP
from repro.graphs.graph import (Forest, WeightedTree, path_graph, random_tree,
                                star_tree)

BACKENDS = ["host", "plan", "pallas"]

PARITY_FNS = [
    C.Exponential(-0.7, 1.3),
    C.Polynomial((0.5, -0.2, 0.1)),
    C.AnyFn(lambda z: 1.0 / (1.0 + z)),
]


def _host(trees, fn, X, leaf_size):
    """The numpy host reference over a packed field of `trees`: ExpMP for
    exp f, else the recursive FTFI, one tree at a time."""
    outs, off = [], 0
    for t in trees:
        Xt = np.asarray(X)[off:off + t.num_vertices]
        if isinstance(fn, C.Exponential):
            outs.append(ExpMP(t).integrate(fn.lam, Xt, scale=fn.scale))
        else:
            outs.append(FTFI(t, leaf_size=leaf_size).integrate(fn, Xt))
        off += t.num_vertices
    return np.concatenate(outs, axis=0)


def _reference(tree_or_forest, backend, fn, X, leaf_size):
    """Eager M_f X on `backend`: the host reference, or the un-jitted
    executor `ftfi.apply` on the plan / pallas backend."""
    if backend == "host":
        trees = getattr(tree_or_forest, "trees", [tree_or_forest])
        return _host(trees, fn, X, leaf_size)
    spec, params = ftfi.build(tree_or_forest, leaf_size=leaf_size)
    return ftfi.apply(spec, params, fn, X, backend=backend)


def _rel_err(got, ref):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(ref)))
                 / max(np.max(np.abs(np.asarray(ref))), 1e-12))


# ----------------------------------------------------------------------------
# pytree registration
# ----------------------------------------------------------------------------


def test_pytree_roundtrip_identity():
    """tree_flatten((spec, params)) puts every distance/weight array in the
    leaves and the spec in the aux data; unflatten reproduces both."""
    spec, params = ftfi.build(random_tree(60, seed=2), leaf_size=16)
    leaves, treedef = jax.tree_util.tree_flatten((spec, params))
    assert leaves, "params must contribute pytree leaves"
    assert all(hasattr(leaf, "dtype") for leaf in leaves)
    spec2, params2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert spec2 is spec  # the spec IS the (hashable) aux data
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(params2)):
        assert a is b
    # spec alone flattens to zero leaves; content digest keys retraces
    sl, _ = jax.tree_util.tree_flatten(spec)
    assert sl == []
    assert hash(spec) == hash(spec2) and spec == spec2


def test_params_tree_map():
    """PlanParams is a real pytree: tree_map reaches every distance array."""
    spec, params = ftfi.build(random_tree(40, seed=3), leaf_size=8)
    doubled = jax.tree_util.tree_map(lambda a: a * 2.0, params)
    for a, b in zip(params.cross_tgt_d, doubled.cross_tgt_d):
        assert np.allclose(np.asarray(b), 2.0 * np.asarray(a))


# ----------------------------------------------------------------------------
# jit / vmap parity vs the eager paths
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("fn", PARITY_FNS, ids=lambda f: type(f).__name__)
def test_apply_jit_parity_tree(backend, fn, rng):
    tree = random_tree(130, seed=1)
    X = rng.normal(size=(130, 3))
    ref = _reference(tree, backend, fn, X, leaf_size=16)
    spec, params = ftfi.build(tree, leaf_size=16)
    engine = "pallas" if backend == "pallas" else "plan"
    fm = jax.jit(ftfi.fastmult(spec, fn, backend=engine))
    got = fm(params, jnp.asarray(X))
    assert _rel_err(got, ref) < 1e-5


@pytest.mark.parametrize("backend", BACKENDS)
def test_apply_jit_parity_forest(backend, rng):
    trees = [random_tree(18 + 3 * i, seed=i) for i in range(5)]
    trees += [path_graph(24), star_tree(20, seed=7)]
    forest = Forest(trees)
    X = rng.normal(size=(forest.num_vertices, 2))
    fn = C.Exponential(-0.5, 1.2)
    ref = _reference(forest, backend, fn, X, leaf_size=8)
    spec, params = ftfi.build(forest, leaf_size=8)
    assert spec.num_trees == forest.num_trees
    engine = "pallas" if backend == "pallas" else "plan"
    got = jax.jit(ftfi.fastmult(spec, fn, backend=engine))(params, X)
    assert _rel_err(got, ref) < 1e-5


def test_vmap_over_batched_fields(rng):
    """The pure executor vmaps over a leading batch axis of fields — the
    thing the closure-capturing API could not express."""
    tree = random_tree(50, seed=4)
    spec, params = ftfi.build(tree, leaf_size=8)
    fn = C.Exponential(-0.4)
    Xb = jnp.asarray(rng.normal(size=(6, 50, 2)), jnp.float32)
    fm = ftfi.fastmult(spec, fn)
    got = jax.vmap(fm, in_axes=(None, 0))(params, Xb)
    for b in range(Xb.shape[0]):
        assert _rel_err(got[b], fm(params, Xb[b])) < 1e-6


def test_forest_tree_w_output_weights(rng):
    """params.tree_w scales each tree's output block (== scaling its mask)."""
    forest = Forest([random_tree(15, seed=i) for i in range(4)])
    spec, params = ftfi.build(forest, leaf_size=8)
    fn = C.Exponential(-0.6)
    X = rng.normal(size=(forest.num_vertices, 2))
    w = rng.uniform(0.5, 2.0, size=forest.num_trees)
    ref = np.asarray(ftfi.apply(spec, params, fn, X))
    ref = ref * forest.broadcast(w)[:, None]
    pw = dataclasses.replace(params, tree_w=jnp.asarray(w, jnp.float32))
    got = ftfi.apply(spec, pw, fn, X)
    assert _rel_err(got, ref) < 1e-6


# ----------------------------------------------------------------------------
# reweight: learnable tree metrics
# ----------------------------------------------------------------------------


def test_reweight_identity_matches_birth_params(rng):
    tree = random_tree(45, seed=5)
    spec, params = ftfi.build(tree, leaf_size=8, reweightable=True)
    fn = C.Exponential(-0.6, 1.1)
    X = rng.normal(size=(45, 2))
    a = ftfi.apply(spec, params, fn, X)
    b = ftfi.apply(spec, ftfi.reweight(spec, tree.weights), fn, X)
    assert _rel_err(b, a) < 1e-5


def test_reweight_exact_under_new_weights(rng):
    """The IT decomposition is combinatorial, so reweighted params give the
    TRUE integration for any positive weights on the same topology."""
    tree = random_tree(40, seed=3)
    spec, _ = ftfi.build(tree, leaf_size=8, reweightable=True)
    w1 = rng.uniform(0.2, 2.0, size=tree.num_edges)
    t1 = WeightedTree(tree.num_vertices, tree.edges_u, tree.edges_v, w1)
    fn = C.Exponential(-0.6, 1.1)
    X = rng.normal(size=(40, 2))
    ref = _host([t1], fn, X, leaf_size=8)
    got = ftfi.apply(spec, ftfi.reweight(spec, w1), fn, X)
    assert _rel_err(got, ref) < 1e-5


def test_reweight_gradcheck_finite_differences(rng):
    """jax.grad through ftfi.reweight edge weights matches central finite
    differences (the acceptance-criterion gradcheck)."""
    tree = random_tree(16, seed=8)
    spec, _ = ftfi.build(tree, leaf_size=6, reweightable=True)
    fn = C.Exponential(-0.8)
    X = jnp.asarray(rng.normal(size=(16, 2)), jnp.float32)
    R = jnp.asarray(rng.normal(size=(16, 2)), jnp.float32)
    w0 = jnp.asarray(tree.weights, jnp.float32)

    def loss(w):
        return jnp.sum(R * ftfi.apply(spec, ftfi.reweight(spec, w), fn, X))

    g = np.asarray(jax.grad(loss)(w0))
    assert np.all(np.isfinite(g)) and np.sum(np.abs(g)) > 0
    h = 3e-3
    for i in range(w0.shape[0]):
        e = np.zeros(w0.shape, np.float32)
        e[i] = h
        fd = (float(loss(w0 + e)) - float(loss(w0 - e))) / (2 * h)
        ref_scale = max(abs(fd), float(np.max(np.abs(g))), 1e-4)
        assert abs(g[i] - fd) / ref_scale < 5e-2, (i, g[i], fd)


def test_reweight_requires_reweightable_spec():
    tree = random_tree(20, seed=1)
    spec, _ = ftfi.build(tree, leaf_size=8)
    with pytest.raises(ValueError, match="reweightable"):
        ftfi.reweight(spec, tree.weights)
    rspec, _ = ftfi.build(tree, leaf_size=8, reweightable=True)
    assert rspec.grid_h is None  # a trained metric has no static grid
    with pytest.raises(ValueError, match="edge_w"):
        ftfi.reweight(rspec, np.ones(3))


def test_reweight_forest_packed_edges(rng):
    """Forest reweight: one packed edge vector re-derives every tree's
    block, and per-tree output weights ride along."""
    trees = [random_tree(12, seed=i) for i in range(3)]
    forest = Forest(trees)
    spec, _ = ftfi.build(forest, leaf_size=6, reweightable=True)
    w1 = rng.uniform(0.3, 1.5, size=spec.num_edges)
    off = 0
    new_trees = []
    for t in trees:
        new_trees.append(WeightedTree(t.num_vertices, t.edges_u, t.edges_v,
                                      w1[off:off + t.num_edges]))
        off += t.num_edges
    fn = C.Exponential(-0.5)
    X = rng.normal(size=(forest.num_vertices, 2))
    ref = _host(new_trees, fn, X, leaf_size=6)
    got = ftfi.apply(spec, ftfi.reweight(spec, w1), fn, X)
    assert _rel_err(got, ref) < 1e-5


# ----------------------------------------------------------------------------
# save / load
# ----------------------------------------------------------------------------


def test_save_load_bitwise_roundtrip(tmp_path, rng, monkeypatch):
    tree = random_tree(70, seed=6)
    spec, params = ftfi.build(tree, leaf_size=16)
    fn = C.Exponential(-0.5)
    X = jnp.asarray(rng.normal(size=(70, 3)), jnp.float32)
    a = np.asarray(ftfi.apply(spec, params, fn, X))
    a_jit = np.asarray(jax.jit(ftfi.fastmult(spec, fn))(params, X))
    path = os.path.join(tmp_path, "plan.npz")
    ftfi.save_plan(path, spec, params)

    # loading must NEVER rebuild the IT (the whole point of the artifact)
    import repro.core.itree_flat as itree_flat

    def _boom(*args, **kwargs):
        raise AssertionError("load_plan triggered an IT rebuild")

    monkeypatch.setattr(itree_flat, "_build", _boom)
    spec2, params2 = ftfi.load_plan(path)
    # bit-for-bit in both execution modes: identical arrays in, identical
    # (eager or jitted) program, identical bits out
    b = np.asarray(ftfi.apply(spec2, params2, fn, X))
    assert np.array_equal(a, b)
    b_jit = np.asarray(jax.jit(ftfi.fastmult(spec2, fn))(params2, X))
    assert np.array_equal(a_jit, b_jit)
    assert spec2 == spec and hash(spec2) == hash(spec)
    # the ladder-supervised door over the artifact is also rebuild-free
    # and exact
    c = np.asarray(ftfi.apply_resilient(spec2, params2, fn, X,
                                        backend="plan"))
    assert _rel_err(c, a) < 1e-6


def test_save_load_reweightable_keeps_tables(tmp_path, rng):
    tree = random_tree(24, seed=2)
    spec, params = ftfi.build(tree, leaf_size=8, reweightable=True)
    path = os.path.join(tmp_path, "rw_plan.npz")
    ftfi.save_plan(path, spec, params)
    spec2, _ = ftfi.load_plan(path)
    w1 = rng.uniform(0.4, 1.4, size=tree.num_edges)
    X = rng.normal(size=(24, 2))
    a = ftfi.apply(spec, ftfi.reweight(spec, w1), C.Exponential(-0.7), X)
    b = ftfi.apply(spec2, ftfi.reweight(spec2, w1), C.Exponential(-0.7), X)
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------------
# cache semantics + masks over pairs
# ----------------------------------------------------------------------------


def test_clear_plan_cache_next_build_misses():
    """The LRU holds the built pair as it is: a repeat build is a hit that
    returns the same objects; after clear_plan_cache the next build records
    an `integrate.plan_cache:miss` and compiles a fresh pair."""
    tree = random_tree(30, seed=9)
    pair = ftfi.build(tree, leaf_size=8)
    hits = trace_guard.compiles("integrate.plan_cache:hit")
    again = ftfi.build(tree, leaf_size=8)
    assert again[0] is pair[0] and again[1] is pair[1]
    assert trace_guard.compiles("integrate.plan_cache:hit") == hits + 1
    clear_plan_cache()
    misses = trace_guard.compiles("integrate.plan_cache:miss")
    fresh = ftfi.build(tree, leaf_size=8)
    assert trace_guard.compiles("integrate.plan_cache:miss") == misses + 1
    assert fresh[0] is not pair[0] and fresh[0] == pair[0]


def test_masks_accept_functional_pair(rng):
    """make_tree_fastmult rides a raw (spec, params) pair on the plan
    executor, equal to `ftfi.apply` of the same mask f."""
    from repro.core import masks as MK
    from repro.graphs.graph import grid_graph
    from repro.graphs.mst import minimum_spanning_tree

    mst = minimum_spanning_tree(grid_graph(5, 5))
    pair = ftfi.build(mst, leaf_size=8)
    coeffs = np.asarray([0.1, -0.4], np.float32)
    X = jnp.asarray(rng.normal(size=(25, 3)), jnp.float32)
    a = MK.make_tree_fastmult(pair, "exp", coeffs, 0.5)(X)
    b = ftfi.apply(*pair, MK.mask_f("exp", coeffs, 0.5), X)
    assert _rel_err(a, b) < 1e-5
    # memoized per spec for concrete coeffs
    assert (MK.make_tree_fastmult(pair, "exp", coeffs, 0.5)
            is MK.make_tree_fastmult(pair, "exp", coeffs, 0.5))


def test_masks_pair_memo_distinguishes_reweighted_params(rng):
    """Regression: the (spec, params) memo must key on the params too —
    a reweighted PlanParams over the SAME spec is a different mask."""
    from repro.core import masks as MK

    tree = random_tree(30, seed=1)
    spec, p0 = ftfi.build(tree, leaf_size=8, reweightable=True)
    coeffs = np.asarray([0.1, -0.4], np.float32)
    X = jnp.asarray(rng.normal(size=(30, 2)), jnp.float32)
    fm0 = MK.make_tree_fastmult((spec, p0), "exp", coeffs, 0.5)
    p1 = ftfi.reweight(
        spec, rng.uniform(0.3, 1.5, size=tree.num_edges).astype(np.float32))
    fm1 = MK.make_tree_fastmult((spec, p1), "exp", coeffs, 0.5)
    assert fm1 is not fm0
    ref1 = ftfi.apply(spec, p1, MK.mask_f("exp", coeffs, 0.5), X)
    assert _rel_err(fm1(X), ref1) < 1e-6
    assert MK.make_tree_fastmult((spec, p0), "exp", coeffs, 0.5) is fm0
