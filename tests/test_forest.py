"""Forest integration: ONE fused plan across many trees == the per-tree
loop — container semantics, batched flat-IT structure, backend equivalence
on a mixed-size 50+ graph forest, grid reconciliation, the batched Borůvka
spanning forest, FRT-forest averaging, and per-graph forest masks."""
import numpy as np
import pytest
import jax.numpy as jnp

import jax

from repro import ftfi
from repro.analysis import trace_guard
from repro.core import cordial as C
from repro.core.integrate import FTFI, ExpMP
from repro.core.itree_flat import build_flat_forest, build_flat_it
from repro.graphs.graph import (Forest, caterpillar_tree, path_graph,
                                random_tree, star_tree, synthetic_graph)


def _mixed_forest(num=55, seed=0, lo=8, hi=60):
    rng = np.random.default_rng(seed)
    trees = [random_tree(int(s), seed=seed + i)
             for i, s in enumerate(rng.integers(lo, hi, size=num - 3))]
    trees += [path_graph(34), star_tree(27, seed=seed + 1),
              caterpillar_tree(41, seed=seed + 2)]
    return Forest(trees)


def _host_loop(forest, fn, X):
    """The per-tree host reference loop over a packed field: ExpMP for exp
    f, else the recursive FTFI, one tree at a time."""
    off, outs = forest.offsets, []
    for i, t in enumerate(forest.trees):
        Xi = X[off[i]:off[i + 1]]
        if isinstance(fn, C.Exponential):
            outs.append(ExpMP(t).integrate(fn.lam, Xi, scale=fn.scale))
        else:
            outs.append(FTFI(t).integrate(fn, Xi))
    return np.concatenate(outs, axis=0)


# ---------------------------------------------------------------------------
# container
# ---------------------------------------------------------------------------


def test_forest_container_pack_unpack_broadcast(rng):
    forest = _mixed_forest(10)
    fields = [rng.normal(size=(int(s), 3)) for s in forest.tree_sizes]
    X = forest.pack(fields)
    assert X.shape == (forest.num_vertices, 3)
    back = forest.unpack(X)
    for a, b in zip(back, fields):
        assert np.array_equal(a, b)
    w = rng.normal(size=forest.num_trees)
    wv = forest.broadcast(w)
    assert wv.shape == (forest.num_vertices,)
    off = forest.offsets
    for t in range(forest.num_trees):
        assert np.all(wv[off[t]:off[t + 1]] == w[t])
    with pytest.raises(ValueError):
        forest.pack(fields[:-1])
    with pytest.raises(ValueError):
        forest.unpack(X[:-1])
    with pytest.raises(ValueError):
        Forest([])
    with pytest.raises(TypeError):
        Forest([synthetic_graph(20, 5, seed=0)])  # not a tree


# ---------------------------------------------------------------------------
# batched flat-IT build == per-tree builds (with offsets)
# ---------------------------------------------------------------------------


def test_build_flat_forest_matches_per_tree_builds():
    forest = _mixed_forest(12, seed=3)
    flat = build_flat_forest(forest.trees, leaf_size=16, use_cache=False)
    per = [build_flat_it(t, leaf_size=16, use_cache=False)
           for t in forest.trees]
    off = forest.offsets
    assert flat.n == forest.num_vertices
    assert flat.num_internal == sum(p.num_internal for p in per)
    assert flat.num_leaves == sum(p.num_leaves for p in per)
    exp_piv = np.sort(np.concatenate(
        [p.pivots + off[i] for i, p in enumerate(per)]))
    assert np.array_equal(np.sort(flat.pivots), exp_piv)
    # every vertex appears in exactly the leaves covering it
    leaf_verts = np.sort(np.concatenate(flat.leaf_ids))
    exp_leaf = np.sort(np.concatenate(
        [ids + off[i] for i, p in enumerate(per) for ids in p.leaf_ids]))
    assert np.array_equal(leaf_verts, exp_leaf)
    # per-tree roots are recorded (one ref per tree, valid encoding)
    assert flat.root_refs is not None and flat.root_refs.size == 12


# ---------------------------------------------------------------------------
# acceptance: fused forest plan == per-tree loop on a mixed 50+ graph forest
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["plan", "pallas"])
def test_forest_plan_equals_per_tree_loop(backend, rng):
    forest = _mixed_forest(55)
    X = rng.normal(size=(forest.num_vertices, 3))
    spec, params = ftfi.build(forest, leaf_size=16)
    for fn in (C.Exponential(-0.7, 1.3), C.Polynomial((0.5, -0.2, 0.1)),
               C.AnyFn(lambda z: (z + 1.0) ** -0.5)):
        ref = _host_loop(forest, fn, X)
        got = np.asarray(ftfi.apply(spec, params, fn, X, backend=backend))
        scale = max(np.max(np.abs(ref)), 1e-12)
        assert np.max(np.abs(got - ref)) / scale < 1e-5, type(fn).__name__
    assert forest.num_trees == 55
    assert spec.num_trees == 55
    assert ftfi.describe(spec, C.Exponential(-0.7), backend)["num_trees"] == 55


def test_forest_is_block_diagonal(rng):
    """A field supported on one tree never leaks into another tree's rows."""
    forest = _mixed_forest(8, seed=5)
    off = forest.offsets
    X = np.zeros((forest.num_vertices, 2))
    t = 3
    X[off[t]:off[t + 1]] = rng.normal(size=(off[t + 1] - off[t], 2))
    out = np.asarray(ftfi.apply(*ftfi.build(forest, leaf_size=16),
                                C.Exponential(-0.5), X))
    mask = np.zeros(forest.num_vertices, bool)
    mask[off[t]:off[t + 1]] = True
    assert np.max(np.abs(out[~mask])) < 1e-6 * max(np.max(np.abs(out)), 1e-9)


def test_forest_single_fused_dispatch(rng):
    """The whole forest runs as one cached jitted executor: no retrace on
    repeated calls, num_trees-independent dispatch structure."""
    forest = _mixed_forest(20, seed=7)
    X = rng.normal(size=(forest.num_vertices, 2))
    spec, params = ftfi.build(forest, leaf_size=16)
    fm = jax.jit(ftfi.fastmult(spec, C.Exponential(-0.4)))
    site = "ftfi.fastmult"
    n0 = trace_guard.compiles(site)
    np.asarray(fm(params, X))
    assert trace_guard.compiles(site) == n0 + 1
    with trace_guard.expect_stable(site):
        np.asarray(fm(params, X))  # same shapes: no retrace
    # buckets are merged across trees by size class: far fewer buckets than
    # trees (the whole point of the shared index space)
    assert len(spec.cross_tgt_mask) + len(spec.leaf_ids) < 12


def test_forest_fastmult_shared_across_instances(rng):
    """Content-cached plans share their compiled mask closures: a build
    over an identical forest returns the same pair, so its forest mask
    reuses the jitted executor."""
    from repro.core import masks as MK

    forest = _mixed_forest(6, seed=11)
    p1 = ftfi.build(forest, leaf_size=16)
    coeffs = np.asarray([0.1, -0.3], np.float32)
    fm1 = MK.make_forest_fastmult(p1, forest, "exp", coeffs)
    twin = Forest([type(t)(t.num_vertices, t.edges_u.copy(),
                           t.edges_v.copy(), t.weights.copy())
                   for t in forest.trees])
    p2 = ftfi.build(twin, leaf_size=16)
    assert p2[0] is p1[0] and p2[1] is p1[1]  # content-hash plan hit
    assert MK.make_forest_fastmult(p2, twin, "exp", coeffs) is fm1


def test_forest_grid_h_reconciliation(rng):
    """All-unit-weight forest -> grid_h == 1.0 and the exact Hankel engine
    for general f; one off-grid tree poisons the whole forest to None."""
    unit = Forest([path_graph(40), path_graph(25),
                   path_graph(33)])
    general = C.AnyFn(lambda z: np.sin(z) * np.exp(-0.1 * z) + 1.0)
    X = rng.normal(size=(unit.num_vertices, 2))
    spec, params = ftfi.build(unit, leaf_size=8)
    assert spec.grid_h == pytest.approx(1.0)
    assert ftfi.describe(spec, general)["cross_engine"] == "hankel_fft"
    ref = _host_loop(unit, general, X)
    got = np.asarray(ftfi.apply(spec, params, general, X))
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5
    mixed = Forest([path_graph(40), random_tree(30, seed=2)])
    assert ftfi.build(mixed, leaf_size=8)[0].grid_h is None


# ---------------------------------------------------------------------------
# batched Borůvka spanning forest == per-graph Kruskal
# ---------------------------------------------------------------------------


def test_minimum_spanning_forest_matches_kruskal():
    from repro.graphs.mst import (minimum_spanning_forest,
                                  minimum_spanning_tree)

    graphs = [synthetic_graph(int(n), int(n) // 2, seed=i)
              for i, n in enumerate(np.random.default_rng(0)
                                    .integers(10, 80, size=25))]
    msf = minimum_spanning_forest(graphs)
    for got, g in zip(msf, graphs):
        ref = minimum_spanning_tree(g)
        ka = sorted(zip(got.edges_u.tolist(), got.edges_v.tolist(),
                        got.weights.tolist()))
        kb = sorted(zip(ref.edges_u.tolist(), ref.edges_v.tolist(),
                        ref.weights.tolist()))
        assert ka == kb
    # disconnected member raises
    bad = synthetic_graph(10, 0, seed=0)
    bad = type(bad)(11, bad.edges_u, bad.edges_v, bad.weights)  # isolated v
    with pytest.raises(ValueError, match="disconnected"):
        minimum_spanning_forest([graphs[0], bad])


# ---------------------------------------------------------------------------
# FRT forest averaging
# ---------------------------------------------------------------------------


def test_frt_integrate_forest_equals_mean_of_single_trees(rng):
    from repro.graphs.frt import frt_integrate, frt_integrate_forest

    g = synthetic_graph(60, 30, seed=4)
    X = rng.normal(size=(60, 2))
    fn = C.Exponential(-0.5)
    k = 4
    got = frt_integrate_forest(g, fn, X, num_trees=k, seed=7, leaf_size=16)
    # frt_forest samples tree t with seed = seed + 977 * t
    ref = np.mean(np.stack([
        frt_integrate(g, fn, X, seed=7 + 977 * t, leaf_size=16)
        for t in range(k)]), axis=0)
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5


# ---------------------------------------------------------------------------
# per-graph masks over a packed forest
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("weights", [None, "per_tree"])
def test_make_forest_fastmult_block_diag_mask(weights, rng):
    from repro.core import masks as MK
    from repro.graphs.traverse import tree_all_pairs

    forest = _mixed_forest(5, seed=9, lo=8, hi=24)
    off = forest.offsets
    N = forest.num_vertices
    plan = ftfi.build(forest, leaf_size=8)
    coeffs = jnp.asarray([0.0, -0.3], jnp.float32)
    tw = (rng.uniform(0.5, 1.5, size=forest.num_trees)
          if weights == "per_tree" else None)
    fm = MK.make_forest_fastmult(plan, forest, "exp", coeffs,
                                 dist_scale=1.0, tree_weights=tw)
    X = jnp.asarray(rng.normal(size=(2, N, 4)), jnp.float32)  # batched field
    # dense block-diagonal reference
    M = np.zeros((N, N))
    for t, tree in enumerate(forest.trees):
        D = tree_all_pairs(tree)
        blk = np.exp(-0.3 * D)
        if tw is not None:
            blk = tw[t] * blk
        M[off[t]:off[t + 1], off[t]:off[t + 1]] = blk
    ref = np.einsum("lk,bkd->bld", M, np.asarray(X))
    got = np.asarray(fm(X))
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5
