"""Flat vectorized IT builder: oracle exactness, Lemma-3.1 balance on
degenerate topologies at n=2000 (the old `_centroid_split` re-rooting walk
relied on stale subtree sizes — the flat builder picks a true centroid via a
segmented argmin, so these must terminate AND balance), structural
invariants, and the IT/plan content-hash caches."""
import numpy as np
import pytest

from repro.core import cordial as C
from repro import ftfi
from repro.core.integrate import BTFI, FTFI
from repro.core.plan_api import clear_plan_cache
from repro.core.integrator_tree import build_integrator_tree, it_stats
from repro.core.itree_flat import (build_flat_it, clear_flat_cache,
                                   flat_stats, tree_fingerprint)
from repro.graphs.graph import (caterpillar_tree, grid_graph, path_graph,
                                random_tree, star_tree)
from repro.graphs.meshes import icosphere, mesh_graph
from repro.graphs.mst import minimum_spanning_tree
from repro.graphs.traverse import TreeLCA


TREES = [
    ("random_weighted", lambda: random_tree(300, seed=3)),
    ("mesh_mst", lambda: minimum_spanning_tree(
        mesh_graph(*icosphere(2)))),
    ("path", lambda: path_graph(180)),
    ("star", lambda: star_tree(150, seed=5)),
    ("caterpillar", lambda: caterpillar_tree(200, seed=6)),
    ("grid_mst", lambda: minimum_spanning_tree(grid_graph(12, 12, seed=7))),
]


@pytest.mark.parametrize("name,mk", TREES, ids=[t[0] for t in TREES])
def test_flat_builder_matches_btfi_oracle(name, mk, rng):
    tree = mk()
    n = tree.num_vertices
    X = rng.normal(size=(n, 3))
    for fn in (C.Exponential(-0.6), C.Polynomial((0.4, -0.1, 0.05)),
               C.AnyFn(lambda z: np.log1p(z) * np.exp(-0.3 * z))):
        ref = BTFI(tree).integrate(fn, X)
        got = FTFI(tree, leaf_size=16).integrate(fn, X)
        scale = max(np.max(np.abs(ref)), 1e-12)
        assert np.max(np.abs(got - ref)) / scale < 1e-5


@pytest.mark.parametrize("mk", [lambda: path_graph(2000),
                                lambda: star_tree(2000, seed=0),
                                lambda: caterpillar_tree(2000, seed=0)],
                         ids=["path2000", "star2000", "caterpillar2000"])
def test_degenerate_topologies_balance_at_n2000(mk):
    """Regression for the stale-size re-rooting bug: the build must
    terminate and satisfy the Lemma-3.1 balance bound on adversarial
    shapes."""
    flat = build_flat_it(mk(), leaf_size=64, use_cache=False)
    stats = flat_stats(flat)
    assert stats["balance_ok"]
    assert stats["max_depth"] <= 4 * int(np.ceil(np.log2(2000)))
    # materialized view agrees
    st2 = it_stats(build_integrator_tree(mk(), leaf_size=64))
    assert st2["balance_ok"]
    assert st2["internal"] == stats["internal"]
    assert st2["leaves"] == stats["leaves"]


def test_flat_side_arrays_are_true_pivot_distances():
    tree = random_tree(257, seed=11)
    flat = build_flat_it(tree, leaf_size=16, use_cache=False)
    lca = TreeLCA(tree)
    for i in range(flat.num_internal):
        p = flat.pivots[i]
        for side in (flat.left[i], flat.right[i]):
            assert side.ids[0] == p
            assert side.d[0] == 0.0
            # id_d is monotone (ids are emitted in ascending-distance order,
            # so the segment layout is the identity permutation)
            assert np.all(np.diff(side.id_d) >= 0)
            assert side.seg_starts[0] == 0
            ref = lca.distance(np.full(side.ids.size, p), side.ids)
            assert np.allclose(side.d[side.id_d], ref, atol=1e-9)
        both = set(flat.left[i].ids) & set(flat.right[i].ids)
        assert both == {int(p)}


def test_flat_it_cache_and_fingerprint():
    tree = random_tree(120, seed=2)
    clear_flat_cache()
    f1 = build_flat_it(tree, leaf_size=16)
    f2 = build_flat_it(tree, leaf_size=16)
    assert f1 is f2  # content-hash hit
    assert build_flat_it(tree, leaf_size=32) is not f1
    # an identical copy of the tree hits the same cache entry
    twin = type(tree)(tree.num_vertices, tree.edges_u.copy(),
                      tree.edges_v.copy(), tree.weights.copy())
    assert tree_fingerprint(twin) == tree_fingerprint(tree)
    assert build_flat_it(twin, leaf_size=16) is f1
    # different weights -> different key
    other = type(tree)(tree.num_vertices, tree.edges_u.copy(),
                       tree.edges_v.copy(), tree.weights * 2.0)
    assert tree_fingerprint(other) != tree_fingerprint(tree)
    clear_flat_cache()
    assert build_flat_it(tree, leaf_size=16) is not f1


def test_cache_keys_include_seed():
    """Regression: differently-seeded builds must never alias to the first
    build via the content-hash caches (the key used to omit `seed`)."""
    tree = random_tree(90, seed=1)
    clear_plan_cache()
    clear_flat_cache()
    p0 = ftfi.build(tree, leaf_size=16, seed=0)
    p1 = ftfi.build(tree, leaf_size=16, seed=1)
    assert p0 is not p1
    assert ftfi.build(tree, leaf_size=16, seed=0) is p0
    assert ftfi.build(tree, leaf_size=16, seed=1) is p1
    f0 = build_flat_it(tree, leaf_size=16, seed=0)
    f1 = build_flat_it(tree, leaf_size=16, seed=1)
    assert f0 is not f1
    assert build_flat_it(tree, leaf_size=16, seed=0) is f0
    # forest builds carry the seed in their key too
    from repro.core.itree_flat import build_flat_forest

    trees = [tree, random_tree(40, seed=2)]
    ff0 = build_flat_forest(trees, leaf_size=16, seed=0)
    ff1 = build_flat_forest(trees, leaf_size=16, seed=1)
    assert ff0 is not ff1
    assert build_flat_forest(trees, leaf_size=16, seed=0) is ff0


def test_plan_cache_amortizes_recompilation():
    tree = random_tree(150, seed=4)
    clear_plan_cache()
    clear_flat_cache()
    p1 = ftfi.build(tree, leaf_size=16)
    p2 = ftfi.build(tree, leaf_size=16)
    assert p1 is p2
    assert ftfi.build(tree, leaf_size=32) is not p1
    clear_plan_cache()
    assert ftfi.build(tree, leaf_size=16) is not p1


def test_plan_flat_index_arrays_consistent():
    tree = random_tree(200, seed=9)
    plan, _ = ftfi.build(tree, leaf_size=16, use_cache=False)
    n = tree.num_vertices
    # gather/scatter vertex ids are real vertices (padding-free by design)
    assert plan.src_gather.min() >= 0 and plan.src_gather.max() < n
    assert plan.tgt_scatter.min() >= 0 and plan.tgt_scatter.max() < n
    assert plan.src_seg.max() < plan.n_src_groups
    assert plan.tgt_gather.max() < plan.n_tgt_groups
    # each (node, direction) job contributes its non-pivot targets once:
    # total scatter size == sum over internal nodes of (kL-1) + (kR-1)
    assert plan.num_cross_jobs == 2 * plan.pivots.size
