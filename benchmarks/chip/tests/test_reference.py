"""The mesh reference's own arithmetic against plainer witnesses: its tree
distances against Dijkstra's, and its every-row exp integrate and its
row blocks against the dense product f(D) X."""
import numpy as np
import pytest

import mesh_integrate_ref as ref


@pytest.fixture(scope="module")
def tree():
    return ref.mesh_tree(4)  # 2,562 vertices


def test_tree_distances_match_dijkstra(tree):
    rows = np.random.default_rng(0).choice(tree["n"], 300, replace=False)
    got = ref.tree_distances(tree, rows)
    want = ref.dijkstra_distances(tree, rows)
    assert got.shape == (300, tree["n"])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


@pytest.mark.parametrize("family", ["exp", "rational"])
def test_integrate_rows_match_the_dense_product(tree, family):
    f = ref.resolve({"family": family, "length_edges": 3}, tree)
    X = np.random.default_rng(1).standard_normal((tree["n"], 3))
    rows = np.arange(0, tree["n"], 7)
    dense = ref.integrand(f)(ref.dijkstra_distances(tree, rows)) @ X
    got, = ref.integrate_rows(tree, f, rows, [X])
    np.testing.assert_allclose(got, dense, rtol=1e-12, atol=1e-12)
    if family == "exp":
        every = ref.exp_integrate(tree, f["lam"], X)
        np.testing.assert_allclose(every[rows], dense, rtol=1e-10,
                                   atol=1e-12)


def test_length_scale_is_in_mean_edges(tree):
    h = ref.mean_edge(tree)
    assert ref.resolve({"family": "exp", "length_edges": 2},
                       tree)["lam"] == pytest.approx(-1 / (2 * h))
    assert ref.resolve({"family": "rational", "length_edges": 2},
                       tree)["c"] == pytest.approx(1 / (2 * h) ** 2)


def test_normals_are_unit_vertex_positions(tree):
    np.testing.assert_allclose(np.linalg.norm(tree["normals"], axis=1), 1.0)
