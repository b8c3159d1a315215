"""Paper Fig. 4: mesh vertex-normal interpolation — preprocessing time vs
cosine similarity for FTFI / BTFI / random-spanning-tree baselines."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timeit
from repro.core import FTFI, Rational
from repro.core.integrate import BTFI
from repro.graphs.graph import WeightedTree
from repro.graphs.meshes import icosphere, mesh_graph, torus_mesh, vertex_normals
from repro.graphs.mst import minimum_spanning_tree
from repro.graphs.traverse import tree_bfs_order


def random_spanning_tree(g, seed=0):
    """Random-weight spanning tree (low-stretch baseline stand-in)."""
    rng = np.random.default_rng(seed)
    from repro.graphs.graph import Graph

    g2 = Graph(g.num_vertices, g.edges_u, g.edges_v,
               rng.uniform(0.1, 1.0, g.num_edges))
    t = minimum_spanning_tree(g2)
    # restore true edge lengths on the chosen edges
    key = {(min(u, v), max(u, v)): w for u, v, w in
           zip(g.edges_u, g.edges_v, g.weights)}
    w = np.array([key[(min(u, v), max(u, v))]
                  for u, v in zip(t.edges_u, t.edges_v)])
    return WeightedTree(t.num_vertices, t.edges_u, t.edges_v, w)


def _interpolate(integrator, fn, normals, known):
    F = np.where(known[:, None], normals, 0.0)
    pred = integrator.integrate(fn, F)
    pred /= np.maximum(np.linalg.norm(pred, axis=1, keepdims=True), 1e-12)
    cos = np.sum(pred[~known] * normals[~known], axis=1)
    return float(np.mean(cos))


def run(meshes=None, lambdas=(1.0, 4.0, 16.0)):
    meshes = meshes or [("ico3", *icosphere(3)), ("ico4", *icosphere(4)),
                        ("torus", *torus_mesh(48, 24))]
    rng = np.random.default_rng(0)
    results = []
    for name, verts, faces in meshes:
        normals = vertex_normals(verts, faces)
        g = mesh_graph(verts, faces)
        n = verts.shape[0]
        known = rng.random(n) < 0.2
        for method, mk in [
            ("ftfi_mst", lambda: FTFI(minimum_spanning_tree(g), leaf_size=128)),
            ("btfi_mst", lambda: BTFI(minimum_spanning_tree(g),
                                      dtype=np.float32)),
            ("ftfi_rst", lambda: FTFI(random_spanning_tree(g), leaf_size=128)),
        ]:
            t_pre = timeit(mk, repeat=1, warmup=0)
            integ = mk()
            best = -1.0
            for lam in lambdas:
                fn = Rational((1.0,), (1.0, 0.0, lam))
                best = max(best, _interpolate(integ, fn, normals, known))
            emit(f"fig4/{name}/n{n}/{method}", t_pre, f"cos={best:.4f}")
            results.append((name, method, t_pre, best))
        # FRT tree baseline (paper's Fig-4 comparison; O(N^2) preprocessing)
        if n <= 3000:
            import time as _t

            from repro.core.integrate import FTFI as _FTFI
            from repro.graphs.frt import frt_tree
            from repro.graphs.traverse import graph_all_pairs

            t0 = _t.perf_counter()
            Dg = graph_all_pairs(g)  # seed-independent; shared with the
            ft, leaf = frt_tree(g, seed=0, D=Dg)  # forest row below
            integ = _FTFI(ft, leaf_size=128)
            t_pre = _t.perf_counter() - t0
            best = -1.0
            for lam in lambdas:
                fn = Rational((1.0,), (1.0, 0.0, lam))
                F = np.where(known[:, None], normals, 0.0)
                Ffull = np.zeros((ft.num_vertices, 3))
                Ffull[leaf] = F
                pred = integ.integrate(fn, Ffull)[leaf]
                pred /= np.maximum(np.linalg.norm(pred, axis=1, keepdims=True),
                                   1e-12)
                cos = float(np.mean(np.sum(pred[~known] * normals[~known], 1)))
                best = max(best, cos)
            emit(f"fig4/{name}/n{n}/ftfi_frt", t_pre, f"cos={best:.4f}")
            results.append((name, "ftfi_frt", t_pre, best))
            # FRT FOREST: Fig 4's expectation estimate — k sampled trees as
            # ONE fused forest integration, per-tree outputs averaged.
            # Forest construction is hoisted out of the lambda sweep (it is
            # lambda-independent), mirroring the single-tree row above.
            from repro import ftfi
            from repro.graphs.frt import forest_leaf_integrate, frt_forest

            k = 4
            t0 = _t.perf_counter()
            forest, leaf = frt_forest(g, k, seed=0, D=Dg)
            finteg = ftfi.build(forest, leaf_size=128)
            t_forest_pre = _t.perf_counter() - t0
            best = -1.0
            for lam in lambdas:
                fn = Rational((1.0,), (1.0, 0.0, lam))
                F = np.where(known[:, None], normals, 0.0)
                pred = forest_leaf_integrate(forest, leaf, finteg, fn, F)
                pred /= np.maximum(np.linalg.norm(pred, axis=1, keepdims=True),
                                   1e-12)
                cos = float(np.mean(np.sum(pred[~known] * normals[~known], 1)))
                best = max(best, cos)
            emit(f"fig4/{name}/n{n}/ftfi_frt_forest{k}", t_forest_pre,
                 f"cos={best:.4f}")
            results.append((name, f"ftfi_frt_forest{k}", t_forest_pre, best))
    return results


if __name__ == "__main__":
    run()
