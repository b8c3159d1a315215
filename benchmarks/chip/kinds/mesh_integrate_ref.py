"""Plain float64 reference of the tree-field integrate, independent of the
program: it imports nothing of `repro` and takes nothing the program made.

    out[i] = sum_j f(dist_T(i, j)) X[j]

The tree is the input: the MST of an icosphere, made here with numpy and
scipy, whose vertex normals (the unit sphere's, so the vertex positions)
are the field the integrate carries. Distances are exact path lengths
through the tree in float64: from a root by Dijkstra, and between any two
vertices through their deepest common ancestor. For f = exp(lam s), whose
value over a path is the product of its edges' values, every row of the
integrate comes from one pass up the rooted tree and one pass down.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import (breadth_first_order, dijkstra,
                                  minimum_spanning_tree)


def icosphere(subdivisions: int) -> tuple[np.ndarray, np.ndarray]:
    """(vertices (V, 3), faces (F, 3)) of the unit icosphere: the
    icosahedron with each triangle split in four, `subdivisions` times,
    and the new vertices pushed out to the sphere."""
    t = (1.0 + np.sqrt(5.0)) / 2.0
    verts = np.array([[-1, t, 0], [1, t, 0], [-1, -t, 0], [1, -t, 0],
                      [0, -1, t], [0, 1, t], [0, -1, -t], [0, 1, -t],
                      [t, 0, -1], [t, 0, 1], [-t, 0, -1], [-t, 0, 1]],
                     np.float64)
    verts /= np.linalg.norm(verts, axis=1, keepdims=True)
    faces = np.array([[0, 11, 5], [0, 5, 1], [0, 1, 7], [0, 7, 10],
                      [0, 10, 11], [1, 5, 9], [5, 11, 4], [11, 10, 2],
                      [10, 7, 6], [7, 1, 8], [3, 9, 4], [3, 4, 2],
                      [3, 2, 6], [3, 6, 8], [3, 8, 9], [4, 9, 5],
                      [2, 4, 11], [6, 2, 10], [8, 6, 7], [9, 8, 1]],
                     np.int64)
    for _ in range(subdivisions):
        F = faces.shape[0]
        e = np.sort(np.concatenate([faces[:, [0, 1]], faces[:, [1, 2]],
                                    faces[:, [2, 0]]]), axis=1)
        uniq, inv = np.unique(e, axis=0, return_inverse=True)
        mid = verts.shape[0] + inv.reshape(-1)
        new = (verts[uniq[:, 0]] + verts[uniq[:, 1]]) / 2.0
        verts = np.concatenate([verts, new / np.linalg.norm(
            new, axis=1, keepdims=True)])
        ab, bc, ca = mid[:F], mid[F:2 * F], mid[2 * F:]
        a, b, c = faces.T
        faces = np.concatenate([np.stack([a, ab, ca], 1),
                                np.stack([b, bc, ab], 1),
                                np.stack([c, ca, bc], 1),
                                np.stack([ab, bc, ca], 1)])
    return verts, faces


def mesh_tree(subdivisions: int) -> dict:
    """The MST of the icosphere's edge graph, edge weights the Euclidean
    edge lengths: {"n", "u", "v", "w"} with n - 1 edges, the vertex
    normals (n, 3)."""
    verts, faces = icosphere(subdivisions)
    n = verts.shape[0]
    e = np.unique(np.sort(np.concatenate([faces[:, [0, 1]], faces[:, [1, 2]],
                                          faces[:, [2, 0]]]), axis=1), axis=0)
    w = np.linalg.norm(verts[e[:, 0]] - verts[e[:, 1]], axis=1)
    mst = minimum_spanning_tree(coo_matrix((w, (e[:, 0], e[:, 1])),
                                           shape=(n, n))).tocoo()
    if mst.nnz != n - 1:
        raise ValueError(f"mesh graph is not connected: {mst.nnz} MST edges")
    tree = {"n": n, "u": mst.row.astype(np.int32),
            "v": mst.col.astype(np.int32), "w": mst.data.astype(np.float64),
            "normals": verts}
    return tree


def mean_edge(tree: dict) -> float:
    """The mean edge length of the tree: the unit of the integrands'
    length scales."""
    return float(np.mean(tree["w"]))


def _graph(tree: dict):
    n, u, v, w = tree["n"], tree["u"], tree["v"], tree["w"]
    return coo_matrix((np.concatenate([w, w]),
                       (np.concatenate([u, v]), np.concatenate([v, u]))),
                      shape=(n, n)).tocsr()


def rooted(tree: dict) -> dict:
    """The tree rooted at vertex 0, made once and kept in `tree`: each
    vertex's parent (the root its own), the length of the edge to it, its
    distance from the root, and the vertices grouped by their number of
    edges from the root."""
    if "rooted" in tree:
        return tree["rooted"]
    g = _graph(tree)
    order, pred = breadth_first_order(g, 0, directed=False,
                                      return_predecessors=True)
    parent = np.where(pred < 0, 0, pred).astype(np.int64)
    hops = np.zeros(tree["n"], np.int64)
    for x in order[1:]:  # breadth-first: a parent comes before its child
        hops[x] = hops[parent[x]] + 1
    up = np.asarray(g[order[1:], parent[order[1:]]]).ravel()
    pw = np.zeros(tree["n"])
    pw[order[1:]] = up
    root_dist = dijkstra(g, directed=False, indices=0)
    levels = np.split(order, np.cumsum(np.bincount(hops))[:-1])
    tree["rooted"] = {"parent": parent, "parent_w": pw,
                      "root_dist": root_dist, "levels": levels}
    return tree["rooted"]


def _distance_blocks(tree: dict, rows, block: int = 256):
    """Yield (slice of rows, (n, len) float64 distances to those rows),
    a block of rows at a time: dist(r, v) = root_dist[r] + root_dist[v] -
    2 root_dist[a], where a is the deepest vertex on both paths to the
    root, found by one pass down the levels."""
    t = rooted(tree)
    par, rd, levels = t["parent"], t["root_dist"], t["levels"]
    rows = np.asarray(rows, np.int64)
    for b0 in range(0, rows.size, block):
        r = rows[b0:b0 + block]
        k = np.arange(r.size)
        on_path = np.zeros((tree["n"], r.size), bool)  # v on r's root path
        cur = r.copy()
        for _ in range(len(levels)):
            on_path[cur, k] = True
            cur = par[cur]
        a = np.empty((tree["n"], r.size))
        a[levels[0]] = rd[levels[0], None]
        for lv in levels[1:]:
            a[lv] = np.where(on_path[lv], rd[lv, None], a[par[lv]])
        a *= -2.0
        a += rd[:, None]
        a += rd[r][None, :]
        yield slice(b0, b0 + r.size), a


def tree_distances(tree: dict, rows) -> np.ndarray:
    """(len(rows), n) float64 path distances through the tree."""
    return np.concatenate([a.T for _, a in _distance_blocks(tree, rows)])


def dijkstra_distances(tree: dict, rows) -> np.ndarray:
    """The same distances by Dijkstra from each row: slower, and kept as a
    second witness for the tests."""
    return dijkstra(_graph(tree), directed=True, indices=np.asarray(rows))


def exp_integrate(tree: dict, lam: float, X: np.ndarray) -> np.ndarray:
    """Every row of sum_j exp(lam dist(i, j)) X[j], in float64: sums over
    each subtree going up, then each vertex's parent's total with the
    vertex's own subtree taken out going down."""
    t = rooted(tree)
    par, levels = t["parent"], t["levels"]
    e = np.exp(lam * t["parent_w"])[:, None]
    up = np.array(X, np.float64)
    for lv in reversed(levels[1:]):
        np.add.at(up, par[lv], e[lv] * up[lv])
    out = np.empty_like(up)
    out[levels[0]] = up[levels[0]]
    for lv in levels[1:]:
        out[lv] = up[lv] + e[lv] * (out[par[lv]] - e[lv] * up[lv])
    return out


def resolve(f: dict, tree: dict) -> dict:
    """The traffic's integrand with its length scale, given in mean edge
    lengths of the tree (`length_edges`), made a number:
    exp(-s / l) for `exp`, 1 / (1 + (s / l)^2) for `rational`."""
    ell = float(f["length_edges"]) * mean_edge(tree)
    if f["family"] == "exp":
        return {"family": "exp", "lam": -1.0 / ell}
    if f["family"] == "rational":
        return {"family": "rational", "c": 1.0 / (ell * ell)}
    raise ValueError(f"unknown integrand family {f['family']!r}")


def integrand(f: dict):
    """A resolved integrand in float64 numpy; it overwrites its argument
    where `out` is given it."""
    if f["family"] == "exp":
        lam = float(f["lam"])

        def exp(s, out=None):
            s = np.multiply(s, lam, out=out)
            return np.exp(s, out=s)

        return exp
    if f["family"] == "rational":
        c = float(f["c"])

        def rational(s, out=None):
            s = np.multiply(s, s, out=out)
            s *= c
            s += 1.0
            return np.reciprocal(s, out=s)

        return rational
    raise ValueError(f"unknown integrand family {f['family']!r}")


def integrate_rows(tree: dict, f: dict, rows, fields: list) -> list:
    """(len(rows), d) float64 rows of the integrate of each field."""
    fn = integrand(f)
    out = [np.empty((len(rows), x.shape[1])) for x in fields]
    for sl, dist in _distance_blocks(tree, rows):
        F = fn(dist, out=dist)  # (n, block)
        for o, x in zip(out, fields):
            o[sl] = F.T @ np.asarray(x, np.float64)
    return out


def compare(err: np.ndarray, ref: np.ndarray) -> dict:
    """The numbers `correct` may compare, over (..., rows, d) errors and
    reference values: the largest entry error and the root-mean-square
    error, each relative to the reference's own scale (its largest entry
    and its root-mean-square); and quantiles (50th, 90th, 99th) of the
    row errors, each row's error norm over its reference norm, the latter
    floored at a hundredth of the reference rows' root-mean-square norm."""
    rn = np.linalg.norm(err, axis=-1).ravel()
    wn = np.linalg.norm(ref, axis=-1).ravel()
    row = rn / np.maximum(wn, 0.01 * np.sqrt(np.mean(wn * wn)))
    p50, p90, p99 = np.quantile(row, [0.5, 0.9, 0.99])
    return {"max_rel_err": float(np.max(np.abs(err)) / np.max(np.abs(ref))),
            "rms_rel_err": float(np.sqrt(np.sum(err * err)
                                         / np.sum(ref * ref))),
            "row_err_p50": float(p50), "row_err_p90": float(p90),
            "row_err_p99": float(p99)}


def dot_high(a, b):
    """float32 matmul at `high`: three bf16 passes (hi*hi + hi*lo + lo*hi,
    each product exact in float32, float32 sums), written out so that it
    computes the same on any device."""
    import jax.numpy as jnp

    def split(x):
        x = jnp.asarray(x, jnp.float32)
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def control_rows(tree: dict, f: dict, rows, fields: list) -> list:
    """The reference put in the program's place one precision below the
    configuration's float32 at `highest`: the rows of F X with F in float32
    and the product at `high`, as float64."""
    fn = integrand(f)
    out = [np.empty((len(rows), x.shape[1])) for x in fields]
    for sl, dist in _distance_blocks(tree, rows):
        F = fn(dist, out=dist).astype(np.float32).T
        for o, x in zip(out, fields):
            o[sl] = np.asarray(dot_high(F, x), np.float64)
    return out
