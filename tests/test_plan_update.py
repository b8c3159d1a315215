"""Incremental plan updates (`ftfi.update_plan`) vs full rebuilds.

The oracle is exhaustive: after any sequence of insert_leaf / delete_leaf /
reweight ops, integrating through the patched plan must match a
from-scratch `ftfi.build` of the edited tree/forest (ghost rows excluded:
they must be exactly zero and ignore their input). Differences are f32-eps
scale only (the distance derivations sum in different orders before the
float32 executor), so the comparisons use a small relative tolerance.
"""
import numpy as np
import pytest

from repro import ftfi
from repro.core import cordial as C
from repro.graphs.graph import Forest, WeightedTree, random_tree

FNS = [C.Exponential(-0.5, 1.1), C.Polynomial((0.4, -0.15, 0.05))]
TOL = 2e-5


def _rel_err(got, ref):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(ref)))
                 / max(np.max(np.abs(np.asarray(ref))), 1e-12))


class _Model:
    """Pure-python mirror of update_plan's id/edge semantics, used to build
    the rebuild oracle: per-tree local edge lists (inserts append, deletes
    remove), per-tree sizes, per-tree ghost sets. Global id of local vertex
    v of tree t is offset_t + v; an insert into tree t appends local id
    size_t (shifting later trees' global ids up by one)."""

    def __init__(self, trees):
        self.sizes = [t.num_vertices for t in trees]
        self.edges = [[(int(u), int(v), float(w)) for u, v, w in
                       zip(t.edges_u, t.edges_v, t.weights)] for t in trees]
        self.ghosts = [set() for _ in trees]

    def offsets(self):
        return np.concatenate([[0], np.cumsum(self.sizes)])

    def locate(self, g):
        off = self.offsets()
        t = int(np.searchsorted(off, g, side="right")) - 1
        return t, int(g - off[t])

    def insert(self, parent_g, w):
        t, p = self.locate(parent_g)
        v = self.sizes[t]
        self.edges[t].append((p, v, float(w)))
        self.sizes[t] += 1
        return int(self.offsets()[t]) + v  # new global id

    def degree(self, t, v):
        return sum(v in (u, x) for u, x, _ in self.edges[t])

    def delete(self, g):
        t, v = self.locate(g)
        assert self.degree(t, v) == 1 and v != 0
        self.edges[t] = [e for e in self.edges[t] if v not in e[:2]]
        self.ghosts[t].add(v)

    def reweight(self, rng):
        w = rng.uniform(0.1, 2.0, sum(len(e) for e in self.edges))
        i = 0
        for t in range(len(self.edges)):
            self.edges[t] = [(u, v, float(w[i + j]))
                             for j, (u, v, _) in enumerate(self.edges[t])]
            i += len(self.edges[t])
        return w

    def live_leaves(self):
        """Global ids of deletable vertices: degree 1, not the tree root."""
        out = []
        off = self.offsets()
        for t in range(len(self.edges)):
            deg = {}
            for u, v, _ in self.edges[t]:
                deg[u] = deg.get(u, 0) + 1
                deg[v] = deg.get(v, 0) + 1
            out += [int(off[t]) + v for v, d in deg.items()
                    if d == 1 and v != 0 and v not in self.ghosts[t]]
        return out

    def live_vertices(self):
        off = self.offsets()
        return [int(off[t]) + v for t in range(len(self.sizes))
                for v in range(self.sizes[t]) if v not in self.ghosts[t]]

    def rebuild(self):
        """(tree_or_forest, live_global_rows): compacted rebuild oracle."""
        trees, rows = [], []
        off = self.offsets()
        for t in range(len(self.sizes)):
            live = [v for v in range(self.sizes[t])
                    if v not in self.ghosts[t]]
            relab = {v: i for i, v in enumerate(live)}
            eu = [relab[u] for u, v, _ in self.edges[t]]
            ev = [relab[v] for _, v, _ in self.edges[t]]
            w = [x for _, _, x in self.edges[t]]
            trees.append(WeightedTree(len(live), eu, ev, w))
            rows += [int(off[t]) + v for v in live]
        obj = trees[0] if len(trees) == 1 else Forest(trees)
        return obj, np.asarray(rows)


def _apply_rows(spec, params, fn, X):
    return np.asarray(ftfi.apply(spec, params, fn, X))


def _check_vs_rebuild(spec, params, model, rng, label):
    obj, rows = model.rebuild()
    rspec, rparams = ftfi.build(obj, leaf_size=8, reweightable=True)
    X = rng.normal(size=(spec.n, 3)).astype(np.float32)
    for fn in FNS:
        got = _apply_rows(spec, params, fn, X)
        ref = _apply_rows(rspec, rparams, fn, X[rows])
        assert _rel_err(got[rows], ref) < TOL, label
        # ghost rows produce exactly zero output
        ghost_rows = np.setdiff1d(np.arange(spec.n), rows)
        if ghost_rows.size:
            assert float(np.max(np.abs(got[ghost_rows]))) == 0.0, label
            # ...and their input is ignored
            X2 = X.copy()
            X2[ghost_rows] = 1e6
            got2 = _apply_rows(spec, params, fn, X2)
            assert _rel_err(got2[rows], ref) < TOL, label


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_single_ops_match_rebuild_tree(seed):
    rng = np.random.default_rng(seed)
    tree = random_tree(48 + 11 * seed, seed=seed)
    spec0, pp0 = ftfi.build(tree, leaf_size=8, reweightable=True)
    model0 = _Model([tree])

    # insert
    model = _Model([tree])
    parent = int(rng.choice(model.live_vertices()))
    model.insert(parent, 0.7)
    s, p = ftfi.update_plan(spec0, pp0, [("insert_leaf", parent, 0.7)])
    _check_vs_rebuild(s, p, model, rng, "insert")

    # delete
    model = _Model([tree])
    leaf = int(rng.choice(model0.live_leaves()))
    model.delete(leaf)
    s, p = ftfi.update_plan(spec0, pp0, [("delete_leaf", leaf)])
    _check_vs_rebuild(s, p, model, rng, "delete")

    # reweight
    model = _Model([tree])
    w = model.reweight(rng)
    s, p = ftfi.update_plan(spec0, pp0, [("reweight", w)])
    _check_vs_rebuild(s, p, model, rng, "reweight")


@pytest.mark.parametrize("forest,seed", [(False, 3), (False, 4),
                                         (True, 5), (True, 6)])
def test_random_op_sweep_matches_rebuild(forest, seed):
    """Property-style sweep: a random mixed sequence of ops, applied both
    one-at-a-time (chained update_plan generations) and as one batch, must
    match the compacted rebuild — on trees AND forests."""
    rng = np.random.default_rng(seed)
    if forest:
        trees = [random_tree(int(s), seed=seed * 10 + i)
                 for i, s in enumerate(rng.integers(10, 30, size=4))]
    else:
        trees = [random_tree(40, seed=seed)]
    obj = trees[0] if len(trees) == 1 else Forest(trees)
    spec, pp = ftfi.build(obj, leaf_size=8, reweightable=True)
    model = _Model(trees)
    ops = []
    for _ in range(8):
        kind = rng.choice(["insert", "insert", "delete", "reweight"])
        if kind == "insert":
            parent = int(rng.choice(model.live_vertices()))
            w = float(rng.uniform(0.2, 1.5))
            model.insert(parent, w)
            op = ("insert_leaf", parent, w)
        elif kind == "delete":
            leaves = model.live_leaves()
            if not leaves:
                continue
            v = int(rng.choice(leaves))
            model.delete(v)
            op = ("delete_leaf", v)
        else:
            op = ("reweight", model.reweight(rng))
        ops.append(op)
        # chained: each op patches the previous generation
        spec, pp = ftfi.update_plan(spec, pp, [op])
    _check_vs_rebuild(spec, pp, model, rng, f"chained seed={seed}")

    # batch: all ops in one update_plan call on the original plan. The
    # op-chained fingerprint is call-batching invariant; the content digest
    # is NOT asserted equal because masked (harmless) slots may carry
    # different garbage depending on when a mid-sequence reweight re-derived
    # the distance tables.
    spec0, pp0 = ftfi.build(obj, leaf_size=8, reweightable=True)
    sb, pb = ftfi.update_plan(spec0, pp0, ops)
    assert sb.fingerprint == spec.fingerprint
    _check_vs_rebuild(sb, pb, model, rng, f"batch seed={seed}")


def test_updated_plan_runs_on_pallas_backend():
    tree = random_tree(40, seed=11)
    spec, pp = ftfi.build(tree, leaf_size=8, reweightable=True)
    s, p = ftfi.update_plan(spec, pp, [("insert_leaf", 7, 0.9),
                                       ("delete_leaf", 39)])
    rng = np.random.default_rng(0)
    X = rng.normal(size=(s.n, 3)).astype(np.float32)
    fn = C.Exponential(-0.4)
    ref = _apply_rows(s, p, fn, X)
    assert ftfi.describe(s, fn, "pallas")["cross_engine"] == "fdist_matvec:exp"
    got = np.asarray(ftfi.apply(s, p, fn, X, backend="pallas"))
    assert _rel_err(got, ref) < TOL


def test_update_preserves_tree_w_and_chains_fingerprint():
    tree = random_tree(30, seed=2)
    spec, pp = ftfi.build(tree, leaf_size=8, reweightable=True)
    ops = [("insert_leaf", 5, 0.8), ("delete_leaf", 29)]
    s1, p1 = ftfi.update_plan(spec, pp, ops)
    s2, p2 = ftfi.update_plan(spec, pp, ops)
    # deterministic: identical edit histories -> identical provenance AND
    # identical content digest
    assert s1.fingerprint == s2.fingerprint
    assert s1.fingerprint != spec.fingerprint
    assert s1.digest == s2.digest
    assert p1.tree_w is pp.tree_w or np.array_equal(
        np.asarray(p1.tree_w), np.asarray(pp.tree_w))


def test_update_error_cases():
    tree = random_tree(30, seed=8)
    spec, pp = ftfi.build(tree, leaf_size=8, reweightable=True)
    model = _Model([tree])
    leaf = model.live_leaves()[0]

    # non-reweightable plans carry no update tables
    s0, p0 = ftfi.build(tree, leaf_size=8)
    with pytest.raises(ValueError, match="reweightable"):
        ftfi.update_plan(s0, p0, [("insert_leaf", 0, 1.0)])

    with pytest.raises(ValueError, match="degree"):
        # vertex 0 is the BFS root: never degree-1-deletable in these trees,
        # and internal vertices are rejected the same way
        internal = next(v for v in range(30)
                        if model.degree(0, v) > 1)
        ftfi.update_plan(spec, pp, [("delete_leaf", internal)])
    with pytest.raises(ValueError, match="out of range"):
        ftfi.update_plan(spec, pp, [("insert_leaf", 30, 1.0)])
    with pytest.raises(ValueError, match="already deleted"):
        ftfi.update_plan(spec, pp, [("delete_leaf", leaf),
                                    ("delete_leaf", leaf)])
    with pytest.raises(ValueError, match="was deleted"):
        ftfi.update_plan(spec, pp, [("delete_leaf", leaf),
                                    ("insert_leaf", leaf, 1.0)])
    with pytest.raises(ValueError, match="edge weights"):
        ftfi.update_plan(spec, pp, [("reweight", np.ones(7))])
    with pytest.raises(ValueError, match="unknown update op"):
        ftfi.update_plan(spec, pp, [("frobnicate", 3)])


def test_deleting_all_but_root_leaves_zero_plan():
    """Degenerate stress: peel a small tree down to its root; every output
    row except the root must be exactly zero, the root row must equal the
    single-vertex integral f(0) * x."""
    tree = random_tree(10, seed=13)
    spec, pp = ftfi.build(tree, leaf_size=4, reweightable=True)
    model = _Model([tree])
    while True:
        leaves = model.live_leaves()
        if not leaves:
            break
        v = leaves[0]
        model.delete(v)
        spec, pp = ftfi.update_plan(spec, pp, [("delete_leaf", v)])
    assert sorted(model.live_vertices()) == [0]
    fn = C.Exponential(-0.3, 2.0)
    X = np.ones((spec.n, 2), np.float32)
    out = _apply_rows(spec, pp, fn, X)
    np.testing.assert_allclose(out[0], fn.f0 * X[0], rtol=1e-6)
    assert float(np.max(np.abs(out[1:]))) == 0.0
