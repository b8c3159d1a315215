"""Topological masking: Algorithm 1, Toeplitz fastmult, cordial decode."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro import ftfi
from repro.core import masks as MK
from repro.core.toeplitz import (causal_toeplitz_matvec,
                                 symmetric_toeplitz_matvec, toeplitz_dense)


@settings(max_examples=15, deadline=None)
@given(L=st.integers(4, 96), d=st.integers(1, 5), seed=st.integers(0, 10**6),
       causal=st.booleans())
def test_toeplitz_fastmult_property(L, d, seed, causal):
    r = np.random.default_rng(seed)
    F = jnp.asarray(r.normal(size=L), jnp.float32)
    V = jnp.asarray(r.normal(size=(L, d)), jnp.float32)
    M = toeplitz_dense(F, L, causal=causal)
    ref = M @ V
    got = (causal_toeplitz_matvec if causal else symmetric_toeplitz_matvec)(F, V)
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4 * max(
        1.0, float(jnp.max(jnp.abs(ref))))


@pytest.mark.parametrize("g,coeffs", [("exp", [0.1, -0.4]),
                                      ("exp", [0.0, -0.2, -0.1]),
                                      ("identity", [1.0, 0.3, 0.05]),
                                      ("recip", [0.0, 1.0])])
def test_algorithm1_vs_bruteforce(g, coeffs, rng):
    L, d, m = 64, 8, 6
    qf = jnp.asarray(np.abs(rng.normal(size=(2, L, m))), jnp.float32)
    kf = jnp.asarray(np.abs(rng.normal(size=(2, L, m))), jnp.float32)
    V = jnp.asarray(rng.normal(size=(2, L, d)), jnp.float32)
    cs = jnp.asarray(coeffs, jnp.float32)
    fm = MK.make_sequence_fastmult(g, cs, L, causal=True, dist_scale=1 / L)
    got = MK.masked_linear_attention(qf, kf, V, fm)
    Fv = MK.sequence_mask_values(g, cs, L, 1 / L)
    mask = toeplitz_dense(Fv, L, causal=True)
    ref = MK.masked_attention_bruteforce(qf, kf, V, mask)
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


@pytest.mark.parametrize("g,coeffs", [("exp", [0.1, -0.4]),
                                      ("identity", [1.0, 0.3, 0.05])])
def test_cordial_decode_equals_prefill(g, coeffs, rng):
    L, d, m = 48, 4, 6
    qf = jnp.asarray(np.abs(rng.normal(size=(2, L, m))), jnp.float32)
    kf = jnp.asarray(np.abs(rng.normal(size=(2, L, m))), jnp.float32)
    V = jnp.asarray(rng.normal(size=(2, L, d)), jnp.float32)
    cs = np.asarray(coeffs, np.float32)
    Fv = MK.sequence_mask_values(g, jnp.asarray(cs), L, 1 / L)
    ref = MK.masked_attention_bruteforce(qf, kf, V,
                                         toeplitz_dense(Fv, L, causal=True))
    dec = MK.cordial_decomposition(g, cs, dist_scale=1 / L)
    state = MK.decode_state_init(dec, m, d, batch_shape=(2,))
    outs = []
    for t in range(L):
        state = MK.decode_state_update(dec, state, t, kf[:, t], V[:, t])
        outs.append(MK.decode_state_read(dec, state, t, qf[:, t]))
    got = jnp.stack(outs, axis=1)
    assert float(jnp.max(jnp.abs(got - ref))) < 2e-4


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10**6), L=st.integers(3, 40),
       dmode=st.integers(0, 3), perhead=st.booleans())
def test_cordial_decode_property(seed, L, dmode, perhead):
    """Property (satellite): decode_state_init/update/read reproduces
    masked_attention_bruteforce TOKEN-BY-TOKEN for every exactly-separable
    family — g="exp" with deg <= 1 and g="identity" polynomials — with both
    synced and per-head (asynced) coefficient batches."""
    g, T = [("exp", 0), ("exp", 1), ("identity", 1), ("identity", 2)][dmode]
    r = np.random.default_rng(seed)
    H, m, d = 2, 3, 4
    shape = (H, T + 1) if perhead else (T + 1,)
    coeffs = r.uniform(-0.6, 0.6, size=shape).astype(np.float32)
    # keep f positive (identity masks must stay away from zero denominators)
    coeffs[..., 0] = r.uniform(1.5, 2.5, size=shape[:-1])
    dist_scale = 1.0 / L
    qf = jnp.asarray(np.abs(r.normal(size=(H, L, m))), jnp.float32)
    kf = jnp.asarray(np.abs(r.normal(size=(H, L, m))), jnp.float32)
    V = jnp.asarray(r.normal(size=(H, L, d)), jnp.float32)

    # per-head dense causal mask oracle
    cs = coeffs if perhead else np.broadcast_to(coeffs, (H, T + 1))
    diff = (np.arange(L)[:, None] - np.arange(L)[None, :]) * dist_scale
    z = np.zeros((H, L, L))
    for t in range(T, -1, -1):
        z = z * diff[None] + cs[:, t][:, None, None]
    f = np.exp(z) if g == "exp" else z
    mask = jnp.asarray(f * np.tril(np.ones((L, L))), jnp.float32)
    ref = MK.masked_attention_bruteforce(qf, kf, V, mask)

    dec = MK.cordial_decomposition(g, coeffs, dist_scale=dist_scale)
    state = MK.decode_state_init(dec, m, d, batch_shape=(H,))
    for t in range(L):
        state = MK.decode_state_update(dec, state, t, kf[:, t], V[:, t])
        out = MK.decode_state_read(dec, state, t, qf[:, t])
        step_ref = ref[:, t]
        tol = 5e-4 * max(1.0, float(jnp.max(jnp.abs(step_ref))))
        assert float(jnp.max(jnp.abs(out - step_ref))) < tol, (g, T, t)


def test_chebyshev_separable_decode(rng):
    """Non-separable mask (g=exp, degree 2): the Chebyshev rank-R expansion
    decodes streaming with spectral accuracy (beyond-paper, DESIGN §3)."""
    from repro.configs.base import get_smoke_config
    from repro.models import attention as A

    cfg = get_smoke_config("llama3_2_1b").replace(
        dtype="float32", attention_variant="topo", topo_degree=2,
        topo_dist_scale=1.0 / 48, topo_synced=True)
    coeffs = jnp.asarray(np.array([[0.1, -1.2, -0.7]] * cfg.num_heads),
                         jnp.float32)
    L = 48
    alpha, beta, R = A.topo_decomposition(cfg, coeffs, L, rank=24)
    # reconstruct f(i-j) from the decomposition and compare
    from repro.core.masks import GS
    ii = np.arange(L, dtype=np.float32)
    errs = []
    for i in range(0, L, 7):
        for j in range(0, i + 1, 5):
            a = alpha(jnp.asarray(float(i)))
            b = beta(jnp.asarray(float(j)))
            approx_v = float(jnp.sum(a[0] * b[0]))
            z = (i - j) * cfg.topo_dist_scale
            exact = float(np.exp(0.1 - 1.2 * z - 0.7 * z * z))
            errs.append(abs(approx_v - exact) / max(abs(exact), 1e-9))
    assert max(errs) < 1e-4


def _pin_path(monkeypatch, path):
    """Steer make_tree_fastmult: N_DENSE = 0 forces the plan executor."""
    if path == "plan":
        monkeypatch.setattr(MK, "N_DENSE", 0)


def _path_counts():
    from repro.analysis import trace_guard

    return {p: trace_guard.compiles(f"masks.tree_fastmult:{p}")
            for p in ("dense", "plan")}


@pytest.mark.parametrize("path", ["dense", "plan"])
@pytest.mark.parametrize("backend", ["plan", "pallas"])
def test_grid_mask_fastmult(backend, path, rng, monkeypatch):
    """ViT grid masks over the grid plan == dense mask multiply, with
    batch/head axes folded by the tree fastmult factory, on both of its
    paths (the dense product by f(D), and the plan executor) and both
    executor backends."""
    from repro.graphs.graph import grid_graph
    from repro.graphs.mst import minimum_spanning_tree
    from repro.graphs.traverse import tree_all_pairs

    _pin_path(monkeypatch, path)
    g = grid_graph(6, 6)
    mst = minimum_spanning_tree(g)
    plan = ftfi.build(mst, leaf_size=8)
    D = tree_all_pairs(mst)
    coeffs = jnp.asarray([0.0, -0.3], jnp.float32)
    X = jnp.asarray(rng.normal(size=(2, 36, 5)), jnp.float32)  # batched field
    ref = np.einsum("lk,bkd->bld", np.exp(-0.3 * D), np.asarray(X))
    n0 = _path_counts()
    fm = MK.make_tree_fastmult(plan, "exp", coeffs, dist_scale=1.0,
                               backend=backend, tree=mst)
    n1 = _path_counts()
    assert n1[path] == n0[path] + 1
    got = np.asarray(fm(X))
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-5


def test_grid14_dense_and_plan_agree_with_f64_reference(monkeypatch):
    """The cell's tree (the 14 x 14 grid MST, n = 196) under a degree-2 exp
    mask: the dense product and the plan executor give the same outputs
    and the same gradients w.r.t. the mask coefficients and the field, and
    both match a float64 dense reference to 1e-5 relative."""
    from repro.graphs.graph import grid_graph
    from repro.graphs.mst import minimum_spanning_tree
    from repro.graphs.traverse import tree_all_pairs

    mst = minimum_spanning_tree(grid_graph(14, 14))
    plan = ftfi.build(mst, leaf_size=16)
    scale = 0.0625
    c = np.asarray([0.1, -0.5, -0.2])
    r = np.random.default_rng(14)
    X = r.normal(size=(2, 196, 6))
    G = r.normal(size=(2, 196, 6))

    def loss(c, X):
        fm = MK.make_tree_fastmult(plan, "exp", c, scale, tree=mst)
        return jnp.sum(fm(X) * G), fm(X)

    def run():
        (_, out), (dc, dX) = jax.value_and_grad(loss, argnums=(0, 1),
                                                has_aux=True)(
            jnp.asarray(c, jnp.float32), jnp.asarray(X, jnp.float32))
        return [np.asarray(a, np.float64) for a in (out, dc, dX)]

    n0 = _path_counts()
    dense = run()
    monkeypatch.setattr(MK, "N_DENSE", 0)
    plan = run()
    n1 = _path_counts()
    assert n1["dense"] > n0["dense"] and n1["plan"] > n0["plan"]

    S = tree_all_pairs(mst) * scale
    M = np.exp(c[0] + c[1] * S + c[2] * S * S)
    GX = np.einsum("bic,bjc->ij", G, X)
    ref = [np.einsum("ij,bjc->bic", M, X),
           np.asarray([np.sum(GX * M * S ** t) for t in range(3)]),
           np.einsum("ij,bic->bjc", M, G)]

    def rel(a, b):
        return np.max(np.abs(a - b)) / np.max(np.abs(b))

    for name, d, p, want in zip(("out", "d_coeffs", "d_X"), dense, plan,
                                ref):
        assert rel(d, want) < 1e-5, name
        assert rel(p, want) < 1e-5, name
        assert rel(d, p) < 1e-5, name


def _counter_case(case, monkeypatch):
    """(make the closure, the path it must take) for each kind of plan."""
    from repro.graphs.graph import Forest, random_tree

    coeffs = np.asarray([0.1, -0.4], np.float32)
    if case == "single_tree":
        tree = random_tree(40, seed=2)
        plan = ftfi.build(tree, leaf_size=8)
        return (lambda: MK.make_tree_fastmult(plan, "exp", coeffs,
                                              tree=tree)), "dense"
    if case == "forest":
        forest = Forest([random_tree(20 + 3 * i, seed=i) for i in range(3)])
        plan = ftfi.build(forest, leaf_size=8)
        return (lambda: MK.make_forest_fastmult(plan, forest, "exp",
                                                coeffs)), "plan"
    if case == "traced_params":
        tree = random_tree(30, seed=1)
        spec, _ = ftfi.build(tree, leaf_size=8, reweightable=True)

        def make():
            @jax.jit
            def use(w):
                fm = MK.make_tree_fastmult((spec, ftfi.reweight(spec, w)),
                                           "exp", coeffs)
                return fm(jnp.ones((spec.n, 2), jnp.float32))

            return use(jnp.asarray(tree.weights, jnp.float32))

        return make, "plan"
    assert case == "above_n_dense"
    monkeypatch.setattr(MK, "N_DENSE", 32)
    plan = ftfi.build(random_tree(40, seed=2), leaf_size=8)
    return (lambda: MK.make_tree_fastmult(plan, "exp", coeffs)), "plan"


@pytest.mark.parametrize("case", ["single_tree", "forest", "traced_params",
                                  "above_n_dense"])
def test_tree_fastmult_path_counter(case, monkeypatch):
    """Each bound closure records which path it took: dense for a single
    small tree, the plan executor for a forest, for params traced under an
    enclosing jit, and for a tree above N_DENSE."""
    make, want = _counter_case(case, monkeypatch)
    other = "plan" if want == "dense" else "dense"
    n0 = _path_counts()
    make()
    n1 = _path_counts()
    assert n1[want] == n0[want] + 1
    assert n1[other] == n0[other]


@pytest.mark.parametrize("source", ["pair", "from_plan", "reweighted",
                                    "tree_weight"])
def test_dense_distances_from_plan(source, rng, tmp_path):
    """Without a tree to read, the dense path derives D from the plan
    itself (the executor with f(s) = s on the identity field): the closure
    matches the plan executor's own mask multiply, a per-tree output weight
    included. "from_plan" is a pair loaded from a `save_plan` artifact."""
    from repro.graphs.graph import random_tree

    tree = random_tree(50, seed=5)
    spec, params = ftfi.build(tree, leaf_size=8, reweightable=True)
    if source == "reweighted":
        params = ftfi.reweight(spec, rng.uniform(
            0.3, 1.5, size=tree.num_edges).astype(np.float32))
    elif source == "tree_weight":
        params = ftfi.reweight(spec, np.asarray(tree.weights, np.float32),
                               tree_w=np.asarray([1.7], np.float32))
    handle = (spec, params)
    if source == "from_plan":
        ftfi.save_plan(tmp_path / "plan.npz", spec, params)
        handle = spec, params = ftfi.load_plan(tmp_path / "plan.npz")
    coeffs = np.asarray([0.2, -0.3, -0.05], np.float32)
    X = jnp.asarray(rng.normal(size=(3, 50, 4)), jnp.float32)
    n0 = _path_counts()
    got = MK.make_tree_fastmult(handle, "exp", coeffs, 0.5)(X)
    assert _path_counts()["dense"] == n0["dense"] + 1
    f = MK.mask_f("exp", coeffs, 0.5)
    want = np.stack([np.asarray(ftfi.apply(spec, params, f, x)) for x in X])
    assert np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)) \
        < 1e-5


def test_tree_fastmult_pallas_pair_matches_kernel(rng, monkeypatch):
    """The fdist_matvec kernel runs the mask's family on a pair: the
    functional API on "pallas" with the mask as `ExpQuadratic` fires its
    VPU counter. `make_tree_fastmult` on the same pair with
    backend="pallas" keeps the callable mask on the plan engines (the
    kernel's counter stays still) and gives the same product."""
    from repro.analysis import trace_guard
    from repro.core.cordial import ExpQuadratic
    from repro.graphs.graph import random_tree

    monkeypatch.setattr(MK, "N_DENSE", 0)
    jax.clear_caches()  # the kernel's jit traces a shape once a process
    spec, params = plan = ftfi.build(random_tree(60, seed=3), leaf_size=8)
    c0, c1, c2 = 0.1, -0.5, -0.2
    s = 0.25
    X = jnp.asarray(rng.normal(size=(60, 6)), jnp.float32)
    vpu = trace_guard.compiles("fdist_matvec:vpu")
    want = ftfi.fastmult(spec, ExpQuadratic(c2 * s * s, c1 * s, c0),
                         backend="pallas")(params, X)
    assert trace_guard.compiles("fdist_matvec:vpu") > vpu
    vpu = trace_guard.compiles("fdist_matvec:vpu")
    coeffs = np.asarray([c0, c1, c2], np.float32)
    got = MK.make_tree_fastmult(plan, "exp", coeffs, s, backend="pallas")(
        jnp.moveaxis(X.reshape(60, 3, 2), -1, 0))
    assert trace_guard.compiles("fdist_matvec:vpu") == vpu
    got = jnp.moveaxis(got, 0, -1).reshape(60, 6)
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) \
        / np.max(np.abs(np.asarray(want))) < 1e-5


def test_grid14_dense_distances_are_tree_all_pairs(rng, monkeypatch):
    """The TopoViT grid plan with its grid MST: the dense path's D is
    bitwise `tree_all_pairs` of the 14 x 14 grid MST in f32 (never derived
    from the plan), and the closure is the product by f(D)."""
    from repro.configs.base import get_smoke_config
    from repro.graphs.graph import grid_graph
    from repro.graphs.mst import minimum_spanning_tree
    from repro.graphs.traverse import tree_all_pairs
    from repro.models import vit

    cfg = get_smoke_config("topovit_b16").replace(num_prefix_embeddings=196)
    plan = vit.build_grid_integrator(cfg)
    D = np.asarray(tree_all_pairs(minimum_spanning_tree(grid_graph(14, 14))),
                   np.float32)
    got_D = MK.tree_distances(vit.grid_mst(14))
    assert got_D.dtype == np.float32
    np.testing.assert_array_equal(got_D, D)

    def no_plan_distances(*a):
        raise AssertionError("the grid D came from the plan, not the MST")

    monkeypatch.setattr(MK, "_plan_distances", no_plan_distances)
    coeffs = np.asarray([0.1, -0.5, -0.2], np.float32)
    X = jnp.asarray(rng.normal(size=(2, 196, 4)), jnp.float32)
    got = MK.make_tree_fastmult(plan, "exp", coeffs, 0.0625,
                                tree=vit.grid_mst(14))(X)
    M = MK.mask_f("exp", coeffs, 0.0625)(jnp.asarray(D))
    want = jnp.einsum("ij,...jc->...ic", M, X,
                      precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
