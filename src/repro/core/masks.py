"""Topological RPE masks for linear attention (paper Sec 4.4 + Alg. 1, App. C).

The mask is M = [f(dist(i,j))] with f = g(sum_t a_t x^t) and (a_t) learnable —
**3 extra scalars** per layer (synced) or per head (asynced). FastMult_M:
  - sequences (LM archs): Toeplitz FFT, exact for any f (core.toeplitz);
  - grids/graphs (ViT):   IT-plan executor, exact engines (core.integrate);
    on a single tree of at most N_DENSE vertices, one dense f32 product by
    f(D) over the exact all-pairs distances (make_tree_fastmult);
  - many graphs at once:  make_forest_fastmult over a packed Forest — each
    request's own mask applied block-diagonally in ONE fused dispatch.

Decode: for separable f (g=exp & t<=1, or g=identity polynomial), the cross
term f(i-j) = sum_r alpha_r(i) beta_r(j) splits, so masked linear attention
admits an O(1)-per-token recurrent state (beyond-paper; DESIGN §3).
"""
from __future__ import annotations

import dataclasses
import math
import weakref
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.analysis import trace_guard
from repro.core.lru import BoundedLRU
from repro.core.toeplitz import causal_toeplitz_matvec, symmetric_toeplitz_matvec


# ----------------------------------------------------------------------------
# learnable f
# ----------------------------------------------------------------------------

GS = {
    "exp": lambda z: jnp.exp(z),
    "recip": lambda z: 1.0 / (1.0 + z * z),  # stabilized z -> z^{-1} family
    "identity": lambda z: z,
}


def mask_f(g: str, coeffs, dist_scale: float = 1.0) -> Callable:
    """f(x) = g(sum_t coeffs[..., t] * (x * dist_scale)^t). coeffs may carry
    leading batch (head) dims; result broadcasts accordingly."""

    def f(x):
        z = 0.0
        xs = x * dist_scale
        c = jnp.asarray(coeffs)
        for t in range(c.shape[-1] - 1, -1, -1):
            z = z * xs + c[..., t, None] if c.ndim > 1 else z * xs + c[..., t]
        return GS[g](z)

    return f


def sequence_mask_values(g: str, coeffs, L: int, dist_scale: float = 1.0):
    """F[..., k] = f(k) for k = 0..L-1 (token path metric)."""
    ks = jnp.arange(L, dtype=jnp.float32) * dist_scale
    c = jnp.asarray(coeffs)
    z = jnp.zeros(c.shape[:-1] + (L,), jnp.float32)
    for t in range(c.shape[-1] - 1, -1, -1):
        z = z * ks + c[..., t : t + 1]
    return GS[g](z)


def chebyshev_nodes(L: int, rank: int):
    """Chebyshev nodes on [0, L] (numpy, static)."""
    kk = np.arange(rank)
    t = np.cos((2 * kk + 1) * np.pi / (2 * rank))
    return ((L / 2.0) + (L / 2.0) * t).astype(np.float32)  # (rank,)


def _poly_mask_eval(g: str, coeffs, zs):
    """f = g(poly(coeffs)) evaluated on a 2-trailing-dim grid `zs` (already
    dist-scaled); coeffs (..., t+1) broadcasts its leading (head) dims."""
    c = jnp.asarray(coeffs, jnp.float32)
    acc = jnp.zeros(c.shape[:-1] + zs.shape, jnp.float32)
    for t in range(c.shape[-1] - 1, -1, -1):
        acc = acc * zs + c[..., t][..., None, None]
    return GS[g](acc)


def chebyshev_separable_expansion(g: str, coeffs, L: int,
                                  dist_scale: float = 1.0, rank: int = 16):
    """Node grid + node-pair mask values of the rank-R Chebyshev expansion
    of (i, j) -> f(i - j) on [0, L)^2. Shared by the table builder below and
    the O(1)-state decode (attention.topo_decomposition), so train/prefill
    and decode use ONE expansion. Returns (nodes (rank,) np, Bmat
    (..., rank, rank) differentiable in coeffs)."""
    nodes = chebyshev_nodes(L, rank)
    zs = jnp.asarray(nodes[:, None] - nodes[None, :]) * dist_scale  # (r, r)
    return nodes, _poly_mask_eval(g, coeffs, zs)


def chebyshev_separable_tables(g: str, coeffs, L: int, dist_scale: float = 1.0,
                               rank: int = 16):
    """Rank-R separable expansion of the sequence mask, tabulated per position:

        f(i - j) ~= sum_r alpha[..., i, r] * beta[..., j, r]

    for i, j in [0, L) via 2-D Chebyshev interpolation of (i, j) -> f(i - j)
    (spectral accuracy for the paper's smooth g(poly) masks). `coeffs` carries
    leading head dims (H, t+1) and the tables are differentiable in it — this
    is what lets the fused attention kernels train the 3 mask scalars.

    Returns (alpha (..., L, rank), beta (..., L, rank))."""
    nodes, Bmat = chebyshev_separable_expansion(g, coeffs, L, dist_scale, rank)
    from repro.core.engines.plan import _lagrange_batched
    pos = np.arange(L, dtype=np.float32)
    Lg = _lagrange_batched(pos[None, :], nodes[None, :])[0]  # (L, r)
    Lg = jnp.asarray(Lg, jnp.float32)
    alpha = jnp.einsum("lq,...qr->...lr", Lg, Bmat)
    beta = jnp.broadcast_to(Lg, Bmat.shape[:-2] + Lg.shape)
    return alpha, beta


def sequence_mask_matrix(g: str, coeffs, C: int, dist_scale: float = 1.0,
                         strict: bool = False):
    """Lower-triangular (..., C, C) tile of the causal sequence mask:
    f(i - j) where i > j (>= unless `strict`), zero above the diagonal.
    This is the exact within-chunk mask the fused attention kernels apply;
    differentiable in `coeffs` (leading head dims broadcast)."""
    d = np.arange(C)[:, None] - np.arange(C)[None, :]
    vals = _poly_mask_eval(g, coeffs, jnp.asarray(d, jnp.float32) * dist_scale)
    keep = jnp.asarray(d > 0 if strict else d >= 0)
    return jnp.where(keep, vals, 0.0)


# ----------------------------------------------------------------------------
# Algorithm 1 (App. C): general efficient low-rank masked attention
# ----------------------------------------------------------------------------


def masked_linear_attention(q_feat, k_feat, v, fastmult: Callable, eps=1e-6):
    """Alg. 1. q_feat/k_feat: (..., L, m) nonneg features, v: (..., L, d);
    fastmult(X): applies M to the L axis of X (..., L, c). Returns (..., L, d).
    """
    L, m = q_feat.shape[-2], q_feat.shape[-1]
    d = v.shape[-1]
    v1 = (k_feat[..., :, :, None] * v[..., :, None, :]).reshape(
        v.shape[:-1] + (m * d,))  # rows vec(phi(k_i) v_i^T)
    d1 = fastmult(v1)  # (..., L, m*d)
    d2 = fastmult(k_feat)  # (..., L, m)
    num = jnp.einsum("...lm,...lmd->...ld",
                     q_feat, d1.reshape(d1.shape[:-1] + (m, d)))
    den = jnp.einsum("...lm,...lm->...l", q_feat, d2)
    den = jnp.where(jnp.abs(den) < eps, eps, den)
    return num / den[..., None]


def masked_attention_bruteforce(q_feat, k_feat, v, mask, eps=1e-6):
    """Oracle: A = M ⊙ (phi(Q) phi(K)^T); O(L^2 d). Tests only."""
    A = jnp.einsum("...lm,...km->...lk", q_feat, k_feat) * mask
    den = jnp.sum(A, axis=-1)
    den = jnp.where(jnp.abs(den) < eps, eps, den)
    return jnp.einsum("...lk,...kd->...ld", A, v) / den[..., None]


# ----------------------------------------------------------------------------
# sequence (Toeplitz) fastmult factories
# ----------------------------------------------------------------------------


def make_sequence_fastmult(g: str, coeffs, L: int, causal: bool,
                           dist_scale: float = 1.0) -> Callable:
    F = sequence_mask_values(g, coeffs, L, dist_scale)  # (..., L)

    def fastmult(X):
        if causal:
            return causal_toeplitz_matvec(F, X)
        return symmetric_toeplitz_matvec(F, X)

    return fastmult


# ----------------------------------------------------------------------------
# tree / grid (IT-plan) fastmult factory
# ----------------------------------------------------------------------------


_TREE_FM_CACHE = BoundedLRU(64)
_TREE_DIST_CACHE = BoundedLRU(8)

# Largest single tree whose mask FastMult is one dense f32 product by
# M = f(D) (see make_tree_fastmult). On a TPU v5e at width 4096 the dense
# product is 2.3x (n = 196) to 18x (n = 4096) faster than the plan
# executor (benchmarks/sweep_tree_fastmult.py, PERF.md); above 4096 it is
# not measured, and D, a constant of n^2 f32 held on the host and in each
# compiled program, reaches 64 MiB here.
N_DENSE = 4096


def _purge_dead_tree_fm_entries():
    """Drop entries whose Integrator has been garbage collected: their
    id-based key can never hit again, and keeping them would pin the plan
    arrays and compiled closures of dead integrators. Peeks (no recency
    promotion) so the scan doesn't scramble LRU eviction order."""
    for key, entry in _TREE_FM_CACHE.items():
        if entry[1]() is None:
            _TREE_FM_CACHE.discard(key)


def _resolve_plan_handle(integrator):
    """(impl, spec, params) for an `Integrator` facade, a raw backend, or a
    functional (spec, params) pair. `impl` is the object whose
    (non-deprecated, memoizing) `fastmult` the mask closure rides; it is
    None for the pure-pair form, which executes through `plan_api.fastmult`
    directly."""
    if isinstance(integrator, (tuple, list)) and len(integrator) == 2:
        spec, params = integrator
        return None, spec, params
    impl = getattr(integrator, "_impl", integrator)
    return (impl, getattr(impl, "spec", None), getattr(impl, "params", None))


def _has_tracer(tree) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(tree))


def _takes_dense(spec, params, *, use_shard: bool = False,
                 params_traced: bool = False) -> bool:
    """The dense path's condition: a single tree of at most N_DENSE
    vertices, concrete params, no sharding."""
    return (not use_shard and not params_traced and spec is not None
            and params is not None and spec.num_trees == 1
            and spec.n <= N_DENSE)


def tree_fastmult_path(integrator) -> str:
    """"dense" or "plan": the path `make_tree_fastmult` takes over
    `integrator` (unsharded, with its own concrete params)."""
    _, spec, params = _resolve_plan_handle(integrator)
    return "dense" if _takes_dense(spec, params) else "plan"


def _tree_distances(impl, spec, params) -> np.ndarray:
    """(n, n) f32 all-pairs distances of a single-tree plan, computed once
    per (spec, params) on the host. From the backend's own tree where it
    has one (`tree_all_pairs`); otherwise (a `(spec, params)` pair, a
    loaded plan, reweighted params) from the plan itself: the executor with
    f(s) = s, the exact polynomial engine, applied to the identity field.
    Per-tree output weights are left out: they scale the output, not D."""
    key = (spec.digest, id(params))
    hit = _TREE_DIST_CACHE.get(key)
    if hit is not None and hit[1]() is params:
        return hit[0]
    tree = getattr(impl, "tree", None)
    if tree is not None:
        from repro.graphs.traverse import tree_all_pairs

        D = tree_all_pairs(tree)
    else:
        from repro.core import cordial, plan_api

        unweighted = dataclasses.replace(params, tree_w=None)
        # may run inside an enclosing trace: evaluate now, stage nothing
        with jax.ensure_compile_time_eval():
            D = jax.jit(plan_api.fastmult(spec, cordial.Polynomial(
                (0.0, 1.0))))(unweighted, jnp.eye(spec.n, dtype=jnp.float32))
    D = np.asarray(D, np.float32)
    _TREE_DIST_CACHE.put(key, (D, weakref.ref(params)))
    return D


def make_tree_fastmult(integrator, g: str, coeffs,
                       dist_scale: float = 1.0, *, sharded: bool = False,
                       mesh=None) -> Callable:
    """FastMult_M for M = [f(dist_T(i,j))] over a tree's plan.

    Works on fields with arbitrary leading batch/head axes (..., L, c).
    `integrator` is a repro.core.engines.Integrator (any backend with a
    jit-able fastmult, i.e. plan or pallas) OR a functional `(spec, params)`
    pair from `ftfi.build` / `ftfi.load_plan`. One of two paths runs,
    chosen from what the closure can observe:

    - dense: a single tree (`spec.num_trees == 1`) of at most `N_DENSE`
      vertices, with concrete params and no sharding. The closure applies
      M = f(D) as one f32 contraction at `HIGHEST` precision, D the tree's
      exact all-pairs distances (computed once per plan on the host, a
      constant of the compiled program). It is the same product the plan
      evaluates, with no term dropped, and exact in f32; on trees this
      small the executor's gathers and scatter-adds cost far more than
      the n x n product. M is built from `coeffs` inside the trace, so
      gradients reach them as on the plan path.
    - plan: everything else (forests, whose cross-tree entries must stay
      0; params traced under an enclosing jit, e.g. reweighted edge
      weights; the sharded path; larger trees). The mask multiply is
      linear in the field, so every leading axis folds into the trailing
      field dim of one plan execution.

    `sharded=True` rides the multi-device shard_map executor
    (`plan_shard.sharded_fastmult`) over `mesh` (default: the active
    `launch.sharding` mesh): leaf blocks over the plan axis, halo exchange +
    psum_scatter, exact to the single-device path. With no mesh (or one
    device) it falls back to the single-device paths, so model code can
    pass `sharded=cfg.topo_shard_plan` unconditionally.

    Each bound closure records `masks.tree_fastmult:dense` or `:plan` in
    `trace_guard`. For concrete (non-traced) coefficients the closure is
    memoized per (integrator-or-spec, g, coeffs, dist_scale[, mesh]), so
    repeated mask rebuilds (serving, eval loops) reuse one compiled
    executor; traced coeffs (training under jit) bypass the cache and
    trace inline as before."""
    impl, p_spec, p_params = _resolve_plan_handle(integrator)
    if sharded and mesh is None:
        from repro.launch import sharding

        mesh = sharding.current_mesh()
    use_shard = (bool(sharded) and mesh is not None
                 and int(mesh.devices.size) > 1
                 and p_spec is not None and p_params is not None)
    ref_target = integrator if impl is not None else p_spec
    # reweighted params may themselves be traced (training edge weights
    # under an enclosing jit): never cache a tracer-capturing closure
    params_traced = (impl is None or use_shard) and _has_tracer(p_params)
    traced = params_traced or _has_tracer(coeffs)
    dense = _takes_dense(p_spec, p_params, use_shard=use_shard,
                         params_traced=params_traced)
    key = None
    if not traced:
        _purge_dead_tree_fm_entries()
        c = np.asarray(coeffs)
        # the pair path keys on the PARAMS object too: the same spec serves
        # many PlanParams (ftfi.reweight), and each deserves its own bound
        # closure — the entry pins `p_params` so its id stays valid for the
        # entry's lifetime
        key = (id(ref_target),
               id(p_params) if (impl is None or use_shard) else None,
               g, float(dist_scale), c.shape, c.tobytes(),
               id(mesh) if use_shard else 0, dense)
        hit = _TREE_FM_CACHE.get(key)
        if hit is not None and hit[1]() is ref_target:
            trace_guard.record("masks.tree_fastmult", event="hit")
            return hit[0]
        trace_guard.record("masks.tree_fastmult", event="miss")
    trace_guard.record("masks.tree_fastmult",
                       event="dense" if dense else "plan")
    f_eval = mask_f(g, coeffs, dist_scale)
    if dense:
        D = _tree_distances(impl, p_spec, p_params)
        w = p_params.tree_w

        def fastmult(X):  # X: (..., L, c)
            with jax.named_scope("ftfi.dense"):
                M = f_eval(jnp.asarray(D))
                if w is not None:
                    M = M * jnp.asarray(w, jnp.float32)[0]
                return jnp.einsum("ij,...jc->...ic", M,
                                  X.astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST)
    else:
        fastmult = _plan_fastmult(impl, p_spec, p_params, f_eval, traced,
                                  mesh if use_shard else None)

    if key is not None:
        try:
            ref = weakref.ref(ref_target)
        except TypeError:
            ref = None
        if ref is not None:
            # weakly referenced: the purge above drops the entry (and the
            # plan/closure memory it pins) once the integrator/spec dies.
            # p_params rides along strongly so the id() in the key cannot
            # be recycled while the entry lives (None on the impl path).
            _TREE_FM_CACHE.put(key, (fastmult, ref, p_params))
    return fastmult


def _plan_fastmult(impl, p_spec, p_params, f_eval, traced: bool,
                   mesh) -> Callable:
    """The plan path of `make_tree_fastmult`: every leading axis of the
    field folded into the trailing field dim of one plan execution, on the
    sharded executor when `mesh` is given."""
    if mesh is not None:
        # multi-device path: shard_map executor over the mesh; the closure
        # pins `mesh`, so the id() in the memo key stays valid for the
        # entry's lifetime
        from repro.core import plan_shard

        sfm = plan_shard.sharded_fastmult(p_spec, f_eval, mesh=mesh)
        if traced:
            base = lambda X: sfm(p_params, X)  # noqa: E731
        else:
            jfm = jax.jit(sfm)
            base = lambda X: jfm(p_params, X)  # noqa: E731
    elif impl is not None:
        # backend path: the impl's fastmult memoizes/jits over ITS OWN
        # (spec, params) through the same pure executor as plan_api.apply
        base = impl.fastmult(f_eval)
    else:
        from repro.core import plan_api

        fm = plan_api.fastmult(p_spec, f_eval)
        if traced:  # inside an enclosing jit: trace inline, never pin
            base = lambda X: fm(p_params, X)  # noqa: E731
        else:
            jfm = jax.jit(fm)
            base = lambda X: jfm(p_params, X)  # noqa: E731

    def fastmult(X):  # X: (..., L, c)
        shape = X.shape
        L = shape[-2]
        Xf = jnp.moveaxis(X.reshape(-1, L, shape[-1]), 0, -1)  # (L, c, B*)
        Xf = Xf.reshape(L, -1)
        out = base(Xf.astype(jnp.float32))
        out = out.reshape(L, shape[-1], -1)
        return jnp.moveaxis(out, -1, 0).reshape(shape)

    return fastmult


def make_forest_fastmult(integrator, forest, g: str, coeffs,
                         dist_scale: float = 1.0,
                         tree_weights=None) -> Callable:
    """Per-graph FastMult over a packed `Forest` field (..., sum_t n_t, c).

    `integrator` is `Integrator.from_forest(forest, ...)`: its plan is
    block-diagonal across trees, so ONE fused execution applies each graph's
    own mask M_t = [f(dist_{T_t}(i,j))] to its own rows — per-request
    topological masks under serving load ride a single jit dispatch instead
    of a Python loop over requests.

    `tree_weights` (K,) optionally broadcasts a per-tree coefficient onto
    each tree's output block (the multiply is linear, so scaling the output
    rows of tree t equals scaling its mask) — e.g. FRT-forest averaging
    weights or per-request temperature. Shares the concrete-coeff memo with
    `make_tree_fastmult`; traced coeffs bypass caching exactly as there."""
    base = make_tree_fastmult(integrator, g, coeffs, dist_scale)
    if tree_weights is None:
        return base
    w = jnp.asarray(forest.broadcast(
        np.asarray(tree_weights, np.float32)))[:, None]  # (N, 1)

    def fastmult(X):  # X: (..., N, c)
        return base(X) * w

    return fastmult


# ----------------------------------------------------------------------------
# cordial decode states: O(1)-per-token masked linear attention (causal)
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CordialDecomposition:
    """f(i - j) = sum_r alpha_r(i) beta_r(j): per-term callables evaluated on
    integer positions (float32)."""

    num_terms: int
    alpha: Callable  # (pos (...,),) -> (..., R)
    beta: Callable


def cordial_decomposition(g: str, coeffs, dist_scale: float = 1.0
                          ) -> CordialDecomposition:
    coeffs = np.asarray(coeffs, dtype=np.float32)
    T = coeffs.shape[-1] - 1
    if g == "exp" and T <= 1:
        # exp(a0 + a1 (i-j)s) = [e^{a0} e^{a1 s i}] * [e^{-a1 s j}]
        a0 = coeffs[..., 0]
        a1 = coeffs[..., 1] if T == 1 else np.zeros_like(coeffs[..., 0])

        def alpha(pos):
            return (np.exp(a0) * jnp.exp(a1 * dist_scale * pos))[..., None]

        def beta(pos):
            return jnp.exp(-a1 * dist_scale * pos)[..., None]

        return CordialDecomposition(1, alpha, beta)
    if g == "identity":
        # poly(i-j) = sum_t a_t sum_l C(t,l) i^l (-j)^{t-l}: terms (l, t-l)
        # consolidated by l: alpha_l(i) = i^l, beta_l(j) = sum_{t>=l} a_t C(t,l) (-j)^{t-l}
        R = T + 1

        def alpha(pos):
            ps = pos * dist_scale
            return jnp.stack([ps ** l for l in range(R)], axis=-1)

        def beta(pos):
            ps = pos * dist_scale
            outs = []
            for l in range(R):
                acc = 0.0
                for t in range(l, T + 1):
                    acc = acc + coeffs[..., t] * math.comb(t, l) * (-ps) ** (t - l)
                outs.append(acc)
            return jnp.stack(outs, axis=-1)

        return CordialDecomposition(R, alpha, beta)
    raise ValueError(
        f"g={g!r}, degree={T}: not exactly separable; use the Toeplitz path "
        "(chunked prefill) or g in {'exp' (deg<=1), 'identity'}")


def decode_state_init(decomp: CordialDecomposition, m: int, d: int,
                      batch_shape=(), dtype=jnp.float32):
    """S: (..., R, m, d) cross-moment states; z: (..., R, m) normalizers."""
    R = decomp.num_terms
    return (jnp.zeros(batch_shape + (R, m, d), dtype),
            jnp.zeros(batch_shape + (R, m), dtype))


def decode_state_update(decomp, state, pos, k_feat, v):
    """Absorb token at integer position `pos`: k_feat (..., m), v (..., d)."""
    S, z = state
    b = decomp.beta(jnp.asarray(pos, jnp.float32))  # (R,) or (..., R)
    b = jnp.broadcast_to(b, S.shape[:-2])  # (..., R)
    S = S + b[..., None, None] * (k_feat[..., None, :, None] * v[..., None, None, :])
    z = z + b[..., None] * k_feat[..., None, :]
    return (S, z)


def decode_state_read(decomp, state, pos, q_feat, eps=1e-6):
    """Masked linear attention output for the query at position `pos`."""
    S, z = state
    a = decomp.alpha(jnp.asarray(pos, jnp.float32))
    a = jnp.broadcast_to(a, S.shape[:-2])  # (..., R)
    num = jnp.einsum("...m,...rmd,...r->...d", q_feat, S, a)
    den = jnp.einsum("...m,...rm,...r->...", q_feat, z, a)
    den = jnp.where(jnp.abs(den) < eps, eps, den)
    return num / den[..., None]
