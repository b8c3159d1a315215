"""Functional plan API: static `PlanSpec` + differentiable `PlanParams`.

A plan is one (spec, params) pair, built straight from the integrator
tree's flat decomposition (`integrate._assemble_plan`), cached as it is and
passed explicitly to every entry point, so it stays visible to
`jit`/`grad`/`vmap` and crosses process and device boundaries:

  PlanSpec    hashable, static: index arrays, bucket layout, masks, grid
              metadata, provenance (content hash, seed, leaf_size) and —
              for reweightable builds — the (pivot, representative, LCA)
              tables plus the root-path edge CSR that re-derive every
              distance from edge weights. Registered as a zero-leaf pytree
              (the spec IS the aux data), so it rides through jit/vmap as a
              static argument keyed by content digest.

  PlanParams  dynamic: leaf/cross distances and per-tree output weights as
              jnp arrays — traceable, differentiable, shardable,
              checkpointable.

Pure entry points (also exposed as `repro.ftfi`):

  build(tree_or_forest, ...)      -> (spec, params)
  apply(spec, params, fn, X)      -> Y            (jit/vmap/grad-safe)
  fastmult(spec, fn)              -> (params, X) -> Y   (jittable)
  reweight(spec, edge_w)          -> PlanParams   (differentiable in edge_w)
  update_plan(spec, params, ops)  -> (spec', params')  incremental edits
  save_plan / load_plan           npz round trip, zero IT rebuild at load

Reweight exactness: the IT decomposition is purely combinatorial (it covers
every vertex pair regardless of weights), so recomputing distances as
d(u,v) = depth[u] + depth[v] - 2 depth[lca(u,v)] with depth = root-path edge
sums yields the TRUE integration for ANY positive edge weights — provided
each distance slot maps to one vertex. `build(..., reweightable=True)`
therefore expands distance groups to per-vertex slots (and disables the
grid/Hankel engine, whose integer grid would not survive retraining).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.analysis import trace_guard
from repro.core.cordial import FamilySpec, spec_of
from repro.core.lru import BoundedLRU

KERNEL_MODES = ("poly", "exp", "expq", "rational")

# Every executor and cross-engine contraction runs at full f32 precision: the
# TPU's default f32 matmul is one bf16 pass (~1e-3 relative), which breaks the
# exactness the integrate promises (~1e-6 at mesh scale). The CPU is exact
# either way.
EXACT = jax.lax.Precision.HIGHEST

_SAVE_VERSION = 1
# PlanSpec field-layout generation, mixed into disk-cache keys (NOT the npz
# version: old artifacts still load — absent fields default to None)
# 4: update tables (children/root_refs) are int32 like every other index
#    array — bumping the schema misses stale disk-cache entries so they
#    rebuild in canonical form instead of round-tripping int64
_SPEC_SCHEMA = 4


# ----------------------------------------------------------------------------
# PlanSpec / PlanParams
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False, repr=False)
class PlanSpec:
    """Static half of a plan. Hashable by content digest; every array is
    host-side numpy and never traced. Tuples are indexed by cross/leaf
    bucket."""

    n: int
    num_trees: int
    tree_sizes: tuple
    leaf_size: int
    seed: int
    fingerprint: str
    grid_h: float | None
    reweightable: bool
    # cross buckets (static layout; build-time distances kept for the
    # grid/Hankel engine, which requires host-side integer grid indices)
    cross_tgt_mask: tuple  # of (B, Ut) bool
    cross_src_mask: tuple  # of (B, Us) bool
    cross_src_off: tuple
    cross_tgt_off: tuple
    cross_tgt_d0: tuple  # of (B, Ut) float64
    cross_src_d0: tuple
    # leaf buckets
    leaf_ids: tuple  # of (B, K) int32, padded with n
    leaf_mask: tuple  # of (B, K) bool
    leaf_dists0: tuple  # of (B, K, K) float64
    # fused executor index arrays
    pivots: np.ndarray
    src_gather: np.ndarray
    src_seg: np.ndarray
    n_src_groups: int
    tgt_gather: np.ndarray
    tgt_scatter: np.ndarray
    n_tgt_groups: int
    num_cross_jobs: int
    # reweight tables (only for reweightable builds)
    num_edges: int = 0
    path_rows: np.ndarray | None = None  # (P,) vertex per root-path entry
    path_edges: np.ndarray | None = None  # (P,) edge id per entry
    cross_piv: tuple | None = None  # of (B,) pivot vertex per job row
    cross_tgt_rep: tuple | None = None  # of (B, Ut) representative vertex
    cross_tgt_lca: tuple | None = None  # of (B, Ut) lca(piv, rep)
    cross_src_rep: tuple | None = None
    cross_src_lca: tuple | None = None
    leaf_lca: tuple | None = None  # of (B, K, K) lca(ids_i, ids_j)
    # update tables (only when compiled by this codebase's assembler; they
    # let `update_plan` patch single leaves without a rebuild)
    children: np.ndarray | None = None  # (I, 2) canonical IT child refs
    root_refs: np.ndarray | None = None  # (num_trees,) per-tree root ref
    job_bucket: np.ndarray | None = None  # (2I,) bucket index per cross job
    job_row: np.ndarray | None = None  # (2I,) row within bucket
    leaf_bucket: np.ndarray | None = None  # (L,) bucket per leaf node
    leaf_row: np.ndarray | None = None  # (L,) row within leaf bucket
    edges_u: np.ndarray | None = None  # (E,) packed edge endpoints (global)
    edges_v: np.ndarray | None = None
    edge_w0: np.ndarray | None = None  # (E,) build-time edge weights
    ghosts: np.ndarray | None = None  # deleted-vertex ids (update_plan)
    # mesh/device provenance (0/empty = artifact not bound to a mesh):
    # recorded by `save_plan(..., mesh=...)` so plan_guard / apply_sharded
    # can reject a sharded artifact on a mismatched mesh up front
    mesh_devices: int = 0
    mesh_axes: tuple = ()
    shard_layout: int = 0

    def __post_init__(self):
        # digest is lazy: hashing tens of MB of index arrays costs more than
        # vectorized assembly itself, and incremental updates / cache hits
        # often never need it
        object.__setattr__(self, "_digest", None)

    @property
    def digest(self) -> str:
        if self._digest is None:
            h = hashlib.sha1()
            for f in dataclasses.fields(self):
                _mix(h, getattr(self, f.name))
            object.__setattr__(self, "_digest", h.hexdigest())
        return self._digest

    @property
    def provenance(self) -> dict:
        return {"fingerprint": self.fingerprint, "seed": self.seed,
                "leaf_size": self.leaf_size, "n": self.n,
                "num_trees": self.num_trees, "grid_h": self.grid_h,
                "reweightable": self.reweightable,
                "mesh_devices": self.mesh_devices,
                "mesh_axes": tuple(self.mesh_axes),
                "shard_layout": self.shard_layout}

    def __hash__(self):
        return hash(self.digest)

    def __eq__(self, other):
        return (type(other) is PlanSpec
                and other.digest == self.digest)

    def __repr__(self):
        return (f"PlanSpec(n={self.n}, num_trees={self.num_trees}, "
                f"leaf_size={self.leaf_size}, seed={self.seed}, "
                f"grid_h={self.grid_h}, reweightable={self.reweightable}, "
                f"sha={self.digest[:12]})")


def _mix(h, val):
    if val is None:
        h.update(b"\x00N")
    elif isinstance(val, np.ndarray):
        h.update(str(val.dtype).encode())
        h.update(np.int64(val.shape).tobytes())
        h.update(np.ascontiguousarray(val).tobytes())
    elif isinstance(val, (tuple, list)):
        h.update(b"\x00T%d" % len(val))
        for v in val:
            _mix(h, v)
    else:
        h.update(repr(val).encode())


@dataclasses.dataclass
class PlanParams:
    """Dynamic half of a plan: jnp arrays, registered as pytree leaves.

    `tree_w` is the per-tree output weight vector (None = all ones): the
    multiply is linear, so scaling tree t's output rows equals scaling its
    mask — FRT averaging weights, per-request temperatures, learnable
    per-graph gains all land here."""

    cross_tgt_d: tuple  # of (B, Ut)
    cross_src_d: tuple  # of (B, Us)
    leaf_dists: tuple  # of (B, K, K)
    tree_w: object | None = None  # (num_trees,) or None


jax.tree_util.register_pytree_node(
    PlanParams,
    lambda p: ((p.cross_tgt_d, p.cross_src_d, p.leaf_dists, p.tree_w), None),
    lambda _, c: PlanParams(*c),
)

# zero-leaf pytree: the spec IS the (hashable) aux data, so a (spec, params)
# pair flattens to params leaves only and jit retrace keys on spec equality
jax.tree_util.register_pytree_node(
    PlanSpec, lambda s: ((), s), lambda s, _: s)


def _birth_params(spec: PlanSpec) -> PlanParams:
    # a build may run INSIDE a jit trace (a model that builds its plan at
    # trace time); without this guard the float64->float32 canonicalization
    # becomes a traced op and the cached params would leak tracers out of
    # that trace
    with jax.ensure_compile_time_eval():
        return PlanParams(
            cross_tgt_d=tuple(jnp.asarray(d) for d in spec.cross_tgt_d0),
            cross_src_d=tuple(jnp.asarray(d) for d in spec.cross_src_d0),
            leaf_dists=tuple(jnp.asarray(d) for d in spec.leaf_dists0),
            tree_w=None,
        )


# ----------------------------------------------------------------------------
# build
# ----------------------------------------------------------------------------

_PLAN_CACHE = BoundedLRU(32)


def clear_plan_cache() -> None:
    """Drop the in-memory plan LRU (a configured disk cache is kept)."""
    _PLAN_CACHE.clear()


def _cached_plan(key):
    """The (spec, params) pair under `key` from the in-memory LRU, recorded
    in trace_guard as an `integrate.plan_cache` hit or miss; on a miss,
    from the disk cache when one is configured (then kept in memory too)."""
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        trace_guard.record("integrate.plan_cache", event="hit")
        return hit
    trace_guard.record("integrate.plan_cache", event="miss")
    from repro.core import plan_cache

    hit = plan_cache.load(plan_cache.key_str(key))
    if hit is not None:
        _PLAN_CACHE.put(key, hit)
    return hit


def build(tree_or_forest, *, leaf_size: int = 64, seed: int = 0,
          reweightable: bool = False, detect_grid_spacing: bool = True,
          use_cache: bool = True):
    """Compile a tree or `Forest` into a functional (spec, params) pair.

    A `Forest` compiles into ONE plan over the packed vertex space (vertex
    v of tree t at row `forest.offsets[t] + v`): the batched flat-IT build
    decomposes all trees in one level sweep and the assembly merges their
    cross jobs and leaves into shared size-class buckets, so the executor
    runs N trees in the same handful of fused dispatches as one. The
    multiply is block-diagonal by construction.

    Cache hierarchy, keyed by content fingerprint(s), leaf_size, seed,
    grid detection and reweightable: the in-memory BoundedLRU first, then
    (when `FTFI_PLAN_CACHE` is configured) the disk-persistent artifact
    cache, so a cold process over a known topology pays one npz read
    instead of an O(N log N) decomposition. Both hold the pair as built.
    `use_cache=False` bypasses both.

    `reweightable=True` additionally records the (pivot, representative,
    LCA) tables and root-path edge CSR that let `reweight(spec, edge_w)`
    re-derive `params` differentiably from edge weights — at the cost of
    per-vertex (uncollapsed) distance groups and no grid/Hankel engine."""
    import hashlib

    from repro.core.integrate import _assemble_plan, _reweight_tables
    from repro.core.itree_flat import (build_flat_forest, build_flat_it,
                                       tree_fingerprint)
    from repro.graphs.graph import Forest

    forest = isinstance(tree_or_forest, Forest)
    trees = tree_or_forest.trees if forest else [tree_or_forest]
    if reweightable:
        detect_grid_spacing = False
    ls = max(int(leaf_size), 6)
    with trace_guard.span("ftfi.build.fingerprint"):
        fps = tuple(tree_fingerprint(t) for t in trees)
    if use_cache:
        key = ((("forest", fps) if forest else (fps[0],))
               + (ls, int(seed), detect_grid_spacing, reweightable))
        hit = _cached_plan(key)
        if hit is not None:
            return hit

    with trace_guard.span("ftfi.build.decompose"):
        if forest:
            flat = build_flat_forest(trees, leaf_size=leaf_size, seed=seed,
                                     use_cache=use_cache)
        else:
            flat = build_flat_it(tree_or_forest, leaf_size=leaf_size,
                                 seed=seed, use_cache=use_cache)
    with trace_guard.span("ftfi.build.assemble"):
        fields = _assemble_plan(flat, tree_or_forest.num_vertices,
                                detect_grid_spacing,
                                expand_groups=reweightable)
    if reweightable:
        fields.update(_reweight_tables(fields, trees))
    sizes = (tuple(int(s) for s in tree_or_forest.tree_sizes) if forest
             else (tree_or_forest.num_vertices,))
    spec = PlanSpec(
        num_trees=max(len(sizes), 1), tree_sizes=sizes or (fields["n"],),
        leaf_size=ls, seed=int(seed),
        fingerprint=(hashlib.sha1("".join(fps).encode()).hexdigest()
                     if forest else fps[0]),
        reweightable=reweightable, **fields)
    pair = (spec, _birth_params(spec))
    if use_cache:
        from repro.core import plan_cache

        _PLAN_CACHE.put(key, pair)
        plan_cache.store(plan_cache.key_str(key), *pair)
    return pair


# ----------------------------------------------------------------------------
# batched cross engines
# ----------------------------------------------------------------------------


def chebyshev_batched_matvec(fn_eval, tgt_d, tgt_mask, src_d, src_mask, Xp,
                             degree: int = 32):
    """Batched low-rank multiply via per-node 2D Chebyshev interpolation."""
    big = 1e30
    x_lo = jnp.min(jnp.where(tgt_mask, tgt_d, big), axis=1)  # (B,)
    x_hi = jnp.max(jnp.where(tgt_mask, tgt_d, -big), axis=1)
    y_lo = jnp.min(jnp.where(src_mask, src_d, big), axis=1)
    y_hi = jnp.max(jnp.where(src_mask, src_d, -big), axis=1)
    r = degree
    k = np.arange(r)
    t = np.cos((2 * k + 1) * np.pi / (2 * r))  # (r,)
    xc = (x_lo[:, None] + x_hi[:, None]) / 2 + (x_hi - x_lo)[:, None] / 2 * t  # (B, r)
    yc = (y_lo[:, None] + y_hi[:, None]) / 2 + (y_hi - y_lo)[:, None] / 2 * t
    Bmat = fn_eval(xc[:, :, None] + yc[:, None, :])  # (B, r, r)
    Lx = _lagrange_batched(tgt_d, xc)  # (B, Kx, r)
    Ly = _lagrange_batched(src_d, yc)  # (B, Ky, r)
    tmp = jnp.einsum("bkr,bkd->brd", Ly, Xp, precision=EXACT)
    tmp = jnp.einsum("bqr,brd->bqd", Bmat, tmp, precision=EXACT)
    return jnp.einsum("bkq,bqd->bkd", Lx, tmp, precision=EXACT)


def _lagrange_batched(pts, nodes):
    r = nodes.shape[1]
    k = np.arange(r)
    w = ((-1.0) ** k) * np.sin((2 * k + 1) * np.pi / (2 * r))  # (r,)
    diff = pts[:, :, None] - nodes[:, None, :]  # (B, K, r)
    small = jnp.abs(diff) < 1e-12
    diff = jnp.where(small, 1.0, diff)
    terms = w[None, None, :] / diff
    L = terms / jnp.sum(terms, axis=-1, keepdims=True)
    any_small = jnp.any(small, axis=-1, keepdims=True)
    return jnp.where(any_small, small.astype(L.dtype), L)


def polynomial_batched_matvec(coeffs, tgt_d, tgt_mask, src_d, src_mask, Xp):
    """Exact batched multiply for f = polynomial(coeffs) — differentiable
    w.r.t. coeffs. O((Kt+Ks) * deg) per node."""
    coeffs = jnp.asarray(coeffs)
    Bdeg = coeffs.shape[0] - 1
    xpow = _powers_b(tgt_d, Bdeg)  # (B, Kt, deg+1)
    ypow = _powers_b(src_d, Bdeg)  # (B, Ks, deg+1)
    ypow = ypow * src_mask[:, :, None]
    S = jnp.einsum("bku,bkd->bud", ypow, Xp,
                   precision=EXACT)  # (B, deg+1, d)
    Wrows = []
    for l in range(Bdeg + 1):
        acc = 0.0
        for tt in range(l, Bdeg + 1):
            acc = acc + coeffs[tt] * math.comb(tt, l) * S[:, tt - l]
        Wrows.append(acc)
    W = jnp.stack(Wrows, axis=1)  # (B, deg+1, d)
    return jnp.einsum("bkl,bld->bkd", xpow, W, precision=EXACT)


def _powers_b(x, B):
    pows = [jnp.ones_like(x)]
    for _ in range(B):
        pows.append(pows[-1] * x)
    return jnp.stack(pows, axis=-1)


def exponential_batched_matvec(lam, scale, tgt_d, tgt_mask, src_d, src_mask,
                               Xp):
    """Exact rank-1 multiply for f = scale * exp(lam s), numerically shifted.
    Padded source groups carry zero mass in Xp, so no source mask is needed."""
    ly = lam * src_d  # (B, Us)
    m = jnp.max(jnp.where(src_mask, ly, -jnp.inf), axis=1, keepdims=True)
    t = jnp.einsum("bu,bud->bd", jnp.exp(ly - m) * src_mask, Xp,
                   precision=EXACT)  # (B, d)
    return scale * jnp.exp(lam * tgt_d + m)[:, :, None] * t[:, None, :]


def hankel_batched_matvec(fn_eval, h: float, tgt_d0: np.ndarray,
                          src_d0: np.ndarray, Xp):
    """Exact multiply for ANY f on grid-aligned distances (spacing h).

    The integer grid indices come from the host-side (numpy) build-time
    distance arrays, so every shape below is static under jit: M embeds into
    a Hankel matrix and the multiply becomes an FFT correlation with
    F[k] = f(k h) — the paper's rational-weight embedding (App. A.2.3),
    batched over IT nodes. Requires static distances by construction, which
    is why reweightable specs never select this engine."""
    it = np.rint(tgt_d0 / h).astype(np.int64)  # (B, Ut); padded -> 0
    isrc = np.rint(src_d0 / h).astype(np.int64)  # (B, Us)
    Ms = int(isrc.max()) + 1 if isrc.size else 1
    L = (int(it.max()) if it.size else 0) + Ms  # covers all k + m
    F = fn_eval(h * jnp.arange(L, dtype=Xp.dtype))  # (L,)
    B, Us, d = Xp.shape
    bidx = np.arange(B)[:, None]
    # scatter source mass onto the grid: P[b, m] = sum_{u: isrc[b,u]=m} Xp[b,u]
    P = jnp.zeros((B, Ms, d), Xp.dtype).at[bidx, isrc].add(Xp)
    n = 1 << int(np.ceil(np.log2(L + Ms)))
    Ff = jnp.fft.rfft(F, n=n)  # (n//2+1,)
    Pf = jnp.fft.rfft(P[:, ::-1], n=n, axis=1)  # (B, n//2+1, d)
    full = jnp.fft.irfft(Ff[None, :, None] * Pf, n=n, axis=1)
    out_full = full[:, Ms - 1 : Ms - 1 + L]  # (B, L, d): out[b,k]=sum F[k+m]P[m]
    return jnp.take_along_axis(out_full, jnp.asarray(it)[:, :, None], axis=1)


# ----------------------------------------------------------------------------
# engine selection + the pure executor
# ----------------------------------------------------------------------------


def select_cross(spec: PlanSpec, fspec: FamilySpec, backend: str = "plan",
                 degree: int = 32, pallas_opts: dict | None = None):
    """(engine_name, cross_multiply) for this (spec, f-family, backend).

    cross_multiply(i, tgt_d, tgt_mask, src_d, src_mask, Xp) -> (B, Ut, d)
    receives the bucket index plus the *params* distance arrays (traceable),
    so every engine except the grid/Hankel one differentiates through —
    and flows gradients into — reweighted distances.

    `backend="auto"` resolves by problem size through the degradation
    ladder: the fused pallas kernel only wins past
    `ladder.AUTO_PALLAS_MIN_N` vertices (BENCH_ftfi_runtime.json shows it
    *slower* than the plan engine at n=1000), so small plans pick "plan"."""
    if backend == "auto":
        from repro.core import ladder

        backend = ladder.effective_backend("auto", n=spec.n)
    if backend == "pallas" and fspec.mode in KERNEL_MODES:
        opts = dict(pallas_opts or {})
        coeffs = jnp.asarray(np.asarray(fspec.coeffs, np.float32))
        mode, scale = fspec.mode, fspec.scale

        def cross(i, tgt_d, tgt_mask, src_d, src_mask, Xp):
            from repro.kernels.fdist_matvec.ops import fdist_matvec_batched

            out = fdist_matvec_batched(
                tgt_d.astype(jnp.float32), src_d.astype(jnp.float32),
                Xp.astype(jnp.float32), coeffs, mode=mode, **opts)
            # the kernel's rational family is unit-scaled: 1 / (1 + c0 s^2)
            return out * scale if mode == "rational" else out

        return f"fdist_matvec:{fspec.mode}", cross
    if fspec.mode == "poly":
        cs = fspec.coeffs

        def cross(i, tgt_d, tgt_mask, src_d, src_mask, Xp):
            return polynomial_batched_matvec(cs, tgt_d, tgt_mask, src_d,
                                             src_mask, Xp)

        return "polynomial", cross
    if fspec.mode == "exp":
        lam, scale = fspec.coeffs

        def cross(i, tgt_d, tgt_mask, src_d, src_mask, Xp):
            return exponential_batched_matvec(lam, scale, tgt_d, tgt_mask,
                                              src_d, src_mask, Xp)

        return "exponential", cross
    if spec.grid_h is not None and not spec.reweightable:
        h, fe = spec.grid_h, fspec.fn_eval

        def cross(i, tgt_d, tgt_mask, src_d, src_mask, Xp):
            return hankel_batched_matvec(fe, h, spec.cross_tgt_d0[i],
                                         spec.cross_src_d0[i], Xp)

        return "hankel_fft", cross
    fe = fspec.fn_eval

    def cross(i, tgt_d, tgt_mask, src_d, src_mask, Xp):
        return chebyshev_batched_matvec(fe, tgt_d, tgt_mask, src_d, src_mask,
                                        Xp, degree=degree)

    return "chebyshev", cross


def _execute(spec: PlanSpec, params: PlanParams, fn_eval: Callable,
             cross_multiply: Callable, X):
    """The pure fused executor: one gather + segment-sum (Eq. 3), one cross
    dispatch per size bucket, one gather + scatter-add (Eq. 4), diagonal
    corrections, per-tree output weights. Everything dynamic comes from
    `params`; everything indexing/shaping from `spec`.

    Each phase runs under a `jax.named_scope` (`ftfi.leaf`, `ftfi.gather`,
    `ftfi.cross`, `ftfi.scatter`, `ftfi.diag`): trace-time names that ride
    into the compiled ops' metadata, so a device trace can sum its ops per
    phase. The compiled code is the same with or without them."""
    X = jnp.asarray(X)
    squeeze = X.ndim == 1
    if squeeze:
        X = X[:, None]
    d = X.shape[1]
    Xpad = jnp.concatenate([X, jnp.zeros((1, d), X.dtype)], axis=0)
    out = jnp.zeros_like(Xpad)

    with jax.named_scope("ftfi.leaf"):
        for i in range(len(spec.leaf_ids)):
            ids, mask = spec.leaf_ids[i], spec.leaf_mask[i]
            Xl = Xpad[ids]  # (B, K, d)
            M = fn_eval(params.leaf_dists[i])  # (B, K, K)
            pair_mask = mask[:, :, None] & mask[:, None, :]
            M = jnp.where(jnp.asarray(pair_mask), M, 0.0)
            contrib = jnp.einsum("bij,bjd->bid", M, Xl, precision=EXACT)
            out = out.at[ids].add(contrib * mask[:, :, None])

    if spec.n_src_groups:
        # Eq. 3 for every node at once: X'[g] = sum of source-vertex fields
        # per distance group (pivot/pad groups are empty -> zero)
        with jax.named_scope("ftfi.gather"):
            Xp_flat = jax.ops.segment_sum(Xpad[spec.src_gather], spec.src_seg,
                                          num_segments=spec.n_src_groups)
        parts = []
        for i in range(len(spec.cross_src_mask)):
            B, Us = spec.cross_src_mask[i].shape
            Ut = spec.cross_tgt_mask[i].shape[1]
            off = spec.cross_src_off[i]
            # named at the call site, so every cross engine is covered
            with jax.named_scope("ftfi.cross"):
                Xp = Xp_flat[off:off + B * Us].reshape(B, Us, d)
                res = cross_multiply(
                    i, params.cross_tgt_d[i],
                    jnp.asarray(spec.cross_tgt_mask[i]),
                    params.cross_src_d[i],
                    jnp.asarray(spec.cross_src_mask[i]), Xp)
                parts.append(res.reshape(B * Ut, d))
        # Eq. 4 for every node at once: gather each target's group value and
        # scatter-add into the output field
        with jax.named_scope("ftfi.scatter"):
            cross_flat = (jnp.concatenate(parts, axis=0) if len(parts) > 1
                          else parts[0])
            out = out.at[spec.tgt_scatter].add(cross_flat[spec.tgt_gather])

    with jax.named_scope("ftfi.diag"):
        # diagonal corrections: -f(0) X[p] once per internal node
        f0 = fn_eval(jnp.zeros((1,)))[0]
        out = out.at[spec.pivots].add(-f0 * Xpad[spec.pivots])

        res = out[:-1]
        if params.tree_w is not None:
            w = jnp.repeat(jnp.asarray(params.tree_w),
                           np.asarray(spec.tree_sizes, np.int64),
                           total_repeat_length=spec.n)
            res = res * w[:, None].astype(res.dtype)
    return res[:, 0] if squeeze else res


def _fspec(fn) -> FamilySpec:
    return fn if isinstance(fn, FamilySpec) else spec_of(fn)


def apply(spec: PlanSpec, params: PlanParams, fn, X, *,
          backend: str = "plan", degree: int = 32,
          pallas_opts: dict | None = None, mesh=None,
          axis: str | None = None):
    """Pure integration: Y = M_f X with distances/weights from `params`.

    jit/vmap/grad-safe: `spec` is static (pytree aux), `params`/`X` are
    traced. `fn` is a CordialFn, FamilySpec, or traceable callable.
    `backend` picks the cross-engine family: "plan" (exact LDR + Hankel on
    grids + Chebyshev), "pallas" (fused fdist_matvec kernel for the
    in-kernel families), or "auto" (size-resolved through the ladder). The
    numpy references (`integrate.FTFI`, `ExpMP`, `BTFI`) run on the host
    without a plan.

    `mesh` (optionally with `axis`) routes through the multi-device
    shard_map executor — see `plan_shard.apply_sharded`."""
    if mesh is not None:
        from repro.core.plan_shard import apply_sharded

        return apply_sharded(spec, params, fn, X, mesh=mesh, axis=axis,
                             backend=backend, degree=degree,
                             pallas_opts=pallas_opts)
    fspec = _fspec(fn)
    _, cross = select_cross(spec, fspec, backend=backend, degree=degree,
                            pallas_opts=pallas_opts)
    return _execute(spec, params, fspec.fn_eval, cross, X)


def fastmult(spec: PlanSpec, fn, *, backend: str = "plan", degree: int = 32,
             pallas_opts: dict | None = None) -> Callable:
    """Jittable (params, X) -> Y closure with the engine choice baked in.

    The returned function is pure: params cross jit boundaries explicitly,
    so it vmaps over batched fields, shards, and back-propagates into
    reweighted distances."""
    fspec = _fspec(fn)
    _, cross = select_cross(spec, fspec, backend=backend, degree=degree,
                            pallas_opts=pallas_opts)
    fe = fspec.fn_eval

    def fm(params, X):
        if isinstance(X, jax.core.Tracer):
            # trace-time only: one record per compile, none per cached call
            trace_guard.record("ftfi.fastmult", detail=spec.digest[:12])
        return _execute(spec, params, fe, cross, X)

    return fm


def describe(spec: PlanSpec, fn, backend: str = "plan", degree: int = 32
             ) -> dict:
    name, _ = select_cross(spec, _fspec(fn), backend=backend, degree=degree)
    return {"api": "ftfi", "backend": backend, "cross_engine": name,
            "grid_h": spec.grid_h, "num_trees": spec.num_trees,
            "reweightable": spec.reweightable}


# ----------------------------------------------------------------------------
# reweight: edge weights -> PlanParams (differentiable)
# ----------------------------------------------------------------------------


def reweight(spec: PlanSpec, edge_w, tree_w=None) -> PlanParams:
    """Re-derive every plan distance from edge weights, differentiably.

    depth[v] = sum of edge weights on v's root path (one gather +
    segment-sum over the spec's root-path CSR), then every distance slot is
    d(u, v) = depth[u] + depth[v] - 2 depth[lca(u, v)] via the build-time
    (pivot, representative, LCA) tables. Exact for ANY positive weights on
    the same topology — the IT decomposition is combinatorial — so tree
    metrics (and hence topo-attention RPE distances) become learnable
    parameters. Requires `build(..., reweightable=True)`.

    `edge_w` is (num_edges,) in packed per-tree edge order (the
    concatenation of each tree's `weights` array); `tree_w` optionally sets
    per-tree output weights on the returned params."""
    if spec.path_rows is None:
        raise ValueError(
            "spec was not built with reweightable=True: rebuild via "
            "ftfi.build(tree, reweightable=True) to record the distance "
            "derivation tables")
    edge_w = jnp.asarray(edge_w)
    if edge_w.shape != (spec.num_edges,):
        raise ValueError(
            f"edge_w must have shape ({spec.num_edges},) — packed per-tree "
            f"edge order — got {edge_w.shape}")
    depth = jax.ops.segment_sum(edge_w[spec.path_edges], spec.path_rows,
                                num_segments=spec.n)
    dpad = jnp.concatenate([depth, jnp.zeros((1,), depth.dtype)])

    def _pair(u, v, l):
        return dpad[u] + dpad[v] - 2.0 * dpad[l]

    ctd = tuple(
        _pair(spec.cross_piv[i][:, None], spec.cross_tgt_rep[i],
              spec.cross_tgt_lca[i])
        for i in range(len(spec.cross_tgt_rep)))
    csd = tuple(
        _pair(spec.cross_piv[i][:, None], spec.cross_src_rep[i],
              spec.cross_src_lca[i])
        for i in range(len(spec.cross_src_rep)))
    ld = tuple(
        _pair(spec.leaf_ids[i][:, :, None].astype(np.int64),
              spec.leaf_ids[i][:, None, :].astype(np.int64),
              spec.leaf_lca[i])
        for i in range(len(spec.leaf_ids)))
    return PlanParams(cross_tgt_d=ctd, cross_src_d=csd, leaf_dists=ld,
                      tree_w=None if tree_w is None else jnp.asarray(tree_w))


# ----------------------------------------------------------------------------
# serialization
# ----------------------------------------------------------------------------

_SPEC_ARRAY_FIELDS = ("pivots", "src_gather", "src_seg", "tgt_gather",
                      "tgt_scatter", "path_rows", "path_edges",
                      # update tables (absent in pre-schema-2 artifacts;
                      # loader defaults them to None)
                      "children", "root_refs", "job_bucket", "job_row",
                      "leaf_bucket", "leaf_row", "edges_u", "edges_v",
                      "edge_w0", "ghosts")
_SPEC_TUPLE_FIELDS = ("cross_tgt_mask", "cross_src_mask", "cross_tgt_d0",
                      "cross_src_d0", "leaf_ids", "leaf_mask", "leaf_dists0",
                      "cross_piv", "cross_tgt_rep", "cross_tgt_lca",
                      "cross_src_rep", "cross_src_lca", "leaf_lca")
_SPEC_SCALAR_FIELDS = ("n", "num_trees", "tree_sizes", "leaf_size", "seed",
                       "fingerprint", "grid_h", "reweightable",
                       "cross_src_off", "cross_tgt_off", "n_src_groups",
                       "n_tgt_groups", "num_cross_jobs", "num_edges",
                       "mesh_devices", "mesh_axes", "shard_layout")
# absent in pre-schema-3 artifacts; the loader falls back to these
_SPEC_SCALAR_DEFAULTS = {"mesh_devices": 0, "mesh_axes": (),
                         "shard_layout": 0}


def save_plan(path, spec: PlanSpec, params: PlanParams, *,
              mesh=None) -> None:
    """Serialize (spec, params) to one .npz artifact (no pickle).

    The artifact is self-contained: `load_plan` reconstructs both halves
    with zero IT rebuild, and a load -> apply reproduces results bit-for-bit
    (params are saved post-conversion, so the loaded arrays are the same
    bits the builder's executor consumed).

    `mesh` stamps mesh/device provenance (device count, axis names, shard
    layout version) into the artifact: loading it onto a mismatched mesh
    then fails fast in `plan_guard` / `apply_sharded` instead of crashing
    at gather time."""
    if mesh is not None:
        from repro.core.plan_shard import SHARD_LAYOUT_VERSION

        spec = dataclasses.replace(
            spec, mesh_devices=int(mesh.devices.size),
            mesh_axes=tuple(str(a) for a in mesh.axis_names),
            shard_layout=SHARD_LAYOUT_VERSION)
    arrays: dict = {}
    meta: dict = {"version": _SAVE_VERSION}
    for name in _SPEC_SCALAR_FIELDS:
        meta[name] = getattr(spec, name)
    for name in _SPEC_ARRAY_FIELDS:
        val = getattr(spec, name)
        meta[f"has_{name}"] = val is not None
        if val is not None:
            arrays[f"s_{name}"] = val
    for name in _SPEC_TUPLE_FIELDS:
        val = getattr(spec, name)
        meta[f"len_{name}"] = -1 if val is None else len(val)
        if val is not None:
            for i, a in enumerate(val):
                arrays[f"s_{name}_{i}"] = a
    for name in ("cross_tgt_d", "cross_src_d", "leaf_dists"):
        val = getattr(params, name)
        for i, a in enumerate(val):
            arrays[f"p_{name}_{i}"] = np.asarray(a)
    meta["has_tree_w"] = params.tree_w is not None
    if params.tree_w is not None:
        arrays["p_tree_w"] = np.asarray(params.tree_w)
    arrays["__meta__"] = np.array(json.dumps(meta))
    with open(path, "wb") as fh:
        np.savez_compressed(fh, **arrays)


def load_plan(path, validate: bool = True):
    """Deserialize a `save_plan` artifact -> (spec, params). Never touches
    the IT/plan builders: serving restarts pay one file read, not an
    O(N log N) decomposition.

    The artifact is UNTRUSTED input (disk cache, registry download, operator
    handoff): a truncated/bit-flipped file raises a clear
    `PlanValidationError` instead of feeding garbage indices to the fused
    executor. `validate=True` (default) additionally runs the full
    `plan_guard` bounds/consistency pass under the configured policy;
    malformed-container errors (torn zip, missing members, bad metadata)
    always raise `PlanValidationError` regardless of policy."""
    from repro.core.plan_guard import PlanValidationError

    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z["__meta__"][()]))
            if meta.get("version") != _SAVE_VERSION:
                raise PlanValidationError(
                    f"unsupported plan artifact version: "
                    f"{meta.get('version')!r}")
            kwargs: dict = {}
            for name in _SPEC_SCALAR_FIELDS:
                val = meta.get(name, _SPEC_SCALAR_DEFAULTS.get(name))
                if isinstance(val, list):
                    val = tuple(val)
                kwargs[name] = val
            for name in _SPEC_ARRAY_FIELDS:
                kwargs[name] = (z[f"s_{name}"]
                                if meta.get(f"has_{name}", False) else None)
            for name in _SPEC_TUPLE_FIELDS:
                ln = meta[f"len_{name}"]
                kwargs[name] = (None if ln < 0 else
                                tuple(z[f"s_{name}_{i}"] for i in range(ln)))
            spec = PlanSpec(**kwargs)
            nb = meta["len_cross_tgt_d0"]
            nl = meta["len_leaf_dists0"]
            params = PlanParams(
                cross_tgt_d=tuple(jnp.asarray(z[f"p_cross_tgt_d_{i}"])
                                  for i in range(nb)),
                cross_src_d=tuple(jnp.asarray(z[f"p_cross_src_d_{i}"])
                                  for i in range(nb)),
                leaf_dists=tuple(jnp.asarray(z[f"p_leaf_dists_{i}"])
                                 for i in range(nl)),
                tree_w=(jnp.asarray(z["p_tree_w"]) if meta["has_tree_w"]
                        else None),
            )
    except PlanValidationError:
        raise
    except Exception as e:
        # torn zip / missing npz member / mangled json / wrong dtype: one
        # clear error class so callers (disk cache, serving) reject cleanly
        raise PlanValidationError(
            f"load_plan({path!s}): corrupt or truncated plan artifact "
            f"({type(e).__name__}: {e})") from e
    # canonicalize dtype drift from older artifacts (schema <= 3 saved the
    # update tables as int64): bounds-guarded downcast, never silent wrap
    from repro.core import plan_guard

    spec, _coerced = plan_guard.coerce_index_dtypes(spec)
    if validate:
        plan_guard.validate(spec, params, where=f"load_plan({path!s})")
    return spec, params


# re-export: incremental edits live in their own module but belong to this
# API surface (imported at the bottom to avoid a circular import)
from repro.core.plan_update import update_plan  # noqa: E402,F401
