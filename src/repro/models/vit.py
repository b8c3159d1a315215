"""Topological Vision Transformer (paper Sec 4.4, TopViT with trees).

Performer attention with the RPE mask M = [f(dist_MST(i,j))] over the
2D-grid-graph MST of image patches, applied through Algorithm 1 with the
tree FastMult of `masks.make_tree_fastmult` (exact: a grid of at most
`masks.N_DENSE` patches takes the dense product by f(D), larger ones the
IT plan). 3 learnable mask scalars per layer (synced).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.lru import BoundedLRU
from repro.core.masks import (make_tree_fastmult, mask_f,
                               masked_attention_bruteforce,
                               masked_linear_attention, tree_distances)
from repro.graphs.graph import grid_graph
from repro.graphs.mst import minimum_spanning_tree
from repro.models import attention as A
from repro.models.layers import (dense_init, dtype_of, gated_mlp,
                                 gated_mlp_init, maybe_remat, rms_norm)

# side -> (spec, params) of the patch-grid MST plan: built once per side
# (or installed from an artifact), shared by every backend
_GRID_PLANS = BoundedLRU(8)
# sides whose plan already passed the pallas health probe
_PROBED = set()


@functools.lru_cache(maxsize=8)
def grid_mst(side: int):
    """The MST of the unit-weight side x side patch grid graph."""
    return minimum_spanning_tree(grid_graph(side, side))


def install_grid_plan(spec, params) -> int:
    """Adopt a prebuilt/loaded functional plan (e.g. an `ftfi.load_plan`
    artifact) as the grid plan for its side length: subsequent
    `build_grid_integrator` calls reuse it with ZERO IT rebuild. Returns
    the grid side. Serving startup uses this to trade the O(N log N)
    decomposition for one artifact read. The pair passes the plan guard
    first (FTFI_PLAN_GUARD policy): this is a door untrusted artifacts
    come in by, and the fused executor does no bounds checking of its
    own."""
    from repro.core import plan_guard

    side = int(round(np.sqrt(spec.n)))
    if side * side != spec.n:
        raise ValueError(
            f"plan covers n={spec.n} vertices: not a square patch grid")
    plan_guard.validate(spec, params, where="vit.install_grid_plan")
    _GRID_PLANS.put(side, (spec, params))
    _PROBED.discard(side)
    return side


def build_grid_integrator(cfg):
    """The (PlanSpec, PlanParams) pair of the patch-grid MST plan, built
    once per grid side (or served from `install_grid_plan`). The MST of a
    unit-weight grid graph is grid-aligned (grid_h == 1), so general mask
    functions ride the exact Hankel/FFT cross engine on the plan path.

    Degradation ladder: the first time a side is asked for on the pallas
    backend (resolved by `attention.resolve_topo_backend(cfg)`, as the
    masks resolve it: cfg.topo_backend > cfg.topo_attn_impl) its plan is
    health-probed on that rung BEFORE live traffic sees it — a kernel that
    fails to launch (or emits non-finite fields) blocks the rung globally,
    and the masks then resolve to the next one down."""
    from repro import ftfi

    side = int(round(np.sqrt(cfg.num_prefix_embeddings)))
    assert side * side == cfg.num_prefix_embeddings
    plan = _GRID_PLANS.get(side)
    if plan is None:
        plan = ftfi.build(grid_mst(side), leaf_size=16)
        _GRID_PLANS.put(side, plan)
    pallas = A.resolve_topo_backend(cfg) == "pallas"
    if pallas and side not in _PROBED:
        from repro.core import ladder

        _PROBED.add(side)
        reason = ladder.probe_backend(*plan, "pallas")
        if reason is not None:
            ladder.block_backend("pallas", f"grid {side}x{side} probe: "
                                 f"{reason}")
    return plan


def _vit_block_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    return {
        "attn_norm": {"scale": jnp.zeros((cfg.d_model,), dtype)},
        "attn": A.attn_init(ks[0], cfg, dtype),
        "topo": A.topo_init(ks[1], cfg, dtype),
        "mlp_norm": {"scale": jnp.zeros((cfg.d_model,), dtype)},
        "mlp": gated_mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype),
    }


def init_params(cfg, key, num_classes: int = 1000, patch_dim: int = 768):
    dtype = dtype_of(cfg)
    ks = jax.random.split(key, 6)
    blocks = jax.vmap(lambda k: _vit_block_init(k, cfg, dtype))(
        jax.random.split(ks[0], cfg.num_layers))
    L = cfg.num_prefix_embeddings
    return {
        "patch_proj": {"kernel": dense_init(ks[1], (patch_dim, cfg.d_model),
                                            dtype=dtype),
                       "bias": jnp.zeros((cfg.d_model,), dtype)},
        "pos_embed": (jax.random.normal(ks[2], (L, cfg.d_model)) * 0.02
                      ).astype(dtype),
        "blocks": blocks,
        "final_norm": {"scale": jnp.zeros((cfg.d_model,), dtype)},
        "head": {"kernel": dense_init(ks[3], (cfg.d_model, num_classes),
                                      dtype=dtype),
                 "bias": jnp.zeros((num_classes,), dtype)},
    }


def topo_vit_attention(cfg, p, p_topo, x, integ):
    """Grid-MST masked linear attention. The cfg.topo_attn_impl axis rides
    through here too: `ref` materializes the dense tree mask (oracle), any
    other impl runs Algorithm 1 with `make_tree_fastmult` over the grid
    plan `integ` and the grid MST: the dense product by f(D) on a grid of
    at most `masks.N_DENSE` patches, else the IT plan on the executor
    backend `attention.resolve_topo_backend` picks for `cfg` (plan vs
    fused pallas fdist_matvec)."""
    B, L, _ = x.shape
    q, k, v = A._project_qkv(cfg, p["attn"], x,
                             jnp.zeros((B, L), jnp.int32), rope=False)
    scale = A.topo_logit_scale(cfg, p_topo)  # (H,)
    qf = A.phi_features(q * scale[None, None, :, None], cfg.performer_phi)
    kf = A.phi_features(k, cfg.performer_phi)
    coeffs = A.topo_mask_coeffs(cfg, p_topo)[0]  # synced: same across heads
    # (B,L,H,m) -> heads folded into batch for Alg. 1
    qf_ = qf.transpose(0, 2, 1, 3)
    kf_ = kf.transpose(0, 2, 1, 3)
    v_ = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    mst = grid_mst(int(round(np.sqrt(L))))
    if getattr(cfg, "topo_attn_impl", "fft") == "ref":
        D = jnp.asarray(tree_distances(mst))
        out = masked_attention_bruteforce(
            qf_, kf_, v_, mask_f(cfg.topo_g, coeffs, cfg.topo_dist_scale)(D))
    else:
        with jax.named_scope("vit.alg1"):
            fastmult = make_tree_fastmult(
                integ, cfg.topo_g, coeffs, cfg.topo_dist_scale,
                backend=A.resolve_topo_backend(cfg), tree=mst,
                sharded=getattr(cfg, "topo_shard_plan", False))
            out = masked_linear_attention(qf_, kf_, v_, fastmult)
    out = out.transpose(0, 2, 1, 3).reshape(B, L, -1).astype(x.dtype)
    return out @ p["attn"]["wo"]


def forward(cfg, params, patches, integ):
    """patches: (B, L, patch_dim) -> logits (B, num_classes).
    `integ` is the grid plan, the (spec, params) pair from
    build_grid_integrator.

    The step's parts run under `jax.named_scope`s that a device trace sums
    its ops by: `vit.embed`, `vit.layer_params` (the per-layer slice of the
    stacked weights), `vit.attn` (with Alg. 1 as `vit.alg1` inside it),
    `vit.mlp`, `vit.head`. They name the compiled ops and change none."""
    with jax.named_scope("vit.embed"):
        x = patches.astype(dtype_of(cfg)) @ params["patch_proj"]["kernel"]
        x = x + params["patch_proj"]["bias"] + params["pos_embed"][None]
    B, L, _ = x.shape

    def body(x, p):
        h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps, plus_one=True)
        with jax.named_scope("vit.attn"):
            if cfg.attention_variant == "topo":
                x = x + topo_vit_attention(cfg, p, p["topo"], h, integ)
            else:
                x = x + A.performer_attention_train(
                    cfg, p["attn"], h,
                    jnp.zeros((B, L), jnp.int32), causal=False)
        h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps, plus_one=True)
        with jax.named_scope("vit.mlp"):
            x = x + gated_mlp(p["mlp"], h, cfg.mlp_act)
        return x, ()

    # per-layer remat: Alg. 1's expanded (L, m*hd) fields are the largest
    # activations; without it every layer's stay live for the backward
    # pass
    body = maybe_remat(body, cfg)
    # plan arrays are numpy constants: python loop over stacked params
    n = jax.tree.leaves(params["blocks"])[0].shape[0]
    for i in range(n):
        with jax.named_scope("vit.layer_params"):
            layer = jax.tree.map(lambda a: a[i], params["blocks"])
        x, _ = body(x, layer)
    with jax.named_scope("vit.head"):
        x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps,
                     plus_one=True)
        pooled = jnp.mean(x, axis=1)
        return pooled @ params["head"]["kernel"] + params["head"]["bias"]
