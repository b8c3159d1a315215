"""Mixture-of-Experts FFN with expert parallelism (DeepSeek-style).

Dispatch is sort-based (no (T, E, C) one-hots): flatten (token, k)
assignments, sort by expert, compute position-in-expert from sorted segment
offsets, scatter into an (E, C, d) buffer whose expert axis is sharded over
the `model` mesh axis (EP); XLA inserts the all-to-alls from the sharding
constraints. Capacity overflow drops lowest-priority assignments (standard
capacity-factor semantics); aux load-balancing loss included.

A configuration with `moe_held` takes the chip-share layer instead
(`held_moe_block`): told which experts it holds, it routes over all of
them, drops no token, and computes only its own experts' part.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.launch.sharding import shard
from repro.models.layers import dense_init


def moe_init(key, cfg, dtype=jnp.float32):
    """Router over all `num_experts`; expert weights for the held ones
    (`moe_held`, where given) or all of them."""
    d, E, ffe = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    n_held = len(cfg.moe_held) or E
    ks = jax.random.split(key, 8)
    p = {
        "router": dense_init(ks[0], (d, E), scale=0.02, dtype=jnp.float32),
        "experts_w_gate": dense_init(ks[1], (n_held, d, ffe), dtype=dtype),
        "experts_w_in": dense_init(ks[2], (n_held, d, ffe), dtype=dtype),
        "experts_w_out": dense_init(ks[3], (n_held, ffe, d), dtype=dtype),
    }
    if cfg.num_shared_experts > 0:
        ffs = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared_w_gate"] = dense_init(ks[4], (d, ffs), dtype=dtype)
        p["shared_w_in"] = dense_init(ks[5], (d, ffs), dtype=dtype)
        p["shared_w_out"] = dense_init(ks[6], (ffs, d), dtype=dtype)
    return p


def _dispatch_combine(cfg, p, xt):
    """Per-group dispatch -> expert FFN -> combine. xt: (T, d) -> ((T, d), aux).

    Sort-based capacity dispatch; the (E, C, d) buffer carries the
    ("experts", capacity, embed) sharding constraint so the expert axis is
    EP-sharded; when this function is vmapped over data-local groups
    (moe_groups > 1) the scatter/gather stay group-local and the only
    cross-device traffic is the buffer's data<->expert all-to-all."""
    T, d = xt.shape
    E, K = cfg.num_experts, cfg.top_k

    logits = (xt.astype(jnp.float32)) @ p["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * E * cfg.router_aux_loss

    C = int(cfg.capacity_factor * K * T / E)
    C = max(8, min(C, T))

    flat_expert = expert_ids.reshape(-1)  # (T*K,)
    flat_gate = gate_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), K)

    # position within expert via sort (stable: earlier tokens keep priority)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_e = flat_expert[order]
    idx = jnp.arange(T * K)
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_sorted = idx - seg_start[sorted_e]
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)

    keep = pos < C
    safe_pos = jnp.where(keep, pos, C - 1)

    buf = jnp.zeros((E, C, d), xt.dtype)
    buf = buf.at[flat_expert, safe_pos].add(
        jnp.where(keep[:, None], xt[flat_tok], 0.0).astype(xt.dtype))
    buf = shard(buf, ("experts", "expert_capacity", "embed"))

    actf = jax.nn.silu
    h = actf(jnp.einsum("ecd,edf->ecf", buf, p["experts_w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["experts_w_in"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["experts_w_out"])
    out_buf = shard(out_buf, ("experts", "expert_capacity", "embed"))

    gathered = out_buf[flat_expert, safe_pos]  # (T*K, d)
    weighted = gathered * (flat_gate * keep)[:, None].astype(xt.dtype)
    yt = jnp.zeros((T, d), xt.dtype).at[flat_tok].add(weighted)
    return yt, aux


def moe_block(cfg, p, x):
    """x: (B, L, d) -> (B, L, d) plus aux loss (scalar).

    moe_groups > 1 splits tokens into data-local groups (vmapped dispatch):
    the scatter/gather index ops become batch-sharded (GSPMD keeps them
    local) and the dispatch buffers meet the expert sharding through one
    all-to-all instead of replicating the token tensor (§Perf iteration B).
    Per-group capacity C/G preserves total capacity."""
    if cfg.moe_held:
        return held_moe_block(cfg, p, x)[:2]
    B, L, d = x.shape
    T = B * L
    G = max(1, getattr(cfg, "moe_groups", 1))
    if T % G:
        G = 1
    xt = x.reshape(T, d)
    if G == 1:
        yt, aux = _dispatch_combine(cfg, p, xt)
    else:
        from repro.launch.sharding import batch_axes

        xg = xt.reshape(G, T // G, d)
        xg = shard(xg, ("batch", None, "embed"))
        # spmd_axis_name shards the vmapped group dim over the data axes:
        # without it, vmapped sharding constraints force the G dim
        # REPLICATED and the expert einsums lose all data parallelism
        # (measured 16x flop overcompute; §Perf B2)
        dp = batch_axes()
        vfn = jax.vmap(lambda t: _dispatch_combine(cfg, p, t),
                       spmd_axis_name=dp if dp and len(dp) > 1 else
                       (dp[0] if dp else None))
        yg, auxg = vfn(xg)
        yg = shard(yg, ("batch", None, "embed"))
        yt, aux = yg.reshape(T, d), jnp.mean(auxg)

    if cfg.num_shared_experts > 0:
        actf = jax.nn.silu
        hs = actf(xt @ p["shared_w_gate"]) * (xt @ p["shared_w_in"])
        yt = yt + hs @ p["shared_w_out"]

    return yt.reshape(B, L, d), aux


# ----------------------------------------------------------------------------
# chip-share expert layer: the held experts' part, dropless
# ----------------------------------------------------------------------------

TILE = 512  # rows of one expert matmul of the dropless layer


def held_moe_block(cfg, p, x):
    """The part of a Mixtral-style MoE layer that the experts in
    `cfg.moe_held` give: p = softmax(h W_r) over all `num_experts`, the top
    k renormalized to sum to 1, and each token's sum over its chosen experts
    that are held here of weight * W2(SiLU(W1 h) * W3 h). Chosen experts
    that are not held add nothing (they live on other chips of the stated
    deployment). Dropless: every assignment is computed.

    Under a mesh with a `model` axis the held experts are split over it; each
    chip, holding every token, computes its own experts for the tokens
    routed to them and one all-reduce (`lm.moe.exchange`) sums the parts.
    x: (B, L, d) -> ((B, L, d), aux, the chosen experts' ids (B, L, k))."""
    from jax.sharding import PartitionSpec as P

    from repro.analysis import trace_guard
    from repro.launch.sharding import current_mesh

    B, L, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    held = jnp.asarray(cfg.moe_held, jnp.int32)
    n_held = len(cfg.moe_held)
    trace_guard.record("moe.held", event=str(n_held))
    xt = x.reshape(B * L, d)
    with jax.named_scope("lm.moe"):
        with jax.named_scope("lm.moe.router"):
            logits = jnp.dot(xt.astype(jnp.float32), p["router"],
                             precision=jax.lax.Precision.HIGHEST)
            probs = jax.nn.softmax(logits, axis=-1)
            gate_vals, ids = _top_k_gates(probs, K)
            # (T, n_held): each held expert's weight for each token, 0 where
            # the token did not choose it
            wts = jnp.sum(jnp.where(ids[:, :, None] == held[None, None, :],
                                    gate_vals[:, :, None], 0.0), axis=1)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32),
                          axis=0)
            aux = jnp.sum(me * ce) * E * cfg.router_aux_loss
        ws = (p["experts_w_gate"], p["experts_w_in"], p["experts_w_out"])
        mesh = current_mesh()
        size = mesh.shape.get("model", 1) if mesh is not None else 1
        if size > 1 and n_held % size == 0:
            yt = jax.shard_map(
                lambda xt, wts, *ws: _held_experts(xt, wts, ws, "model"),
                mesh=mesh, in_specs=(P(), P(None, "model")) + (P("model"),) * 3,
                out_specs=P(), check_vma=False)(xt, wts, *ws)
        else:
            yt = _held_experts(xt, wts, ws)
    return yt.reshape(B, L, d), aux, ids.reshape(B, L, K)


def _top_k_gates(probs, k):
    """The k most probable experts of each row, their weights renormalized
    to sum to 1: (weights (T, k), ids (T, k))."""
    g, ids = jax.lax.top_k(probs, k)
    return g / jnp.sum(g, axis=-1, keepdims=True), ids


def _held_experts(xt, wts, ws, axis=None):
    """sum_e wts[:, e] * FFN_e(xt) over the experts of `ws` (stacked on
    their first axis), in xt's dtype (a token meets at most top_k experts),
    then, under shard_map, the sum over `axis`."""
    with jax.named_scope("lm.moe.experts"):
        # a scan over the experts: each step reads its own expert's slice of
        # the stacked weights, so no expert's weights are copied out ahead
        y, _ = jax.lax.scan(
            lambda y, ex: (_expert_tiles(xt, ex[0], ex[1:], y), None),
            jnp.zeros_like(xt), (wts.T,) + tuple(ws))
    if axis is None:
        return y
    with jax.named_scope("lm.moe.exchange"):
        return jax.lax.psum(y, axis)


def _expert_tiles(xt, w, expert, y):
    """y + w[t] * W2(SiLU(W1 x_t) * W3 x_t) for every token t with w[t] > 0:
    the routed tokens, gathered in order, go through the expert in tiles of
    TILE rows; a tile that holds none of them is skipped (the loop has a
    static trip count and a `cond`, so it differentiates)."""
    T = xt.shape[0]
    tm = min(TILE, T)
    n_tiles = -(-T // tm)
    w_gate, w_in, w_out = expert
    n = jnp.sum(w > 0)
    idx = jnp.nonzero(w > 0, size=n_tiles * tm, fill_value=0)[0]

    def tile(t, y):
        def run(y):
            rows = jax.lax.dynamic_slice_in_dim(idx, t * tm, tm)
            xr = xt[rows]
            h = jax.nn.silu(xr @ w_gate) * (xr @ w_in)
            o = (h @ w_out).astype(jnp.float32)
            wr = jnp.where(t * tm + jnp.arange(tm) < n, w[rows], 0.0)
            return y.at[rows].add((o * wr[:, None]).astype(y.dtype))

        return jax.lax.cond(t * tm < n, run, lambda y: y, y)

    return jax.lax.fori_loop(0, n_tiles, tile, y)
