"""Plain float32 reference of MiniMax-Text-01's forward pass, written from
the published description (the public `minimax_text_01` modeling code and
config.json) and independent of `repro.models`: `jax.numpy` at
`jax.default_matmul_precision("highest")`, one sequence at a time, with no
kernel, cache, batching or sharding.

Per layer l (published index), with x the residual stream:

    lightning (attn_type 0), H heads of hd:
        [q|k|v]_h = SiLU(x W_qkv)        (columns per head: q, k, v)
        s_{h,l}   = 2^(-8(h+1)/H) * (1 - l/(N-1) + 1e-5)
        o_{h,i}   = sum_{j<=i} exp(-s_{h,l}(i-j)) (q_{h,i}.k_{h,j}) v_{h,j}
                    (the explicit masked quadratic form)
        y         = W_out(sigmoid(x W_g) * RMSNorm(concat_h o_h))
    softmax (attn_type 1): GQA, RoPE (theta, non-interleaved halves) on the
        first rotary_dim dims of each head, causal softmax scaled 1/sqrt(hd)
    block (postnorm): h = RMSNorm(x); x = alpha h + beta Attn(h);
                      h = RMSNorm(x); x = alpha h + beta MoE(h)
    MoE: p = softmax(h W_r) over all E experts, top k renormalized to 1,
         sum over the chosen experts that are held of
         p * W2(SiLU(W1 h) * W3 h); an absent expert adds nothing

then a final RMSNorm and the untied head. RMSNorm: x / sqrt(mean(x^2) +
eps) * (1 + scale) (the gain is stored as its offset from 1, as the program
stores it). Departures from the published model: the held experts and the
vocabulary slice of a chip-share configuration; H a power of two (the
published slope rule's other branch is not written here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(t):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), t)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + scale)


def slopes(H: int, layer: int, n_layers: int) -> np.ndarray:
    base = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    return base * (1.0 - layer / (n_layers - 1) + 1e-5)


def lightning(cfg, p, x, layer: int):
    L = x.shape[0]
    H, hd = cfg.num_heads, cfg.head_dim
    qkv = jax.nn.silu(x @ p["w_qkv"]).reshape(L, H, 3, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.asarray(slopes(H, layer, cfg.lightning_decay_layers), F32)
    i = np.arange(L)
    dist = (i[:, None] - i[None, :]).astype(np.float32)
    mask = jnp.where(jnp.asarray(dist >= 0)[None],
                     jnp.exp(-s[:, None, None]
                             * jnp.asarray(np.maximum(dist, 0))[None]), 0.0)
    scores = jnp.einsum("ihd,jhd->hij", q, k) * mask
    o = jnp.einsum("hij,jhd->ihd", scores, v).reshape(L, H * hd)
    o = rms_norm(o, p["out_norm"], cfg.norm_eps)
    return (jax.nn.sigmoid(x @ p["w_gate"]) * o) @ p["wo"]


def rope(x, theta: float, rd: int):
    """Rotate the first rd dims of each head (halves convention)."""
    L = x.shape[0]
    inv = 1.0 / theta ** (np.arange(0, rd, 2, dtype=np.float64) / rd)
    ang = jnp.asarray(np.arange(L)[:, None] * inv[None], F32)[:, None]
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rd:]],
                           axis=-1)


def softmax_attention(cfg, p, x):
    L = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rd = cfg.rotary_dim or hd
    q = rope((x @ p["wq"]).reshape(L, H, hd), cfg.rope_theta, rd)
    k = rope((x @ p["wk"]).reshape(L, KV, hd), cfg.rope_theta, rd)
    v = (x @ p["wv"]).reshape(L, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    logits = jnp.einsum("ihd,jhd->hij", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((L, L), bool))
    w = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("hij,jhd->ihd", w, v).reshape(L, H * hd) @ p["wo"]


def moe(cfg, p, x):
    probs = jax.nn.softmax(x @ p["router"], axis=-1)  # (L, E)
    top = jnp.argsort(-probs, axis=-1)[:, :cfg.top_k]
    g = jnp.take_along_axis(probs, top, axis=-1)
    g = g / jnp.sum(g, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e, eid in enumerate(cfg.moe_held):
        w = jnp.sum(jnp.where(top == eid, g, 0.0), axis=-1)  # (L,)
        h = jax.nn.silu(x @ p["experts_w_gate"][e]) * (x @ p["experts_w_in"][e])
        y = y + w[:, None] * (h @ p["experts_w_out"][e])
    return y


def layer_kinds(cfg) -> list[str]:
    """The kind of each layer: the superblock pattern repeated."""
    return list(cfg.superblock) * cfg.num_superblocks


def forward(cfg, params, tokens, routes: list | None = None) -> jax.Array:
    """Logits (L, padded vocab) of one sequence `tokens` (L,). `routes`, a
    list, receives each layer's top-k expert ids (L, k), sorted."""
    with jax.default_matmul_precision("highest"):
        P = _f32(params)
        a, b = cfg.residual_alpha, cfg.residual_beta
        x = P["embed"]["table"][jnp.asarray(tokens)]
        for l, kind in enumerate(layer_kinds(cfg)):
            j, bi = divmod(l, len(cfg.superblock))
            p = jax.tree.map(lambda t: t[j],
                             P["blocks0"][f"b{bi}_{kind}"])
            h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps)
            y = (lightning(cfg, p["attn"], h, l) if kind == "lightning"
                 else softmax_attention(cfg, p["attn"], h))
            x = a * h + b * y
            h = rms_norm(x, p["mlp_norm"]["scale"], cfg.norm_eps)
            x = a * h + b * moe(cfg, p["moe"], h)
            if routes is not None:
                top = jnp.argsort(-(h @ p["moe"]["router"]), axis=-1)
                routes.append(np.sort(np.asarray(top[:, :cfg.top_k]), -1))
        x = rms_norm(x, P["final_norm"]["scale"], cfg.norm_eps)
        return x @ P["lm_head"]["kernel"]
