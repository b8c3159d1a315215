"""Shared neural layers (pure JAX, param pytrees = nested dicts)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.launch.sharding import shard


def dtype_of(cfg):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[cfg.dtype]


def maybe_remat(f, cfg):
    """Wrap one layer body in `jax.checkpoint` per `cfg.remat` and
    `cfg.remat_policy`: "dots" saves non-batched matmul outputs, "nothing"
    recomputes everything, "none" (or remat=False) saves everything."""
    pol = getattr(cfg, "remat_policy", "dots")
    if not cfg.remat or pol == "none":
        return f
    if pol == "nothing":  # full recompute: minimum live activations
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(
        f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def dense_init(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(fan_in)
    return (jax.random.normal(key, shape) * s).astype(dtype)


def rms_norm(x, scale, eps=1e-6, plus_one=False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale) if plus_one else scale
    return (y * w).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., L, H, hd), positions: broadcastable to (..., L)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., L, hd/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., None, :]
    cos = cos[..., None, :]  # (..., L, 1, hd/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def gated_mlp_init(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype=dtype),
        "w_in": dense_init(k2, (d_model, d_ff), dtype=dtype),
        "w_out": dense_init(k3, (d_ff, d_model), dtype=dtype),
    }


def gated_mlp(p, x, act: str = "silu"):
    actf = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]
    h = actf(x @ p["w_gate"]) * (x @ p["w_in"])
    h = shard(h, ("batch", "seq", "ff"))
    return h @ p["w_out"]


def embed_init(key, vocab, d_model, dtype=jnp.float32):
    return {"table": (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)}


def softcap(logits, cap: float):
    if cap and cap > 0:
        return cap * jnp.tanh(logits / cap)
    return logits


def cross_entropy_loss(logits, labels, vocab_size: int, z_loss: float = 1e-4):
    """Mean next-token CE in fp32, with z-loss; labels < 0 are masked."""
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0) & (labels < vocab_size)
    labels_c = jnp.clip(labels, 0, vocab_size - 1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
    nll = logz - gold + z_loss * jnp.square(logz)
    nll = jnp.where(mask, nll, 0.0)
    denom = jnp.maximum(jnp.sum(mask), 1)
    return jnp.sum(nll) / denom
