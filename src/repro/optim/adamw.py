"""AdamW + cosine schedule + global-norm clipping, pure JAX pytrees.

Kept deliberately optax-shaped (init/update pair over pytrees) so the train
loop composes transforms (e.g. optim.compress wraps the gradient stream).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jnp.ndarray  # () int32
    mu: object  # pytree like params
    nu: object


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1
    clip_norm: float = 1.0


def adamw_init(params) -> AdamWState:
    # f32 moments whatever the params' dtype: the update promotes them to f32
    # anyway, and starting there keeps the state's types the same every step
    zeros = lambda p: jnp.zeros_like(
        p, dtype=jnp.promote_types(p.dtype, jnp.float32))
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree.map(zeros, params),
        nu=jax.tree.map(zeros, params),
    )


def cosine_schedule(step, cfg: AdamWConfig):
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip(
        (step - cfg.warmup_steps) / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
        0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), gnorm


def adamw_update(grads, state: AdamWState, params, cfg: AdamWConfig):
    """Returns (new_params, new_state, metrics). Runs under the
    `jax.named_scope` "adamw", the global-norm clip included."""
    with jax.named_scope("adamw"):
        grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
        step = state.step + 1
        lr = cosine_schedule(step, cfg)
        b1, b2 = cfg.b1, cfg.b2
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), state.nu, grads)
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(p, m, v):
            mhat = m / bc1
            vhat = v / bc2
            return (p - lr * (mhat / (jnp.sqrt(vhat) + cfg.eps)
                              + cfg.weight_decay * p)).astype(p.dtype)

        new_params = jax.tree.map(upd, params, mu, nu)
        return new_params, AdamWState(step, mu, nu), {"grad_norm": gnorm, "lr": lr}
