"""Causal (gamma-decayed) linear attention Pallas kernel.

This is the hot path of the paper's Topological Performer for sequences:
masked linear attention with the separable g=exp mask gamma^(i-j) (and
gamma=1 = plain FAVOR+). Grid = (B*H, L chunks), chunk dim sequential; the
(m, hd) KV state and (m,) normalizer persist in VMEM scratch; within a chunk
the causal part is a masked (C, C) quadratic — the standard chunked-scan
linear-attention schedule, with the decay folded into the intra-chunk mask
and the state update (RetNet-style), matching models.attention's XLA path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lin_attn_kernel(q_ref, k_ref, v_ref, g_ref, num_ref, den_ref,
                     s_ref, z_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    lg = g_ref[0]  # log gamma (<= 0); block (None, 1) squeezes to (1,)
    q = q_ref[...].astype(jnp.float32)  # (C, m)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)  # (C, hd)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dmat = jnp.where(i >= j, jnp.exp(lg * (i - j).astype(jnp.float32)), 0.0)
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * dmat
    num_in = jnp.dot(scores, v, preferred_element_type=jnp.float32)
    den_in = jnp.sum(scores, axis=1)
    # inter-chunk: state decayed to each local position
    pos = jax.lax.broadcasted_iota(jnp.float32, (chunk, 1), 0)
    q_dec = q * jnp.exp(lg * pos)
    num_x = jnp.dot(q_dec, s_ref[...], preferred_element_type=jnp.float32)
    den_x = jnp.dot(q_dec, z_ref[...].reshape(-1, 1),
                    preferred_element_type=jnp.float32)[:, 0]
    num_ref[...] = (num_in + num_x).astype(num_ref.dtype)
    den_ref[...] = (den_in + den_x).reshape(1, -1).astype(den_ref.dtype)
    # update state: S' = gamma^C S + sum_t gamma^(C-t) k_t v_t^T
    k_dec = k * jnp.exp(lg * (chunk - pos))
    gC = jnp.exp(lg * chunk)
    s_ref[...] = gC * s_ref[...] + jnp.dot(k_dec.T, v,
                                           preferred_element_type=jnp.float32)
    z_ref[...] = gC * z_ref[...] + jnp.sum(k_dec, axis=0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def linear_attention_pallas(qf, kf, v, log_gamma, *, chunk: int = 256,
                            interpret: bool = False):
    """qf/kf: (B, H, L, m); v: (B, H, L, hd); log_gamma: (H,) <= 0.
    Returns (num (B,H,L,hd), den (B,H,L))."""
    B, H, L, m = qf.shape
    hd = v.shape[-1]
    chunk = min(chunk, L)
    assert L % chunk == 0
    qr = qf.reshape(B * H, L, m)
    kr = kf.reshape(B * H, L, m)
    vr = v.reshape(B * H, L, hd)
    lg = jnp.broadcast_to(jnp.asarray(log_gamma, jnp.float32).reshape(1, -1),
                          (B, H)).reshape(B * H, 1)
    num, den = pl.pallas_call(
        functools.partial(_lin_attn_kernel, chunk=chunk),
        grid=(B * H, L // chunk),
        in_specs=[
            pl.BlockSpec((None, chunk, m), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, m), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, 1), lambda b, c: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, 1, chunk), lambda b, c: (b, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, L, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, 1, L), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((m, hd), jnp.float32),
                        pltpu.VMEM((m,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr, lg)
    return num.reshape(B, H, L, hd), den.reshape(B, H, L)
