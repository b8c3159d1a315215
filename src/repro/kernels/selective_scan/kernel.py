"""Mamba selective-scan Pallas kernel (chunked sequential grid).

Grid = (batch, d_inner blocks, L chunks) with the L dimension sequential:
the (blk_d, N) hidden state lives in VMEM scratch and persists across chunk
steps; each chunk walks its timesteps with a fori_loop. HBM traffic is the
inputs/outputs only — the (L, d, N) discretized tensors are built on the fly
per timestep in VMEM (the same "structure = recompute" move as fdist_matvec).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, h_ref, *,
                 chunk: int):
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...]  # (blk_d, N)
    D = d_ref[...]  # (1, blk_d)

    def step(t, h):
        u_t = u_ref[t, :].astype(jnp.float32)  # (blk_d,)
        dt_t = dt_ref[t, :].astype(jnp.float32)
        b_t = b_ref[t, :].astype(jnp.float32)  # (N,)
        c_t = c_ref[t, :].astype(jnp.float32)
        dA = jnp.exp(dt_t[:, None] * A)  # (blk_d, N)
        h = dA * h + (dt_t * u_t)[:, None] * b_t[None, :]
        y = jnp.sum(h * c_t[None, :], axis=-1) + u_t * D[0]
        y_ref[t, :] = y.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "blk_d", "interpret"))
def selective_scan_pallas(u, dt, A, B, C, D, *, chunk: int = 128,
                          blk_d: int = 512, interpret: bool = False):
    """u, dt: (Bt, L, din); A: (din, N); B, C: (Bt, L, N); D: (din,).
    Returns y: (Bt, L, din). L % chunk == 0, din % blk_d == 0 required."""
    Bt, L, din = u.shape
    N = A.shape[1]
    chunk = min(chunk, L)
    blk_d = min(blk_d, din)
    assert L % chunk == 0 and din % blk_d == 0
    grid = (Bt, din // blk_d, L // chunk)
    out = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, blk_d), lambda b, dblk, l: (b, l, dblk)),
            pl.BlockSpec((None, chunk, blk_d), lambda b, dblk, l: (b, l, dblk)),
            pl.BlockSpec((blk_d, N), lambda b, dblk, l: (dblk, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, dblk, l: (b, l, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, dblk, l: (b, l, 0)),
            pl.BlockSpec((1, blk_d), lambda b, dblk, l: (0, dblk)),
        ],
        out_specs=pl.BlockSpec((None, chunk, blk_d),
                               lambda b, dblk, l: (b, l, dblk)),
        out_shape=jax.ShapeDtypeStruct((Bt, L, din), u.dtype),
        scratch_shapes=[pltpu.VMEM((blk_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, dt, A.astype(jnp.float32), B, C, D.reshape(1, -1).astype(jnp.float32))
    return out
