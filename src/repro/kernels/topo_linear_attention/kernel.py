"""Fused topological masked linear-attention Pallas kernel (paper Alg. 1).

One causal (prefix) sweep over chunks of L fuses the whole masked
linear-attention step for the sequence mask M = [f(i - j)]:

  * the phi-feature outer products k ⊗ v,
  * the masked prefix (lower-triangular Toeplitz) accumulation for both the
    numerator and the denominator,
  * and the normalized output num / den,

without ever materializing the (L, m*hd) expanded field the host-side
fft chunk-loop path streams through HBM. Grid = (B, H, L chunks) with the
chunk axis sequential; the running KV state and normalizer persist in VMEM
scratch across chunks.

Two state parameterizations (static `mode` of the sweep):
  decay — separable g=exp, deg<=1 masks gamma^(i-j): the state is decayed by
          gamma^C per chunk (RetNet-style relative decays — numerically safe
          for any L);
  rank  — general low-degree-polynomial masks via an on-the-fly rank-R
          separable expansion f(i-j) ~= sum_r alpha_r(i) beta_r(j)
          (Chebyshev tables from core.masks.chebyshev_separable_tables):
          the state carries R stacked (m, hd) moments.

Within-chunk the EXACT mask tile f(i-j) (precomputed (H, C, C) `dmat`, which
also encodes causal vs strict) is applied as a masked quadratic; only the
cross-chunk tail rides the separable state. Bidirectional masks compose two
sweeps (forward inclusive + reversed strict) — the second sweep takes the
first's num/den as residual inputs so the combine + normalization stays fused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack(refs, n_in, normalize):
    ins, rest = refs[:n_in], refs[n_in:]
    if normalize:
        outs, scratch = rest[:1], rest[1:]
    else:
        outs, scratch = rest[:2], rest[2:]
    return ins, outs, scratch


def _emit(num, den, outs, combine, normalize, res, eps):
    if combine:
        rn, rd = res
        num = num + rn[...]
        den = den + rd[...][0]
    if normalize:
        (out_ref,) = outs
        den = jnp.where(jnp.abs(den) < eps, eps, den)
        out_ref[...] = (num / den[:, None]).astype(out_ref.dtype)
    else:
        num_ref, den_ref = outs
        num_ref[...] = num.astype(num_ref.dtype)
        den_ref[...] = den.reshape(1, -1).astype(den_ref.dtype)


def _decay_kernel(*refs, chunk: int, eps: float, combine: bool,
                  normalize: bool):
    n_in = 5 + (2 if combine else 0)
    ins, outs, (s_ref, z_ref) = _unpack(refs, n_in, normalize)
    dmat_ref, q_ref, k_ref, v_ref, g_ref = ins[:5]
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    lg = g_ref[...]  # (1, 1) log gamma (<= 0)
    q = q_ref[...].astype(jnp.float32)  # (C, m)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)  # (C, hd)
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * dmat_ref[...]
    num = jnp.dot(scores, v, preferred_element_type=jnp.float32)
    den = jnp.sum(scores, axis=1)
    # inter-chunk: state decayed to each local position
    # Mosaic's iota is integer-only
    pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(
        jnp.float32)
    q_dec = q * jnp.exp(lg * pos)
    num += jnp.dot(q_dec, s_ref[...], preferred_element_type=jnp.float32)
    den += jnp.dot(q_dec, z_ref[...], preferred_element_type=jnp.float32)[:, 0]
    _emit(num, den, outs, combine, normalize, ins[5:], eps)
    # S' = gamma^C S + sum_t gamma^(C-t) k_t v_t^T
    k_dec = k * jnp.exp(lg * (chunk - pos))
    gC = jnp.exp(lg * chunk)
    s_ref[...] = gC * s_ref[...] + jnp.dot(k_dec.T, v,
                                           preferred_element_type=jnp.float32)
    z_ref[...] = gC * z_ref[...] + jnp.sum(k_dec, axis=0)[:, None]


def _rank_kernel(*refs, chunk: int, rank: int, eps: float, combine: bool,
                 normalize: bool):
    n_in = 6 + (2 if combine else 0)
    ins, outs, (s_ref, z_ref) = _unpack(refs, n_in, normalize)
    dmat_ref, q_ref, k_ref, v_ref, a_ref, b_ref = ins[:6]
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    q = q_ref[...].astype(jnp.float32)  # (C, m)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)  # (C, hd)
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * dmat_ref[...]
    num = jnp.dot(scores, v, preferred_element_type=jnp.float32)
    den = jnp.sum(scores, axis=1)
    # inter-chunk: alpha-weighted read of the R stacked (m, hd) moments
    a = a_ref[...]  # (C, R) position table
    qa = jnp.concatenate([a[:, r:r + 1] * q for r in range(rank)], axis=1)
    num += jnp.dot(qa, s_ref[...], preferred_element_type=jnp.float32)
    den += jnp.dot(qa, z_ref[...], preferred_element_type=jnp.float32)[:, 0]
    _emit(num, den, outs, combine, normalize, ins[6:], eps)
    b = b_ref[...]  # (C, R)
    kb = jnp.concatenate([b[:, r:r + 1] * k for r in range(rank)], axis=1)
    s_ref[...] += jnp.dot(kb.T, v, preferred_element_type=jnp.float32)
    z_ref[...] += jnp.sum(kb, axis=0)[:, None]


@functools.partial(jax.jit,
                   static_argnames=("normalize", "chunk", "eps", "interpret"))
def topo_attention_sweep_pallas(qf, kf, v, dmat, *, log_gamma=None,
                                alpha=None, beta=None, res_num=None,
                                res_den=None, normalize: bool = True,
                                chunk: int = 128, eps: float = 1e-6,
                                interpret: bool = False):
    """One fused causal sweep. qf/kf: (B, H, L, m); v: (B, H, L, hd);
    dmat: (H, C, C) exact within-chunk mask tile (encodes causal/strict).

    Exactly one of `log_gamma` (H,) [decay mode] or `alpha`+`beta` (H, L, R)
    position tables [rank mode] selects the cross-chunk state. Optional
    res_num (B, H, L, hd) / res_den (B, H, L) are added before normalization
    (the bidirectional combine). L must be a multiple of `chunk` (ops pads).

    Returns out (B, H, L, hd) f32 if normalize, else (num, den (B, H, L)).
    """
    B, H, L, m = qf.shape
    hd = v.shape[-1]
    C = chunk
    assert L % C == 0, f"L={L} must be a multiple of chunk={C}"
    nC = L // C
    decay = log_gamma is not None
    assert decay != (alpha is not None), "pass log_gamma XOR alpha/beta"
    combine = res_num is not None

    q_spec = pl.BlockSpec((None, None, C, m), lambda b, h, c: (b, h, c, 0))
    v_spec = pl.BlockSpec((None, None, C, hd), lambda b, h, c: (b, h, c, 0))
    den_spec = pl.BlockSpec((None, None, 1, C), lambda b, h, c: (b, h, 0, c))
    in_specs = [pl.BlockSpec((None, C, C), lambda b, h, c: (h, 0, 0)),
                q_spec, q_spec, v_spec]
    inputs = [dmat.astype(jnp.float32), qf, kf, v]
    if decay:
        body = functools.partial(_decay_kernel, chunk=C, eps=eps,
                                 combine=combine, normalize=normalize)
        # (H, 1, 1): the block's last two dims equal the array's, which is
        # what Mosaic requires of a block narrower than the (8, 128) tile
        in_specs.append(pl.BlockSpec((None, 1, 1), lambda b, h, c: (h, 0, 0)))
        inputs.append(jnp.asarray(log_gamma, jnp.float32).reshape(H, 1, 1))
        scratch = [pltpu.VMEM((m, hd), jnp.float32),
                   pltpu.VMEM((m, 1), jnp.float32)]
    else:
        R = alpha.shape[-1]
        body = functools.partial(_rank_kernel, chunk=C, rank=R, eps=eps,
                                 combine=combine, normalize=normalize)
        tab_spec = pl.BlockSpec((None, C, R), lambda b, h, c: (h, c, 0))
        in_specs += [tab_spec, tab_spec]
        inputs += [alpha.astype(jnp.float32), beta.astype(jnp.float32)]
        scratch = [pltpu.VMEM((R * m, hd), jnp.float32),
                   pltpu.VMEM((R * m, 1), jnp.float32)]
    if combine:
        in_specs += [v_spec, den_spec]
        inputs += [res_num.astype(jnp.float32),
                   res_den.astype(jnp.float32).reshape(B, H, 1, L)]
    if normalize:
        out_specs = [v_spec]
        out_shape = [jax.ShapeDtypeStruct((B, H, L, hd), jnp.float32)]
    else:
        out_specs = [v_spec, den_spec]
        out_shape = [jax.ShapeDtypeStruct((B, H, L, hd), jnp.float32),
                     jax.ShapeDtypeStruct((B, H, 1, L), jnp.float32)]
    got = pl.pallas_call(
        body,
        grid=(B, H, nC),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*inputs)
    if normalize:
        return got[0]
    return got[0], got[1].reshape(B, H, L)
