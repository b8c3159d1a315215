"""Public fused topological masked linear attention (paper Alg. 1).

`topo_linear_attention` computes the whole masked linear-attention step
out = (M ⊙ phi(Q)phi(K)^T) V / rowsum(M ⊙ phi(Q)phi(K)^T) for the sequence
mask M = [f(i-j)] (causal) or [f(|i-j|)] (bidirectional) in one fused pass
over chunks of L:

  * on TPU the Pallas kernel (kernel.py) runs compiled; the backward pass
    rides a custom VJP that differentiates the mathematically identical XLA
    twin below (same chunk schedule, same separable expansion), so the 3
    learnable mask scalars train end-to-end through the fused forward;
  * off-TPU the XLA twin is selected directly (the `_sdpa_chunked` precedent:
    a lax.scan chunked scan with identical math, exact to fp32 rounding) —
    the Pallas kernel remains exercisable anywhere via
    `use_kernel=True, interpret=True` (tests/CI).

Mask families (selected from `g` and the coefficient count, both paths):
  separable — g=exp, deg<=1: gamma^(i-j) relative-decay state (exact);
  rank      — any g / low-degree polynomial: on-the-fly rank-R Chebyshev
              separable expansion of f for the cross-chunk tail
              (core.masks.chebyshev_separable_tables), exact within-chunk
              tile — spectral accuracy for the paper's smooth masks.

Coefficients are per-head (H, t+1) (a synced (t+1,) vector broadcasts), i.e.
both synced and asynced mask parameterizations ride the same kernel.

`normalize=False` returns the masked sum (M ⊙ QK^T) V itself, with no
denominator: the features may then have any sign (lightning attention's
SiLU features). Each call records the family it took in `trace_guard`
(`attention.topo:decay` / `attention.topo:rank`) once per trace.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis import trace_guard
from repro.core import masks as MK
from repro.kernels.topo_linear_attention.kernel import (
    topo_attention_sweep_pallas)


class TopoSpec(NamedTuple):
    """Static (hashable) configuration threaded through the custom VJP."""
    g: str
    dist_scale: float
    causal: bool
    chunk: int
    rank: int
    eps: float
    interpret: bool
    normalize: bool = True


def _round_up(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def _is_separable(g: str, coeffs) -> bool:
    return g == "exp" and coeffs.shape[-1] <= 2


def _prepare(spec: TopoSpec, coeffs, Lp: int):
    """Precompute the per-head mask ingredients for both sweep directions.

    Returns (lg, alpha, beta, dmat_inc, dmat_strict): `lg` (H,) for the
    separable decay mode (alpha/beta None), or rank-R position tables
    (H, Lp, R) with lg None. The dmats are the exact (H, C, C) within-chunk
    tiles (inclusive diagonal / strict). All pieces are differentiable in
    `coeffs`; in decay mode the e^{a0} mask factor is folded into kf by
    `_pad_inputs` (it cancels in the normalization except where the eps
    denominator clamp binds).
    """
    import numpy as np

    C = spec.chunk
    if _is_separable(spec.g, coeffs):
        H = coeffs.shape[0]
        lg = (coeffs[:, 1] * spec.dist_scale if coeffs.shape[-1] > 1
              else jnp.zeros((H,), jnp.float32))
        # within-chunk tile from gamma^(i-j) alone: a0 cancels in the
        # normalization and the cross-chunk state carries no a0 either
        d = np.arange(C)[:, None] - np.arange(C)[None, :]
        vals = jnp.exp(lg[:, None, None] * jnp.asarray(d, jnp.float32))
        dmat_inc = jnp.where(jnp.asarray(d >= 0), vals, 0.0)
        dmat_strict = jnp.where(jnp.asarray(d > 0), vals, 0.0)
        return lg, None, None, dmat_inc, dmat_strict
    alpha, beta = MK.chebyshev_separable_tables(
        spec.g, coeffs, Lp, spec.dist_scale, spec.rank)
    dmat_inc = MK.sequence_mask_matrix(spec.g, coeffs, C, spec.dist_scale)
    dmat_strict = MK.sequence_mask_matrix(spec.g, coeffs, C, spec.dist_scale,
                                          strict=True)
    return None, alpha, beta, dmat_inc, dmat_strict


def _pad_inputs(spec: TopoSpec, qf, kf, v, coeffs):
    """Pad L to a chunk multiple, in the inputs' own dtype (the kernel reads
    them into float32 tiles; the XLA twin upcasts)."""
    L = qf.shape[2]
    Lp = _round_up(L, spec.chunk)
    pad = ((0, 0), (0, 0), (0, Lp - L), (0, 0))
    if _is_separable(spec.g, coeffs) and spec.normalize:
        # decay mode carries gamma^(i-j) only; fold the mask's e^{a0} factor
        # into kf so num/den match the other impls even where the eps
        # denominator clamp binds (unnormalized, it scales the output)
        kf = kf.astype(jnp.float32) * jnp.exp(coeffs[:, 0])[None, :, None,
                                                             None]
    return jnp.pad(qf, pad), jnp.pad(kf, pad), jnp.pad(v, pad), Lp


def _a0_scale(spec: TopoSpec, coeffs, out):
    """The unnormalized decay-mode output times the mask's e^{a0}."""
    if _is_separable(spec.g, coeffs) and not spec.normalize:
        return out * jnp.exp(coeffs[:, 0])[None, :, None, None]
    return out


def _flip(t):
    return jnp.flip(t, axis=2) if t is not None else None


def _pallas_forward(spec: TopoSpec, qf, kf, v, coeffs):
    """Fused forward: one sweep (causal) or two fused sweeps (bidirectional,
    the second combining + normalizing in-kernel via residual inputs)."""
    L = qf.shape[2]
    qp, kp, vp, Lp = _pad_inputs(spec, qf, kf, v, coeffs)
    lg, alpha, beta, dmat_inc, dmat_strict = _prepare(spec, coeffs, Lp)
    kw = dict(chunk=spec.chunk, eps=spec.eps, interpret=spec.interpret)
    if spec.causal:
        out = topo_attention_sweep_pallas(
            qp, kp, vp, dmat_inc, log_gamma=lg, alpha=alpha, beta=beta,
            normalize=spec.normalize, **kw)
        out = out if spec.normalize else _a0_scale(spec, coeffs, out[0])
        return out[:, :, :L]
    num, den = topo_attention_sweep_pallas(
        qp, kp, vp, dmat_inc, log_gamma=lg, alpha=alpha, beta=beta,
        normalize=False, **kw)
    # Reversed strict sweep covers j > i; the forward partials ride in as
    # residuals so the combine + normalization stays in-kernel. The rank
    # tables are NOT flipped: the reversed sweep indexes row p' = Lp-1-p, and
    # alpha[Lp-1-i]·beta[Lp-1-j] ~= f((Lp-1-i) - (Lp-1-j)) = f(j - i) — the
    # correct (positive) anticausal distance. Flipping them along L would
    # evaluate f(i - j) instead and corrupt any odd-coefficient mask.
    out_rev = topo_attention_sweep_pallas(
        _flip(qp), _flip(kp), _flip(vp), dmat_strict, log_gamma=lg,
        alpha=alpha, beta=beta,
        res_num=_flip(num), res_den=jnp.flip(den, axis=2),
        normalize=spec.normalize, **kw)
    if not spec.normalize:
        out_rev = _a0_scale(spec, coeffs, out_rev[0])
    return _flip(out_rev)[:, :, :L]


# ----------------------------------------------------------------------------
# XLA twin (lax.scan, identical chunk schedule) — CPU/GPU path and the
# differentiation surface of the fused kernel's custom VJP
# ----------------------------------------------------------------------------


def _sweep_xla(qp, kp, vp, dmat, lg=None, alpha=None, beta=None):
    """One causal sweep over chunks; returns (num, den) pre-normalization."""
    qp, kp, vp = (t.astype(jnp.float32) for t in (qp, kp, vp))
    B, H, Lp, m = qp.shape
    hd = vp.shape[-1]
    C = dmat.shape[-1]
    nC = Lp // C
    qc = qp.reshape(B, H, nC, C, m).transpose(2, 0, 1, 3, 4)
    kc = kp.reshape(B, H, nC, C, m).transpose(2, 0, 1, 3, 4)
    vc = vp.reshape(B, H, nC, C, hd).transpose(2, 0, 1, 3, 4)
    if lg is not None:
        i = jnp.arange(C, dtype=jnp.float32)
        decq = jnp.exp(lg[:, None] * i[None, :])          # (H, C)
        deck = jnp.exp(lg[:, None] * (C - i[None, :]))
        gC = jnp.exp(lg * C)

        def step(carry, inp):
            S, z = carry  # (B,H,m,hd), (B,H,m)
            q, k, v = inp
            scores = jnp.einsum("bhim,bhjm->bhij", q, k) * dmat[None]
            num = jnp.einsum("bhij,bhjd->bhid", scores, v)
            den = jnp.sum(scores, axis=-1)
            qd = q * decq[None, :, :, None]
            num += jnp.einsum("bhim,bhmd->bhid", qd, S)
            den += jnp.einsum("bhim,bhm->bhi", qd, z)
            kd = k * deck[None, :, :, None]
            S = S * gC[None, :, None, None] + jnp.einsum(
                "bhjm,bhjd->bhmd", kd, v)
            z = z * gC[None, :, None] + jnp.sum(kd, axis=2)
            return (S, z), (num, den)

        carry0 = (jnp.zeros((B, H, m, hd), jnp.float32),
                  jnp.zeros((B, H, m), jnp.float32))
        xs = (qc, kc, vc)
    else:
        R = alpha.shape[-1]
        ac = alpha.reshape(H, nC, C, R).transpose(1, 0, 2, 3)
        bc = beta.reshape(H, nC, C, R).transpose(1, 0, 2, 3)

        def step(carry, inp):
            S, z = carry  # (B,H,R,m,hd), (B,H,R,m)
            q, k, v, a, b = inp
            scores = jnp.einsum("bhim,bhjm->bhij", q, k) * dmat[None]
            num = jnp.einsum("bhij,bhjd->bhid", scores, v)
            den = jnp.sum(scores, axis=-1)
            num += jnp.einsum("bhim,hir,bhrmd->bhid", q, a, S)
            den += jnp.einsum("bhim,hir,bhrm->bhi", q, a, z)
            S = S + jnp.einsum("bhjm,hjr,bhjd->bhrmd", k, b, v)
            z = z + jnp.einsum("bhjm,hjr->bhrm", k, b)
            return (S, z), (num, den)

        carry0 = (jnp.zeros((B, H, R, m, hd), jnp.float32),
                  jnp.zeros((B, H, R, m), jnp.float32))
        xs = (qc, kc, vc, ac, bc)
    _, (num, den) = jax.lax.scan(step, carry0, xs)
    num = num.transpose(1, 2, 0, 3, 4).reshape(B, H, Lp, hd)
    den = den.transpose(1, 2, 0, 3).reshape(B, H, Lp)
    return num, den


def _xla_forward(spec: TopoSpec, qf, kf, v, coeffs):
    L = qf.shape[2]
    qp, kp, vp, Lp = _pad_inputs(spec, qf, kf, v, coeffs)
    lg, alpha, beta, dmat_inc, dmat_strict = _prepare(spec, coeffs, Lp)
    num, den = _sweep_xla(qp, kp, vp, dmat_inc, lg, alpha, beta)
    if not spec.causal:
        # tables deliberately unflipped — see the comment in _pallas_forward
        nb, db = _sweep_xla(_flip(qp), _flip(kp), _flip(vp), dmat_strict,
                            lg, alpha, beta)
        num = num + _flip(nb)
        den = den + jnp.flip(db, axis=2)
    if not spec.normalize:
        return _a0_scale(spec, coeffs, num)[:, :, :L]
    den = jnp.where(jnp.abs(den) < spec.eps, spec.eps, den)
    return (num / den[..., None])[:, :, :L]


# ----------------------------------------------------------------------------
# custom VJP: fused Pallas forward, XLA-twin backward
# ----------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused(spec, qf, kf, v, coeffs):
    return _pallas_forward(spec, qf, kf, v, coeffs)


def _fused_fwd(spec, qf, kf, v, coeffs):
    return _pallas_forward(spec, qf, kf, v, coeffs), (qf, kf, v, coeffs)


def _fused_bwd(spec, res, ct):
    qf, kf, v, coeffs = res
    _, vjp = jax.vjp(functools.partial(_xla_forward, spec), qf, kf, v, coeffs)
    return vjp(ct)


_fused.defvjp(_fused_fwd, _fused_bwd)


# ----------------------------------------------------------------------------
# public entry
# ----------------------------------------------------------------------------


def topo_linear_attention(qf, kf, v, coeffs, *, g: str = "exp",
                          dist_scale: float = 1.0, causal: bool = True,
                          chunk: int = 128, rank: int = 16,
                          eps: float = 1e-6, normalize: bool = True,
                          use_kernel: bool | None = None,
                          interpret: bool | None = None):
    """Fused Alg.-1 masked linear attention over the sequence mask.

    qf/kf: (B, H, L, m) phi features (nonneg where normalized; any sign
    with normalize=False, which returns the unnormalized masked sum);
    v: (B, H, L, hd);
    coeffs: (H, t+1) or (t+1,) effective mask coefficients (already
    constraint-shaped, e.g. attention.topo_mask_coeffs). Any L (padded to a
    chunk multiple internally), any head count. Returns (B, H, L, hd) f32.

    use_kernel=None auto-selects the compiled Pallas kernel on TPU and the
    XLA twin elsewhere; use_kernel=True + interpret=True runs the kernel
    body in interpret mode anywhere (parity tests).
    """
    qf = jnp.asarray(qf)
    B, H, L, m = qf.shape
    coeffs = jnp.asarray(coeffs, jnp.float32)
    if coeffs.ndim == 1:
        coeffs = jnp.broadcast_to(coeffs[None], (H, coeffs.shape[0]))
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    if interpret is None:
        interpret = not on_tpu
    C = min(chunk, _round_up(L, 8))
    spec = TopoSpec(g, float(dist_scale), bool(causal), C, int(rank),
                    float(eps), bool(interpret), bool(normalize))
    trace_guard.record("attention.topo", event=(
        "decay" if _is_separable(g, coeffs) else "rank"))
    if use_kernel:
        return _fused(spec, qf, kf, v, coeffs)
    return _xla_forward(spec, qf, kf, v, coeffs)


def topo_linear_attention_sharded(qf, kf, v, coeffs, *, mesh,
                                  batch_axis: str = "data",
                                  head_axis: str = "model", **kw):
    """`topo_linear_attention` under shard_map: batch over the mesh's data
    axis and heads over its model axis. Every (batch, head) pair's masked
    linear-attention sweep is independent — each device runs the identical
    fused sweep on its (B/d, H/m) slab with zero collectives, so the result
    is bit-identical to the single-device call. An axis whose extent does
    not divide the corresponding dim is dropped (that dim runs replicated),
    mirroring `launch.sharding.shard`'s divisibility fallback."""
    from jax.sharding import PartitionSpec as P

    qf = jnp.asarray(qf)
    B, H = qf.shape[0], qf.shape[1]
    coeffs = jnp.asarray(coeffs, jnp.float32)
    if coeffs.ndim == 1:
        coeffs = jnp.broadcast_to(coeffs[None], (H, coeffs.shape[0]))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ba = batch_axis if B % sizes.get(batch_axis, 1) == 0 else None
    ha = head_axis if H % sizes.get(head_axis, 1) == 0 else None
    if ba is None and ha is None:
        return topo_linear_attention(qf, kf, v, coeffs, **kw)

    def local(q, k, vv, c):
        return topo_linear_attention(q, k, vv, c, **kw)

    io = P(ba, ha)
    return jax.shard_map(local, mesh=mesh, in_specs=(io, io, io, P(ha)),
                         out_specs=io, check_vma=False)(qf, kf, v, coeffs)
