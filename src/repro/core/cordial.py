"""Cordial functions: fast multiplication with matrices M = [f(x_i + y_j)].

This is the LDR/structured-matrix heart of the paper (Sec 3.2.1):

  engine        f class                         exact?   complexity
  ------------  ------------------------------  -------  -----------------
  dense         any                             yes      O(a·b·d)
  polynomial    sum_t c_t x^t                   yes      O((a+b)·B·d)
  exponential   s·exp(λx)                       yes      O((a+b)·d)      (rank 1)
  exp_poly      poly(x)·exp(λx)                 yes      O((a+b)·B·d)
  trigonometric cos/sin(ωx+φ)                   yes      O((a+b)·d)      (rank 2)
  hankel_fft    ANY f, grid-aligned x,y         yes      O(L log L·d), L=grid span
                (unit/rational tree weights —
                 subsumes the paper's
                 Vandermonde D1·V·D2 case)
  chebyshev     any f analytic near [lo,hi]     ~eps     O((a+b)·r·d + r²·d)
                (covers rational f and
                 exp(λx)/(x+c) Cauchy-LDR —
                 spectral convergence)

All engines are written against an array namespace `xp` (numpy or jax.numpy) so
the same code drives host-side graph workloads and the jit'ed in-model plan
executor. Shapes: x (a,), y (b,), V (b, d) -> out (a, d).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable

import numpy as np

# ----------------------------------------------------------------------------
# low-level engines
# ----------------------------------------------------------------------------


def dense_matvec(f: Callable, x, y, V, xp=np):
    M = f(x[:, None] + y[None, :])
    return M @ V


def polynomial_matvec(coeffs, x, y, V, xp=np):
    """f(z) = sum_t coeffs[t] z^t. Exact low-rank outer-product decomposition.

    M = sum_t c_t sum_l C(t,l) x^l (y^{t-l})  =>  out = Xpow @ W,
      S[u]  = sum_j y_j^u V[j]
      W[l]  = sum_{t>=l} c_t C(t,l) S[t-l]
    """
    coeffs = xp.asarray(coeffs)
    B = coeffs.shape[0] - 1
    # powers: (n, B+1)
    xp_pows = _powers(x, B, xp)  # (a, B+1)
    yp_pows = _powers(y, B, xp)  # (b, B+1)
    S = yp_pows.T @ V  # (B+1, d)
    # binomial table
    binom = _binom_table(B, xp, like=coeffs)
    # W[l] = sum_t c_t binom[t, l] S[t-l]  for t in [l, B]
    d = V.shape[1:]
    W = xp.zeros((B + 1,) + d, dtype=V.dtype)
    for l in range(B + 1):
        acc = 0.0
        for t in range(l, B + 1):
            acc = acc + coeffs[t] * binom[t, l] * S[t - l]
        W = _set_row(W, l, acc, xp)
    return xp_pows @ W.reshape(B + 1, -1) if len(d) > 1 else xp_pows @ W


def _powers(x, B, xp):
    pows = [xp.ones_like(x)]
    for _ in range(B):
        pows.append(pows[-1] * x)
    return xp.stack(pows, axis=-1)


def _binom_table(B, xp, like=None):
    tbl = np.zeros((B + 1, B + 1))
    for t in range(B + 1):
        for l in range(t + 1):
            tbl[t, l] = math.comb(t, l)
    return xp.asarray(tbl)


def _set_row(W, l, val, xp):
    if xp is np:
        W[l] = val
        return W
    return W.at[l].set(val)


def exponential_matvec(lam, x, y, V, xp=np, scale=1.0):
    """f(z) = scale * exp(lam * z). Rank-1, numerically shifted."""
    ly = lam * y
    m = xp.max(ly) if y.shape[0] else 0.0
    t = xp.exp(ly - m) @ V  # (d,)
    return scale * xp.exp(lam * x + m)[:, None] * t[None, :]


def exp_poly_matvec(lam, coeffs, x, y, V, xp=np):
    """f(z) = exp(lam z) * poly(z). Hadamard of rank-1 and low-rank (A.2.3)."""
    ly = lam * y
    m = xp.max(ly) if y.shape[0] else 0.0
    Vexp = xp.exp(ly - m)[:, None] * V
    out = polynomial_matvec(coeffs, x, y, Vexp, xp=xp)
    return xp.exp(lam * x + m)[:, None] * out


def trig_matvec(omega, phi, x, y, V, kind="cos", xp=np):
    """f(z) = cos(w z + phi) (or sin). Rank-2 via angle addition."""
    cx, sx = xp.cos(omega * x + phi), xp.sin(omega * x + phi)
    cy, sy = xp.cos(omega * y), xp.sin(omega * y)
    Sc = cy @ V
    Ss = sy @ V
    if kind == "cos":  # cos(A+B) = cosA cosB - sinA sinB
        return cx[:, None] * Sc[None, :] - sx[:, None] * Ss[None, :]
    # sin(A+B) = sinA cosB + cosA sinB
    return sx[:, None] * Sc[None, :] + cx[:, None] * Ss[None, :]


def snap_to_grid(x, h, xp=np, tol=1e-6):
    """Integer grid indices of x w.r.t. spacing h; raises if not grid-aligned."""
    ix = x / h
    ri = xp.round(ix)
    if xp is np and np.max(np.abs(ix - ri)) > tol:
        raise ValueError("values are not aligned to the grid")
    return ri.astype(xp.int32 if xp is not np else np.int64)


def detect_grid(x, y, tol=1e-9) -> float | None:
    """Find spacing h such that all x,y are (close to) integer multiples of h.

    Uses a float-gcd; returns None if no reasonable grid exists (h too small).
    """
    vals = np.abs(np.concatenate([np.asarray(x).ravel(), np.asarray(y).ravel()]))
    vals = np.unique(vals[vals > tol])  # dedupe: the gcd loop is per-value
    if vals.size == 0:
        return 1.0
    # fast path: the smallest value divides everything (unit/rational-weight
    # trees) — one vectorized residual check instead of the gcd loop. Below
    # the 1e-7 noise floor the residual test is meaningless (tol-scale
    # values pass it spuriously), so such inputs take the gcd loop, which
    # rejects them exactly as before.
    h = float(vals[0])
    mult = vals / h
    if h >= 1e-7 and float(np.max(np.abs(vals - np.round(mult) * h))) <= tol:
        return None if float(vals[-1] / h) > 5e6 else h
    g = h
    for v in vals[1:]:
        g = _fgcd(g, float(v), tol)
        if g < 1e-7:
            return None
    span = float(vals.max() / g)
    if span > 5e6:  # FFT length would be impractical
        return None
    return g


def _fgcd(a, b, tol):
    while b > tol:
        a, b = b, a % b
        if b > tol and b / a > 1 - 1e-12:
            b = 0.0
    return a


def hankel_fft_matvec(f: Callable, x, y, V, h: float, xp=np):
    """Exact multiply for ANY f when x, y lie on a common grid of spacing h.

    This is the paper's 'trees with positive rational weights' embedding
    (App. A.2.3) and subsumes the Vandermonde case used by its best ViT
    variants: M embeds into a Hankel matrix; multiplication by correlation
    with the sampled kernel F[k] = f(k·h) via FFT, O(L log L).
    """
    if xp is not np:  # static shapes required under jit: see core.toeplitz
        raise NotImplementedError("hankel_fft_matvec is the host/numpy path")
    ix = snap_to_grid(x, h, xp=xp)  # (a,)
    iy = snap_to_grid(y, h, xp=xp)  # (b,)
    max_ix = int(ix.max()) if ix.size else 0
    max_iy = int(iy.max()) if iy.size else 0
    L = max_ix + max_iy + 1
    F = f(h * np.arange(L, dtype=np.float64))  # (L,)
    # scatter V by iy:  P[m] = sum_{j: iy[j]=m} V[j]
    d = V.shape[1]
    P = np.zeros((max_iy + 1, d), dtype=np.result_type(V.dtype, np.float64))
    np.add.at(P, iy, V)
    out_full = fft_correlate(F, P, xp=np)  # (L, d) ; out_full[k] = sum_m F[k+m] P[m]
    return out_full[ix].astype(V.dtype)


def fft_correlate(F, P, xp=np):
    """out[k] = sum_m F[k+m] P[m] for k in [0, len(F)-1]; zero-padded FFT."""
    L = F.shape[0]
    m = P.shape[0]
    n = 1 << int(np.ceil(np.log2(L + m)))
    Ff = xp.fft.rfft(F, n=n)
    # correlation = conv with reversed P
    Pf = xp.fft.rfft(P[::-1], n=n, axis=0)
    prod = Ff[:, None] * Pf
    full = xp.fft.irfft(prod, n=n, axis=0)
    # index k of correlation sits at position k + m - 1 of the convolution
    return full[m - 1 : m - 1 + L]


def chebyshev_points(lo, hi, r, xp=np):
    k = np.arange(r)
    t = np.cos((2 * k + 1) * np.pi / (2 * r))  # Chebyshev nodes of 1st kind
    return xp.asarray((lo + hi) / 2.0 + (hi - lo) / 2.0 * t)


def _barycentric_weights(nodes):
    # for Chebyshev 1st-kind nodes: w_k = (-1)^k sin((2k+1)pi/(2r))
    r = nodes.shape[0]
    k = np.arange(r)
    return (-1.0) ** k * np.sin((2 * k + 1) * np.pi / (2 * r))


def lagrange_matrix(pts, nodes, xp=np):
    """L[i, k] = k-th Lagrange cardinal function at pts[i] (barycentric)."""
    w = xp.asarray(_barycentric_weights(np.asarray(nodes)))
    diff = pts[:, None] - nodes[None, :]
    # handle exact hits
    small = xp.abs(diff) < 1e-14
    diff = xp.where(small, 1.0, diff)
    terms = w[None, :] / diff
    L = terms / xp.sum(terms, axis=1, keepdims=True)
    any_small = xp.any(small, axis=1, keepdims=True)
    L = xp.where(any_small, small.astype(L.dtype), L)
    return L


def chebyshev_matvec(f: Callable, x, y, V, degree: int = 32, xp=np,
                     tol: float | None = None, _depth: int = 0):
    """Low-rank multiply via 2D Chebyshev interpolation of f(x+y).

    f(x_i+y_j) ~= sum_{k,l} B[k,l] Lx[i,k] Ly[j,l],  B[k,l] = f(xc_k + yc_l).
    Spectral accuracy for f analytic in a neighbourhood of [x_lo+y_lo, x_hi+y_hi].
    If `tol` is given (numpy path only), the x/y boxes are bisected adaptively
    (H-matrix style) until the sampled interpolation error is below tol —
    this covers sharply-peaked rational f and Cauchy-like kernels.
    """
    if x.shape[0] == 0 or y.shape[0] == 0:
        return xp.zeros((x.shape[0],) + V.shape[1:], dtype=V.dtype)
    x_lo, x_hi = xp.min(x), xp.max(x)
    y_lo, y_hi = xp.min(y), xp.max(y)
    if xp is np:
        x_lo, x_hi, y_lo, y_hi = float(x_lo), float(x_hi), float(y_lo), float(y_hi)
    xc = chebyshev_points(x_lo, x_hi + 1e-12, degree, xp)
    yc = chebyshev_points(y_lo, y_hi + 1e-12, degree, xp)
    B = f(xc[:, None] + yc[None, :])  # (r, r)
    Lx = lagrange_matrix(x, xc, xp)  # (a, r)
    Ly = lagrange_matrix(y, yc, xp)  # (b, r)
    out = Lx @ (B @ (Ly.T @ V))
    if tol is not None and xp is np and _depth < 12:
        # sample a few entries to estimate error; bisect if too large
        rng = np.random.default_rng(0)
        na = min(16, x.shape[0])
        nb = min(16, y.shape[0])
        ii = rng.integers(0, x.shape[0], size=na)
        jj = rng.integers(0, y.shape[0], size=nb)
        approx = (Lx[ii] @ B @ Ly[jj].T)
        exact = f(x[ii][:, None] + y[jj][None, :])
        scale = max(np.max(np.abs(exact)), 1e-30)
        if np.max(np.abs(approx - exact)) / scale > tol:
            if x.shape[0] >= y.shape[0] and x.shape[0] > 2 * degree:
                mid = (x_lo + x_hi) / 2.0
                sel = x <= mid
                out = np.empty((x.shape[0],) + V.shape[1:], dtype=out.dtype)
                out[sel] = chebyshev_matvec(f, x[sel], y, V, degree, xp, tol, _depth + 1)
                out[~sel] = chebyshev_matvec(f, x[~sel], y, V, degree, xp, tol, _depth + 1)
            elif y.shape[0] > 2 * degree:
                mid = (y_lo + y_hi) / 2.0
                sel = y <= mid
                out = chebyshev_matvec(f, x, y[sel], V[sel], degree, xp, tol, _depth + 1)
                out = out + chebyshev_matvec(f, x, y[~sel], V[~sel], degree, xp, tol, _depth + 1)
            else:  # small block: fall back to dense (exact)
                out = dense_matvec(f, x, y, V, xp)
    return out


def cauchy_matvec(p, q, V, xp=np, degree: int = 24, tol: float = 1e-10):
    """out_i = sum_j V_j / (p_i + q_j); p_i + q_j > 0 required.

    The Cauchy-like LDR workhorse for f(x) = exp(lam x)/(x+c) (Sec 3.2.1):
    adaptive Chebyshev H-multiply, machine-precision configurable.
    """
    return chebyshev_matvec(lambda s: 1.0 / s, p, q, V, degree=degree, xp=xp, tol=tol)


# ----------------------------------------------------------------------------
# CordialFn: f + a multiply strategy (host/numpy API used by the integrator)
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class CordialFn:
    """A scalar function f plus the structured-multiply strategy for
    M = [f(x_i+y_j)]. Base class multiplies densely."""

    def __call__(self, z):
        raise NotImplementedError

    def matvec(self, x, y, V, xp=np):
        return dense_matvec(self, x, y, V, xp=xp)

    @property
    def f0(self):
        """f(0) — used by the integrator's pivot correction."""
        return float(self(np.zeros(1))[0])


@dataclasses.dataclass
class Polynomial(CordialFn):
    coeffs: tuple  # c_0..c_B

    def __call__(self, z):
        out = 0.0
        for c in reversed(self.coeffs):
            out = out * z + c
        return out

    def matvec(self, x, y, V, xp=np):
        return polynomial_matvec(np.asarray(self.coeffs, dtype=np.float64), x, y, V, xp=xp)


@dataclasses.dataclass
class Exponential(CordialFn):
    lam: float
    scale: float = 1.0

    def __call__(self, z):
        return self.scale * np.exp(self.lam * z)

    def matvec(self, x, y, V, xp=np):
        return exponential_matvec(self.lam, x, y, V, xp=xp, scale=self.scale)


@dataclasses.dataclass
class ExpPoly(CordialFn):
    """f(z) = exp(lam z) * poly(z)."""

    lam: float
    coeffs: tuple

    def __call__(self, z):
        p = 0.0
        for c in reversed(self.coeffs):
            p = p * z + c
        return np.exp(self.lam * z) * p

    def matvec(self, x, y, V, xp=np):
        return exp_poly_matvec(self.lam, np.asarray(self.coeffs), x, y, V, xp=xp)


@dataclasses.dataclass
class Trigonometric(CordialFn):
    omega: float
    phi: float = 0.0
    kind: str = "cos"

    def __call__(self, z):
        fn = np.cos if self.kind == "cos" else np.sin
        return fn(self.omega * z + self.phi)

    def matvec(self, x, y, V, xp=np):
        return trig_matvec(self.omega, self.phi, x, y, V, kind=self.kind, xp=xp)


@dataclasses.dataclass
class Rational(CordialFn):
    """f(z) = poly_num(z) / poly_den(z) (Sec 4.3's learnable family).

    Strategy: exact Hankel/FFT when distances are grid-aligned (rational tree
    weights), else adaptive Chebyshev to `tol`.
    """

    num: tuple
    den: tuple
    tol: float = 1e-10
    degree: int = 32

    def __call__(self, z):
        n = 0.0
        for c in reversed(self.num):
            n = n * z + c
        d = 0.0
        for c in reversed(self.den):
            d = d * z + c
        return n / d

    def matvec(self, x, y, V, xp=np):
        h = detect_grid(x, y) if xp is np else None
        if h is not None:
            return hankel_fft_matvec(self, x, y, V, h, xp=xp)
        return chebyshev_matvec(self, x, y, V, degree=self.degree, xp=xp, tol=self.tol)


@dataclasses.dataclass
class ExpQuadratic(CordialFn):
    """f(z) = exp(u z^2 + v z + w) — the paper's best ViT-variant family.

    Exact via the rational-weight Hankel embedding (== the paper's
    D1·Vandermonde·D2 route); Chebyshev fallback for irrational weights.
    """

    u: float
    v: float
    w: float = 0.0
    tol: float = 1e-10
    degree: int = 48

    def __call__(self, z):
        return np.exp(self.u * z * z + self.v * z + self.w)

    def matvec(self, x, y, V, xp=np):
        h = detect_grid(x, y) if xp is np else None
        if h is not None:
            return hankel_fft_matvec(self, x, y, V, h, xp=xp)
        return chebyshev_matvec(self, x, y, V, degree=self.degree, xp=xp, tol=self.tol)


@dataclasses.dataclass
class ExpRational(CordialFn):
    """f(z) = exp(lam z) / (z + c), c > 0 — the paper's Cauchy-LDR example."""

    lam: float
    c: float
    tol: float = 1e-11
    degree: int = 32

    def __call__(self, z):
        return np.exp(self.lam * z) / (z + self.c)

    def matvec(self, x, y, V, xp=np):
        # M(i,j) = exp(lam x_i) exp(lam y_j) / ((x_i + c/2) + (y_j + c/2)):
        # diagonal-scaled Cauchy (low displacement rank).
        dx = np.exp(self.lam * np.asarray(x))
        dy = np.exp(self.lam * np.asarray(y))
        out = cauchy_matvec(np.asarray(x) + self.c / 2.0, np.asarray(y) + self.c / 2.0,
                            dy[:, None] * V, xp=xp, degree=self.degree, tol=self.tol)
        return dx[:, None] * out


@dataclasses.dataclass
class AnyFn(CordialFn):
    """Arbitrary callable f; Hankel-exact on grids, else Chebyshev(tol)."""

    fn: Callable
    tol: float = 1e-9
    degree: int = 48

    def __call__(self, z):
        return self.fn(z)

    def matvec(self, x, y, V, xp=np):
        h = detect_grid(x, y) if xp is np else None
        if h is not None:
            return hankel_fft_matvec(self.fn, x, y, V, h, xp=xp)
        return chebyshev_matvec(self.fn, x, y, V, degree=self.degree, xp=xp, tol=self.tol)


# ----------------------------------------------------------------------------
# FamilySpec: one normalized description of f for the plan executor
# ----------------------------------------------------------------------------
#
# The executor dispatches on `mode`, the structured-multiply family of f:
#
#   mode        f(s)                          exact engines available
#   ----------  ----------------------------  --------------------------------
#   "poly"      sum_t coeffs[t] s^t           polynomial LDR, Pallas in-kernel
#   "exp"       coeffs[1] * exp(coeffs[0] s)  rank-1, Pallas in-kernel, ExpMP
#   "expq"      exp(c0 s^2 + c1 s + c2)       Pallas in-kernel, Hankel on grids
#   "rational"  scale / (1 + c0 s^2)          Pallas in-kernel, Hankel on grids
#   None        anything                      Hankel on grids, else Chebyshev
#
# `coeffs` follows the layout of kernels/fdist_matvec (`_f_tile`); `scale` is
# a scalar multiplier applied OUTSIDE the kernel families that don't carry
# one. `fn_eval` is an xp-traceable evaluation of the full f (scale included)
# used for leaf blocks, pivot corrections and the Chebyshev/Hankel fallbacks.


@dataclasses.dataclass(frozen=True)
class FamilySpec:
    mode: str | None
    coeffs: tuple
    fn_eval: Callable  # traceable full f (jnp in, jnp out)
    cordial: CordialFn  # host-side strategy object (FTFI path)
    scale: float = 1.0


def _horner(coeffs):
    def f(z):
        acc = 0.0
        for c in reversed(coeffs):
            acc = acc * z + c
        return acc

    return f


def spec_of(fn) -> FamilySpec:
    """Classify `fn` (a CordialFn or a plain traceable callable)."""
    import jax.numpy as jnp

    if isinstance(fn, Polynomial):
        cs = tuple(float(c) for c in fn.coeffs)
        return FamilySpec("poly", cs, _horner(cs), fn)
    if isinstance(fn, Exponential):
        lam, s = float(fn.lam), float(fn.scale)
        return FamilySpec("exp", (lam, s), lambda z: s * jnp.exp(lam * z), fn)
    if isinstance(fn, ExpQuadratic):
        u, v, w = float(fn.u), float(fn.v), float(fn.w)
        return FamilySpec(
            "expq", (u, v, w), lambda z: jnp.exp(u * z * z + v * z + w), fn)
    if isinstance(fn, Rational):
        num, den = tuple(map(float, fn.num)), tuple(map(float, fn.den))
        if (len(num) == 1 and len(den) == 3 and den[0] > 0.0 and den[1] == 0.0
                and den[2] >= 0.0):
            # a / (d0 + d2 s^2) = (a/d0) * 1/(1 + (d2/d0) s^2)
            c0 = den[2] / den[0]
            scale = num[0] / den[0]
            return FamilySpec(
                "rational", (c0,),
                lambda z: scale / (1.0 + c0 * z * z), fn, scale=scale)
        pn, pd = _horner(num), _horner(den)
        return FamilySpec(None, (), lambda z: pn(z) / pd(z), fn)
    if isinstance(fn, ExpPoly):
        lam, cs = float(fn.lam), tuple(map(float, fn.coeffs))
        p = _horner(cs)
        return FamilySpec(None, (), lambda z: jnp.exp(lam * z) * p(z), fn)
    if isinstance(fn, Trigonometric):
        om, ph = float(fn.omega), float(fn.phi)
        trig = jnp.cos if fn.kind == "cos" else jnp.sin
        return FamilySpec(None, (), lambda z: trig(om * z + ph), fn)
    if isinstance(fn, ExpRational):
        lam, c = float(fn.lam), float(fn.c)
        return FamilySpec(None, (), lambda z: jnp.exp(lam * z) / (z + c), fn)
    if isinstance(fn, AnyFn):
        return FamilySpec(None, (), fn.fn, fn)
    if isinstance(fn, CordialFn):
        return FamilySpec(None, (), fn, fn)
    if callable(fn):  # plain traceable callable: wrap for the host path
        return FamilySpec(None, (), fn, AnyFn(fn))
    raise TypeError(f"cannot build a FamilySpec from {type(fn).__name__}")
