"""Fused f-distance matvec Pallas kernel — the paper's core operation.

Computes out[i, :] = sum_j f(x_i + y_j) * V[j, :] WITHOUT materializing the
(a, b) matrix M = [f(x_i + y_j)] in HBM: tiles of M are built on the fly in
VMEM from the 1-D distance vectors and contracted with V at once. This is
the TPU-native reading of the paper's LDR insight — structure means
"recompute cheaply instead of storing" (DESIGN §3): HBM traffic drops from
O(a*b) to O(a + b + b*d).

Two contractions, chosen by the field's width d (a static shape):
  d <= D_VPU — on the VPU in f32: each f-tile is multiplied by the field's
               rows and summed in (rows, 128) partial sums, many 128 x 128
               tiles a grid step (`_narrow`);
  d >  D_VPU — on the MXU: each grid step's (blk_a, blk_b) tile is fed to
               a `HIGHEST`-precision dot (`_fdist_kernel`).

f families supported in-kernel (static `mode`):
  poly     — f(s) = sum_t coeffs[t] s^t            (Sec 3.2.1, 0-cordial)
  exp      — f(s) = coeffs[1] * exp(coeffs[0]*s)   (rank-1 family)
  expq     — f(s) = exp(u s^2 + v s + w)           (best ViT variant)
  rational — f(s) = 1 / (1 + coeffs[0] * s^2)      (mesh interpolation)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import trace_guard


def _f_tile(s, coeffs, mode: str):
    if mode == "poly":
        acc = jnp.zeros_like(s)
        for t in range(len(coeffs) - 1, -1, -1):
            acc = acc * s + coeffs[t]
        return acc
    if mode == "exp":
        return coeffs[1] * jnp.exp(coeffs[0] * s)
    if mode == "expq":
        return jnp.exp(coeffs[0] * s * s + coeffs[1] * s + coeffs[2])
    if mode == "rational":
        return 1.0 / (1.0 + coeffs[0] * s * s)
    raise ValueError(mode)


def _fdist_kernel(x_ref, y_ref, v_ref, c_ref, o_ref, acc_ref, *,
                  mode: str, nb: int, j_axis: int = 1):
    """Shared body: `j_axis` is the grid axis that sweeps source blocks
    (1 for the single-job kernel, 2 when a leading batch axis is present)."""
    j = pl.program_id(j_axis)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = x_ref[...] + y_ref[...]  # (blk_a, 1) + (1, blk_b) -> (blk_a, blk_b)
    m = _f_tile(s, c_ref[...], mode)  # tile of M — exists only in VMEM
    # full f32 MXU passes: the default single bf16 pass is ~1e-3 relative
    acc_ref[...] += jnp.dot(m, v_ref[...], preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(j == nb - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# Fields at most this wide are contracted on the VPU (`_narrow`), wider ones
# on the MXU. One call at the mesh cell's largest cross bucket (B, a, b) =
# (2, 43316, 43316), rational f, on a TPU v5e, in ms VPU / MXU
# (benchmarks/sweep_fdist_matvec.py): d = 1 14.1 / 93.2, 3 16.8 / 93.1,
# 8 31.5 / 90.8, 16 58.1 / 90.7, 32 148.4 / 90.9, 64 351.4 / 90.9.
D_VPU = 16

LANES = 128
# the narrow path's blocks: at most `_VMEM_CHUNKS // (d + 1)` 128-wide chunks
# of targets and of sources a grid step (~4 MiB of VMEM with the double
# buffers), and jobs stacked along the batch axis until a grid step holds
# ~`_MIN_STEP_TILES` 128 x 128 tiles; the chunk loop is unrolled `_UNROLL`
_VMEM_CHUNKS = 2048
_MIN_STEP_TILES = 32
_UNROLL = 8


def _split(n: int, most: int, align: int = 8) -> tuple[int, int]:
    """(size, count) of the blocks that cover `n` units, `most` at most a
    block: one block when it fits, else `align`-multiples with the least
    padding (a block's fixed cost counted as one unit)."""
    if n <= most:
        return n, 1
    best = None
    for size in range(align, most + 1, align):
        count = -(-n // size)
        cost = count * (size + 1)
        if best is None or cost <= best[0]:
            best = (cost, size, count)
    return best[1], best[2]


def _narrow_kernel(c_ref, x_ref, y_ref, v_ref, o_ref, xb_ref, acc_ref, *,
                   mode: str, rows: int):
    """One grid step of the narrow path: `nj` jobs x (ta x 128) targets x
    (tb x 128) sources. Targets lie on sublanes and sources on lanes; each
    (rows, 128) f-chunk is multiplied by the field's rows and summed into
    (rows, 128) partial sums held in registers over the step's sources,
    then reduced across lanes once per target group and step."""
    j = pl.program_id(2)
    nj, ta, _ = x_ref.shape
    tb = y_ref.shape[1]
    d = v_ref.shape[0]
    coeffs = [c_ref[t] for t in range(c_ref.shape[0])]

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def job(n, carry):
        def group(q, carry):
            # the group's 128 targets down the sublanes, each across lanes
            xb_ref[...] = jnp.broadcast_to(x_ref[n, pl.ds(q, 1), :],
                                           (LANES, LANES)).T

            def row_chunk(r, carry):
                r = pl.multiple_of(r * rows, rows)
                xb = xb_ref[pl.ds(r, rows), :]

                def chunk(k, accs):
                    m = _f_tile(xb + y_ref[n, pl.ds(k, 1), :], coeffs, mode)
                    return tuple(acc + m * v_ref[c, n, pl.ds(k, 1), :]
                                 for c, acc in enumerate(accs))

                def chunks(g, accs):
                    for u in range(_UNROLL):
                        accs = chunk(g * _UNROLL + u, accs)
                    return accs

                accs = (jnp.zeros((rows, LANES), jnp.float32),) * d
                accs = jax.lax.fori_loop(0, tb // _UNROLL, chunks, accs)
                accs = jax.lax.fori_loop(tb - tb % _UNROLL, tb, chunk, accs)
                for c in range(d):
                    acc_ref[c, pl.ds(r, rows), :] = accs[c]
                return carry

            jax.lax.fori_loop(0, LANES // rows, row_chunk, 0)
            for c in range(d):
                o_ref[c, n, pl.ds(q, 1), :] += jnp.sum(acc_ref[c].T, axis=0,
                                                       keepdims=True)
            return carry

        return jax.lax.fori_loop(0, ta, group, carry)

    jax.lax.fori_loop(0, nj, job, 0)


def _narrow(x, y, v, coeffs, mode: str, interpret: bool):
    """The narrow-field path of `fdist_matvec_batched_pallas`, on the VPU in
    f32. Every operand is laid out lane-dense in 128-wide chunks, the field's
    columns leading (as XLA lays out a narrow (B, n, d) array): x (B, ca,
    128), y (B, cb, 128), V (d, B, cb, 128), out (d, B, ca, 128)."""
    B, a = x.shape
    b = y.shape[1]
    d = v.shape[2]
    most = _VMEM_CHUNKS // (d + 1)
    ta, na = _split(-(-a // LANES), most)
    tb, nb = _split(-(-b // LANES), most)
    nj, nbatch = _split(B, max(1, _MIN_STEP_TILES // (ta * tb)), align=1)
    ap, bp, Bp = ta * na * LANES, tb * nb * LANES, nj * nbatch
    xp = jnp.pad(x.astype(jnp.float32), ((0, Bp - B), (0, ap - a)))
    yp = jnp.pad(y.astype(jnp.float32), ((0, Bp - B), (0, bp - b)))
    vp = jnp.pad(v.astype(jnp.float32), ((0, Bp - B), (0, bp - b), (0, 0)))
    rows = 32 if d <= 4 else 16 if d <= 8 else 8  # partial sums in registers
    out = pl.pallas_call(
        functools.partial(_narrow_kernel, mode=mode, rows=rows),
        grid=(nbatch, na, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((nj, ta, LANES), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((nj, tb, LANES), lambda n, i, j: (n, j, 0)),
            pl.BlockSpec((d, nj, tb, LANES), lambda n, i, j: (0, n, j, 0)),
        ],
        out_specs=pl.BlockSpec((d, nj, ta, LANES),
                               lambda n, i, j: (0, n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((d, Bp, ap // LANES, LANES),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((LANES, LANES), jnp.float32),
                        pltpu.VMEM((d, LANES, LANES), jnp.float32)],
        interpret=interpret,
    )(coeffs.astype(jnp.float32), xp.reshape(Bp, -1, LANES),
      yp.reshape(Bp, -1, LANES),
      vp.transpose(2, 0, 1).reshape(d, Bp, -1, LANES))
    out = out.reshape(d, Bp, ap)[:, :B, :a].transpose(1, 2, 0)
    return out.astype(v.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "blk_a", "blk_b",
                                             "interpret"))
def fdist_matvec_batched_pallas(x, y, v, coeffs, *, mode: str = "poly",
                                blk_a: int = 128, blk_b: int = 128,
                                interpret: bool = False):
    """Batched fused f-distance matvec: one pallas_call over a whole bucket
    of IT cross jobs. x: (B, a), y: (B, b), v: (B, b, d) -> out (B, a, d).

    This is the kernel the plan executor's `pallas` backend feeds: it builds
    tiles of M_n = [f(x_n,i + y_n,j)] in VMEM and accumulates M_n V_n
    without ever materializing M_n in HBM. Padded tail entries (x=y=0, v=0)
    contribute exactly zero. A field of at most D_VPU columns takes the
    narrow path (`_narrow`), whose blocks follow from the shapes alone and
    which ignores `blk_a` / `blk_b`; a wider one takes the MXU path, one
    (blk_a, blk_b) tile a grid step (n, i, j). Each trace records its path
    as `fdist_matvec:vpu` or `fdist_matvec:mxu` in `trace_guard`.
    """
    B, a = x.shape
    b = y.shape[1]
    d = v.shape[2]
    if d <= D_VPU:
        trace_guard.record("fdist_matvec", event="vpu")
        return _narrow(x, y, v, coeffs, mode, interpret)
    trace_guard.record("fdist_matvec", event="mxu")
    blk_a = min(blk_a, a)
    blk_b = min(blk_b, b)
    pad_a = (-a) % blk_a
    pad_b = (-b) % blk_b
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad_a)))[:, :, None]
    yp = jnp.pad(y.astype(jnp.float32), ((0, 0), (0, pad_b)))[:, None, :]
    vp = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pad_b), (0, 0)))
    na = (a + pad_a) // blk_a
    nb = (b + pad_b) // blk_b
    out = pl.pallas_call(
        functools.partial(_fdist_kernel, mode=mode, nb=nb, j_axis=2),
        grid=(B, na, nb),
        in_specs=[
            pl.BlockSpec((None, blk_a, 1), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((None, 1, blk_b), lambda n, i, j: (n, 0, j)),
            pl.BlockSpec((None, blk_b, d), lambda n, i, j: (n, j, 0)),
            pl.BlockSpec((coeffs.shape[0],), lambda n, i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((None, blk_a, d), lambda n, i, j: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, a + pad_a, d), v.dtype),
        scratch_shapes=[pltpu.VMEM((blk_a, d), jnp.float32)],
        interpret=interpret,
    )(xp, yp, vp, coeffs.astype(jnp.float32))
    return out[:, :a]


@functools.partial(jax.jit, static_argnames=("mode", "blk_a", "blk_b",
                                             "interpret"))
def fdist_matvec_pallas(x, y, v, coeffs, *, mode: str = "poly",
                        blk_a: int = 256, blk_b: int = 256,
                        interpret: bool = False):
    """x: (a,), y: (b,), v: (b, d), coeffs: (k,) -> out (a, d)."""
    a, b = x.shape[0], y.shape[0]
    d = v.shape[1]
    blk_a = min(blk_a, a)
    blk_b = min(blk_b, b)
    pad_a = (-a) % blk_a
    pad_b = (-b) % blk_b
    xp = jnp.pad(x.astype(jnp.float32), (0, pad_a)).reshape(-1, 1)
    yp = jnp.pad(y.astype(jnp.float32), (0, pad_b)).reshape(1, -1)
    vp = jnp.pad(v.astype(jnp.float32), ((0, pad_b), (0, 0)))
    na = (a + pad_a) // blk_a
    nb = (b + pad_b) // blk_b
    out = pl.pallas_call(
        functools.partial(_fdist_kernel, mode=mode, nb=nb),
        grid=(na, nb),
        in_specs=[
            pl.BlockSpec((blk_a, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, blk_b), lambda i, j: (0, j)),
            pl.BlockSpec((blk_b, d), lambda i, j: (j, 0)),
            pl.BlockSpec((coeffs.shape[0],), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((blk_a, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((a + pad_a, d), v.dtype),
        scratch_shapes=[pltpu.VMEM((blk_a, d), jnp.float32)],
        interpret=interpret,
    )(xp, yp, vp, coeffs.astype(jnp.float32))
    return out[:a]
