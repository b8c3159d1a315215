"""Per-kernel Pallas validation (interpret mode) vs pure-jnp oracles,
sweeping shapes and dtypes."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.analysis import trace_guard
from repro.kernels.fdist_matvec.kernel import (D_VPU,
                                               fdist_matvec_batched_pallas,
                                               fdist_matvec_pallas)
from repro.kernels.fdist_matvec.ref import fdist_matvec_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.linear_attention.kernel import linear_attention_pallas
from repro.kernels.linear_attention.ref import linear_attention_ref
from repro.kernels.selective_scan.kernel import selective_scan_pallas
from repro.kernels.selective_scan.ref import selective_scan_ref


@pytest.mark.parametrize("a,b,d", [(300, 200, 8), (128, 128, 4), (97, 33, 3),
                                   (64, 257, 16)])
@pytest.mark.parametrize("mode,coeffs", [
    ("poly", (0.5, -0.2, 0.1)),
    ("exp", (-0.7, 1.3)),
    ("expq", (-0.05, -0.2, 0.1)),
    ("rational", (0.8,)),
])
def test_fdist_matvec(a, b, d, mode, coeffs, rng):
    x = jnp.asarray(rng.uniform(0, 3, a), jnp.float32)
    y = jnp.asarray(rng.uniform(0, 3, b), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    cs = jnp.asarray(coeffs, jnp.float32)
    got = fdist_matvec_pallas(x, y, v, cs, mode=mode, blk_a=64, blk_b=64,
                              interpret=True)
    ref = fdist_matvec_ref(x, y, v, cs, mode)
    err = float(jnp.max(jnp.abs(got - ref))) / max(
        float(jnp.max(jnp.abs(ref))), 1e-9)
    assert err < 3e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fdist_matvec_dtypes(dtype, rng):
    x = jnp.asarray(rng.uniform(0, 2, 128), jnp.float32)
    y = jnp.asarray(rng.uniform(0, 2, 96), jnp.float32)
    v = jnp.asarray(rng.normal(size=(96, 8)), dtype)
    cs = jnp.asarray([-0.5, 1.0], jnp.float32)
    got = fdist_matvec_pallas(x, y, v, cs, mode="exp", blk_a=32, blk_b=32,
                              interpret=True)
    ref = fdist_matvec_ref(x, y, v, cs, "exp")
    tol = 3e-6 if dtype == jnp.float32 else 3e-2
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < tol * max(float(jnp.max(jnp.abs(ref.astype(jnp.float32)))), 1)


FDIST_MODES = [("poly", (0.5, -0.2, 0.1)), ("exp", (-0.7, 1.3)),
               ("expq", (-0.05, -0.2, 0.1)), ("rational", (0.8,))]


def _fdist_batched_f64(x, y, v, cs, mode):
    """The batched product in float64 from the f32 inputs."""
    s = np.asarray(x, np.float64)[:, :, None] + np.asarray(
        y, np.float64)[:, None, :]
    c = np.asarray(cs, np.float64)
    if mode == "poly":
        m = np.polynomial.polynomial.polyval(s, c)
    elif mode == "exp":
        m = c[1] * np.exp(c[0] * s)
    elif mode == "expq":
        m = np.exp(c[0] * s * s + c[1] * s + c[2])
    else:
        m = 1.0 / (1.0 + c[0] * s * s)
    return np.einsum("nab,nbd->nad", m, np.asarray(v, np.float64))


def _path_count():
    return (trace_guard.compiles("fdist_matvec:vpu"),
            trace_guard.compiles("fdist_matvec:mxu"))


# ragged shapes that fill no slab, lane or sublane multiple, at widths up to
# D_VPU (5: 16-row partial sums), and one past it (the MXU path)
BATCHED_CASES = [(B, a, b, d) for B, a, b in ((3, 33, 65), (1, 129, 257),
                                             (1, 300, 1500))
                 for d in (1, 3, D_VPU)] + [(1, 129, 257, 5),
                                            (3, 33, 65, D_VPU + 1)]


@pytest.mark.parametrize("B,a,b,d", BATCHED_CASES)
@pytest.mark.parametrize("mode,coeffs", FDIST_MODES)
def test_fdist_matvec_batched_paths(B, a, b, d, mode, coeffs, rng):
    """Each path of the batched kernel (the VPU one up to D_VPU columns, the
    MXU one past it) against the float64 product; each trace records the
    path it took."""
    x = jnp.asarray(rng.uniform(0, 3, (B, a)), jnp.float32)
    y = jnp.asarray(rng.uniform(0, 3, (B, b)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, b, d)), jnp.float32)
    cs = jnp.asarray(coeffs, jnp.float32)
    before = _path_count()
    got = fdist_matvec_batched_pallas(x, y, v, cs, mode=mode, interpret=True)
    vpu, mxu = (n - m for n, m in zip(_path_count(), before))
    assert (vpu, mxu) == ((1, 0) if d <= D_VPU else (0, 1))
    ref = _fdist_batched_f64(x, y, v, cs, mode)
    assert got.shape == ref.shape and got.dtype == jnp.float32
    err = np.max(np.abs(np.asarray(got) - ref)) / np.max(np.abs(ref))
    assert err < 3e-6


def test_fdist_matvec_narrow_sum_keeps_f32_accuracy(rng):
    """4,096 sources of mixed sign: the narrow path's sum stays within
    1e-6 of the float64 product, relative to its largest entry (a plain
    running f32 sum over the sources reads 2.4e-6 here)."""
    B, a, b, d = 1, 64, 4096, 3
    x = jnp.asarray(rng.uniform(0, 0.2, (B, a)), jnp.float32)
    y = jnp.asarray(rng.uniform(0, 0.2, (B, b)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, b, d)), jnp.float32)
    cs = jnp.asarray((0.8,), jnp.float32)
    got = fdist_matvec_batched_pallas(x, y, v, cs, mode="rational",
                                      interpret=True)
    ref = _fdist_batched_f64(x, y, v, cs, "rational")
    err = np.max(np.abs(np.asarray(got) - ref)) / np.max(np.abs(ref))
    assert err <= 1e-6


@pytest.mark.parametrize("L,hd,blk", [(128, 32, 32), (256, 64, 64), (64, 16, 16)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(L, hd, blk, causal, rng):
    B, H = 2, 2
    q = jnp.asarray(rng.normal(size=(B, H, L, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, L, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, L, hd)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, blk_q=blk, blk_k=blk,
                                 interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(got - ref))) < 2e-5


@pytest.mark.parametrize("L,din,N,chunk,blkd", [(64, 32, 8, 16, 16),
                                                (128, 64, 16, 32, 32)])
def test_selective_scan(L, din, N, chunk, blkd, rng):
    Bt = 2
    u = jnp.asarray(rng.normal(size=(Bt, L, din)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(Bt, L, din))) * 0.1, jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(din, N))) - 0.1, jnp.float32)
    B = jnp.asarray(rng.normal(size=(Bt, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(Bt, L, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(din,)), jnp.float32)
    got = selective_scan_pallas(u, dt, A, B, Cm, D, chunk=chunk, blk_d=blkd,
                                interpret=True)
    ref = selective_scan_ref(u, dt, A, B, Cm, D)
    assert float(jnp.max(jnp.abs(got - ref))) < 2e-5


@pytest.mark.parametrize("L,m,hd,chunk", [(128, 16, 32, 32), (64, 8, 8, 16)])
@pytest.mark.parametrize("lg", [0.0, -0.05])
def test_linear_attention(L, m, hd, chunk, lg, rng):
    B, H = 2, 3
    qf = jnp.asarray(np.abs(rng.normal(size=(B, H, L, m))), jnp.float32)
    kf = jnp.asarray(np.abs(rng.normal(size=(B, H, L, m))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, L, hd)), jnp.float32)
    lgv = jnp.full((H,), lg, jnp.float32)
    num, den = linear_attention_pallas(qf, kf, v, lgv, chunk=chunk,
                                       interpret=True)
    rnum, rden = linear_attention_ref(qf, kf, v, lgv)
    assert float(jnp.max(jnp.abs(num - rnum))) / float(jnp.max(jnp.abs(rnum))) < 1e-5
    assert float(jnp.max(jnp.abs(den - rden))) / float(jnp.max(jnp.abs(rden))) < 1e-5


# ----------------------------------------------------------------------------
# shared ref-vs-ops parity fixture: every kernel family (including future
# ones added to _KERNEL_FAMILY_CASES) gets ops-layer parity coverage for free
# ----------------------------------------------------------------------------


def _case_fdist_matvec(rng):
    from repro.kernels.fdist_matvec.ops import fdist_matvec
    from repro.kernels.fdist_matvec.ref import fdist_matvec_ref

    x = jnp.asarray(rng.uniform(0, 3, 120), jnp.float32)
    y = jnp.asarray(rng.uniform(0, 3, 75), jnp.float32)
    v = jnp.asarray(rng.normal(size=(75, 6)), jnp.float32)
    cs = jnp.asarray([0.4, -0.3, 0.1], jnp.float32)
    return {"out": (fdist_matvec(x, y, v, cs, mode="poly", blk_a=32, blk_b=32),
                    fdist_matvec_ref(x, y, v, cs, "poly"))}


def _case_flash_attention(rng):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.float32)
               for _ in range(3))
    return {"out": (flash_attention(q, k, v, causal=True),
                    attention_ref(q, k, v, causal=True))}


def _case_linear_attention(rng):
    from repro.kernels.linear_attention.ops import linear_attention

    qf = jnp.asarray(np.abs(rng.normal(size=(1, 2, 64, 8))), jnp.float32)
    kf = jnp.asarray(np.abs(rng.normal(size=(1, 2, 64, 8))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 8)), jnp.float32)
    lg = jnp.asarray([-0.04, 0.0], jnp.float32)
    num, den = linear_attention(qf, kf, v, lg, chunk=16)
    rnum, rden = linear_attention_ref(qf, kf, v, lg)
    return {"num": (num, rnum), "den": (den, rden)}


def _case_selective_scan(rng):
    from repro.kernels.selective_scan.ops import selective_scan
    from repro.kernels.selective_scan.ref import selective_scan_ref

    u = jnp.asarray(rng.normal(size=(1, 64, 16)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(1, 64, 16))) * 0.1, jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(16, 8))) - 0.1, jnp.float32)
    B = jnp.asarray(rng.normal(size=(1, 64, 8)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(1, 64, 8)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    return {"out": (selective_scan(u, dt, A, B, Cm, D, chunk=16, blk_d=16),
                    selective_scan_ref(u, dt, A, B, Cm, D))}


def _case_topo_linear_attention(rng):
    from repro.kernels.topo_linear_attention.ops import topo_linear_attention
    from repro.kernels.topo_linear_attention.ref import (
        topo_linear_attention_ref)

    qf = jnp.asarray(np.abs(rng.normal(size=(1, 2, 60, 6))), jnp.float32)
    kf = jnp.asarray(np.abs(rng.normal(size=(1, 2, 60, 6))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 60, 8)), jnp.float32)
    cs = jnp.asarray([[0.1, -0.5, -0.2], [0.0, -0.3, -0.4]], jnp.float32)
    kw = dict(g="exp", dist_scale=1.0 / 60, causal=False)
    return {"out": (topo_linear_attention(qf, kf, v, cs, chunk=16,
                                          use_kernel=True, interpret=True,
                                          **kw),
                    topo_linear_attention_ref(qf, kf, v, cs, **kw))}


_KERNEL_FAMILY_CASES = {
    "fdist_matvec": _case_fdist_matvec,
    "flash_attention": _case_flash_attention,
    "linear_attention": _case_linear_attention,
    "selective_scan": _case_selective_scan,
    "topo_linear_attention": _case_topo_linear_attention,
}


@pytest.mark.parametrize("family", sorted(_KERNEL_FAMILY_CASES))
def test_kernel_family_ops_vs_ref(family, rng):
    """ops-layer entry point (interpret mode off-TPU) == pure-jnp oracle,
    one uniform check per kernel family."""
    for name, (got, ref) in _KERNEL_FAMILY_CASES[family](rng).items():
        got = jnp.asarray(got, jnp.float32)
        ref = jnp.asarray(ref, jnp.float32)
        scale = max(float(jnp.max(jnp.abs(ref))), 1e-6)
        err = float(jnp.max(jnp.abs(got - ref))) / scale
        assert err < 2e-5, (family, name, err)


def test_kernel_xla_equivalence(rng):
    """Pallas linear-attention kernel == the model's XLA chunked path."""
    from repro.models.attention import causal_linear_attention

    B, H, L, m, hd = 1, 2, 128, 16, 16
    qf = jnp.asarray(np.abs(rng.normal(size=(B, L, H, m))), jnp.float32)
    kf = jnp.asarray(np.abs(rng.normal(size=(B, L, H, m))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, L, H, hd)), jnp.float32)
    lg = jnp.asarray([-0.03, 0.0], jnp.float32)
    num_x, den_x = causal_linear_attention(qf, kf, v, lg, chunk=32)
    num_p, den_p = linear_attention_pallas(
        qf.transpose(0, 2, 1, 3), kf.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), lg, chunk=32, interpret=True)
    assert float(jnp.max(jnp.abs(num_x.transpose(0, 2, 1, 3) - num_p))) < 1e-3
    assert float(jnp.max(jnp.abs(den_x.transpose(0, 2, 1) - den_p))) < 1e-3
