"""Ahead-of-time compiles of both FTFI kernels for a TPU v5e, at real widths.

The TPU compiler is installed even where no chip is attached; compiling for
a described v5e topology refuses what interpret mode accepts (block shapes
off the (8, 128) tiling, unsupported ops such as float iota). Nothing runs.

The topology is described inside a module fixture, never at import: only one
process may load libtpu, and every test worker imports this file.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.analysis import trace_guard
from repro.kernels.fdist_matvec.kernel import fdist_matvec_batched_pallas
from repro.kernels.topo_linear_attention.ops import topo_linear_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable written to the persistent cache here cannot be read
    # back without a chip: keep the cache off for these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no v5e target in this install
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("mode,ncoef", [("poly", 3), ("exp", 2),
                                        ("expq", 3), ("rational", 1)])
def test_fdist_matvec_compiles_for_v5e(one_chip, mode, ncoef):
    B, a, b, d = 64, 128, 128, 64
    fn = jax.jit(lambda x, y, v, c: fdist_matvec_batched_pallas(
        x, y, v, c, mode=mode, interpret=False))
    compiled = fn.lower(_f32((B, a), one_chip), _f32((B, b), one_chip),
                        _f32((B, b, d), one_chip),
                        _f32((ncoef,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,a,b", [(2, 43316, 43316), (66, 33, 33)],
                         ids=["largest", "small"])
def test_fdist_matvec_narrow_compiles_for_v5e(one_chip, B, a, b):
    """The narrow-field path at the mesh cell's largest cross bucket
    (icosphere(7), leaf size 64) and at its smallest, d = 3, rational f."""
    before = trace_guard.compiles("fdist_matvec:vpu")
    fn = jax.jit(lambda x, y, v, c: fdist_matvec_batched_pallas(
        x, y, v, c, mode="rational", interpret=False))
    compiled = fn.lower(_f32((B, a), one_chip), _f32((B, b), one_chip),
                        _f32((B, b, 3), one_chip),
                        _f32((1,), one_chip)).compile()
    assert trace_guard.compiles("fdist_matvec:vpu") == before + 1
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
@pytest.mark.parametrize("degree", [1, 2], ids=["decay", "rank"])
def test_topo_linear_attention_compiles_for_v5e(one_chip, degree, causal):
    B, H, L, m = 2, 12, 4096, 64
    fn = jax.jit(lambda q, k, v, c: topo_linear_attention(
        q, k, v, c, g="exp", dist_scale=1.0 / L, causal=causal,
        use_kernel=True, interpret=False))
    qkv = _f32((B, H, L, m), one_chip)
    compiled = fn.lower(qkv, qkv, qkv,
                        _f32((H, degree + 1), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
