"""Jit'd public wrappers: pick the Pallas kernel on TPU, interpret-mode
(= Python execution of the same kernel body) elsewhere for validation."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.fdist_matvec.kernel import (fdist_matvec_batched_pallas,
                                               fdist_matvec_pallas)


def fdist_matvec(x, y, v, coeffs, mode: str = "poly", blk_a: int = 256,
                 blk_b: int = 256):
    on_tpu = jax.default_backend() == "tpu"
    return fdist_matvec_pallas(x, y, v, coeffs, mode=mode, blk_a=blk_a,
                               blk_b=blk_b, interpret=not on_tpu)


def fdist_matvec_batched(x, y, v, coeffs, mode: str = "poly",
                         blk_a: int = 128, blk_b: int = 128,
                         interpret: bool | None = None):
    """Bucketed form used by the plan executor: (B, a) x (B, b) x (B, b, d)
    -> (B, a, d). `interpret=None` auto-selects: compiled on TPU, interpreted
    elsewhere (bit-exact kernel semantics on CPU for tests/CI)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return fdist_matvec_batched_pallas(x, y, v, coeffs, mode=mode,
                                       blk_a=blk_a, blk_b=blk_b,
                                       interpret=interpret)


def fdist_matvec_batched_sharded(x, y, v, coeffs, *, mesh, axis=None,
                                 mode: str = "poly", blk_a: int = 128,
                                 blk_b: int = 128,
                                 interpret: bool | None = None):
    """`fdist_matvec_batched` under shard_map: the bucket (leaf-block) dim
    is split over the mesh's plan axis (`data` by default), each device
    running the same kernel on its B/D slab with no collectives — buckets
    are independent by construction. Ragged bucket counts are zero-padded
    to a multiple of the axis size (pad slabs produce rows that are sliced
    off). Exact: per-slab outputs are the single-device outputs."""
    from repro.launch import sharding

    axis = axis or sharding.plan_axis(mesh)
    D = mesh.shape[axis]
    B = x.shape[0]
    if D == 1:
        return fdist_matvec_batched(x, y, v, coeffs, mode=mode, blk_a=blk_a,
                                    blk_b=blk_b, interpret=interpret)
    pad = (-B) % D
    if pad:
        x, y, v = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                   for a in (jnp.asarray(x), jnp.asarray(y), jnp.asarray(v)))

    def local(xl, yl, vl, cl):
        return fdist_matvec_batched(xl, yl, vl, cl, mode=mode, blk_a=blk_a,
                                    blk_b=blk_b, interpret=interpret)

    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(P(axis), P(axis), P(axis), P()),
                        out_specs=P(axis), check_vma=False)(x, y, v, coeffs)
    return out[:B]
