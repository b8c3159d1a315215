"""`pallas` backend: plan executor with cross multiplies on the fused
fdist_matvec TPU kernel.

Per-bucket cross jobs (B, U_t) x (B, U_s) are batched straight into
`fdist_matvec_batched` for the in-kernel f families (poly / exp / expq /
rational) — each tile of M is built in VMEM, never materialized in HBM,
and contracted there: on the VPU in f32 for fields of at most
`kernels.fdist_matvec.kernel.D_VPU` columns, on the MXU for wider ones (the
`blk_a` / `blk_b` options size the MXU path's tiles only). Engine
selection and the executor live in the functional core
(`plan_api.select_cross` routes these families to the kernel whenever
backend == "pallas"); this subclass only carries the kernel options and
keys the shared fastmult memo with them. The kernel consumes
the *params* distance arrays, so it is traceable — and differentiates —
through `ftfi.reweight`ed distances. General f falls back to the exact
Hankel/FFT engine on grid-aligned trees, else batched Chebyshev. Off-TPU
the kernel runs in interpret mode, so results (and tests) are
platform-independent.
"""
from __future__ import annotations

from repro.core.engines.base import register_backend
from repro.core.engines.plan import PlanBackend
from repro.core.plan_api import KERNEL_MODES  # noqa: F401  (legacy location)


@register_backend("pallas")
class PallasBackend(PlanBackend):
    name = "pallas"

    def __init__(self, tree, leaf_size: int = 64, seed: int = 0,
                 degree: int = 32, detect_grid_spacing: bool = True,
                 reweightable: bool = False, use_cache: bool = True,
                 plan=None, blk_a: int = 128, blk_b: int = 128,
                 interpret: bool | None = None):
        super().__init__(tree, leaf_size=leaf_size, seed=seed, degree=degree,
                         detect_grid_spacing=detect_grid_spacing,
                         reweightable=reweightable, use_cache=use_cache,
                         plan=plan)
        self.blk_a = blk_a
        self.blk_b = blk_b
        self.interpret = interpret  # None -> auto (TPU compiled, else interp)

    def _fm_opts_key(self) -> tuple:
        return (self.blk_a, self.blk_b, self.interpret)

    def _pallas_opts(self) -> dict:
        return {"blk_a": self.blk_a, "blk_b": self.blk_b,
                "interpret": self.interpret}
