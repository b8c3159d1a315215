"""Registered audit entry points: every public compiled surface of the repo.

Each entry is a zero-arg builder returning ``(fn, args)`` — small enough to
trace in seconds on CPU, shaped exactly like the production path (same code
route, same engines, same shard_map wrapping).  ``python -m repro.analysis
--audit`` traces each one and diffs the census against its section of
``ANALYSIS_BUDGETS.json``; tests iterate the same registry so the budget
file and the test suite can never drift apart.

Sections: ``core`` (ftfi functional API on the plan and pallas backends), ``kernels`` (Pallas
ops), ``models`` (train steps / forwards), ``serve`` (prefill), ``sharded``
(shard_map paths — need >= 8 devices, skipped otherwise).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np


class SkipEntry(Exception):
    """Entry point not traceable in this environment (e.g. too few devices)."""


@dataclasses.dataclass
class EntryPoint:
    name: str
    section: str
    build: Callable[[], tuple[Callable, tuple]]
    doc: str = ""


REGISTRY: dict[str, EntryPoint] = {}


def entry(name: str, section: str, doc: str = ""):
    def deco(fn):
        REGISTRY[name] = EntryPoint(name, section, fn, doc)
        return fn

    return deco


def by_section(section: str) -> list[EntryPoint]:
    return [e for e in REGISTRY.values() if e.section == section]


def _require_devices(n: int) -> None:
    import jax
    if len(jax.devices()) < n:
        raise SkipEntry(f"needs >= {n} devices, have {len(jax.devices())} "
                        f"(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _rng():
    return np.random.default_rng(0)


def _mesh24():
    from repro.launch.mesh import make_local_mesh
    _require_devices(8)
    return make_local_mesh(data=2, model=4)


# ---------------------------------------------------------------------------
# core: ftfi functional API + plan engines
# ---------------------------------------------------------------------------

@entry("ftfi.fastmult.tree", "core",
       "fused plan executor, structured exp cross engine")
def _ftfi_fastmult_tree():
    import repro.ftfi as ftfi
    from repro.core import cordial as C
    from repro.graphs.graph import random_tree

    spec, params = ftfi.build(random_tree(96, seed=0))
    X = _rng().standard_normal((96, 4), dtype=np.float32)
    return ftfi.fastmult(spec, C.Exponential(-0.5)), (params, X)


@entry("ftfi.apply.chebyshev", "core",
       "raw-callable f via the batched Chebyshev cross engine")
def _ftfi_apply_cheb():
    import repro.ftfi as ftfi
    from repro.graphs.graph import random_tree

    spec, _params = ftfi.build(random_tree(96, seed=1))
    X = _rng().standard_normal((96, 2), dtype=np.float32)

    def fwd(params, X):
        return ftfi.apply(spec, params, lambda s: 1.0 / (1.0 + s * s), X)

    return fwd, (_params, X)


@entry("ftfi.fastmult.forest", "core",
       "many trees packed into one fused plan dispatch")
def _ftfi_fastmult_forest():
    import repro.ftfi as ftfi
    from repro.core import cordial as C
    from repro.graphs.graph import Forest, random_tree

    fo = Forest([random_tree(40 + 7 * i, seed=i) for i in range(3)])
    spec, params = ftfi.build(fo)
    X = _rng().standard_normal((spec.n, 3), dtype=np.float32)
    return ftfi.fastmult(spec, C.Exponential(-0.3)), (params, X)


@entry("ftfi.reweight.grad", "core",
       "edge-weight gradient through reweight + apply (learnable metrics)")
def _ftfi_reweight_grad():
    import jax
    import jax.numpy as jnp
    import repro.ftfi as ftfi
    from repro.core import cordial as C
    from repro.graphs.graph import random_tree

    t = random_tree(64, seed=2)
    spec, _ = ftfi.build(t, reweightable=True)
    X = _rng().standard_normal((64, 2), dtype=np.float32)
    w0 = np.asarray(t.weights, np.float32)

    def loss(w, X):
        p = ftfi.reweight(spec, w)
        return jnp.sum(ftfi.apply(spec, p, C.Exponential(-0.5), X) ** 2)

    return jax.grad(loss), (w0, X)


@entry("ftfi.fastmult.pallas", "core",
       "fused plan executor on the fdist_matvec kernel (interpret off-TPU)")
def _ftfi_fastmult_pallas():
    import repro.ftfi as ftfi
    from repro.core import cordial as C
    from repro.graphs.graph import random_tree

    spec, params = ftfi.build(random_tree(80, seed=4))
    X = _rng().standard_normal((80, 2), dtype=np.float32)
    return (ftfi.fastmult(spec, C.Exponential(-0.5), backend="pallas"),
            (params, X))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@entry("kernels.fdist_matvec_batched", "kernels",
       "bucketed fused distance-matvec Pallas kernel")
def _fdist():
    from repro.kernels.fdist_matvec.ops import fdist_matvec_batched

    r = _rng()
    x = r.standard_normal((4, 32), dtype=np.float32)
    y = r.standard_normal((4, 48), dtype=np.float32)
    v = r.standard_normal((4, 48, 2), dtype=np.float32)
    coeffs = np.asarray([1.0, -0.5, 0.25], np.float32)

    def fwd(x, y, v, coeffs):
        return fdist_matvec_batched(x, y, v, coeffs, mode="poly")

    return fwd, (x, y, v, coeffs)


@entry("kernels.topo_linear_attention.causal_exp", "kernels",
       "fused Alg.-1 masked linear attention, separable exp decay")
def _topo_attn_exp():
    from repro.kernels.topo_linear_attention.ops import topo_linear_attention

    r = _rng()
    qf = np.abs(r.standard_normal((1, 2, 64, 8), dtype=np.float32))
    kf = np.abs(r.standard_normal((1, 2, 64, 8), dtype=np.float32))
    v = r.standard_normal((1, 2, 64, 4), dtype=np.float32)
    coeffs = np.asarray([1.0, -0.5], np.float32)

    def fwd(qf, kf, v, coeffs):
        return topo_linear_attention(qf, kf, v, coeffs, g="exp", causal=True)

    return fwd, (qf, kf, v, coeffs)


@entry("kernels.topo_linear_attention.bidir_rank", "kernels",
       "rank-R Chebyshev mask path, bidirectional")
def _topo_attn_rank():
    from repro.kernels.topo_linear_attention.ops import topo_linear_attention

    r = _rng()
    qf = np.abs(r.standard_normal((1, 2, 64, 8), dtype=np.float32))
    kf = np.abs(r.standard_normal((1, 2, 64, 8), dtype=np.float32))
    v = r.standard_normal((1, 2, 64, 4), dtype=np.float32)
    coeffs = np.asarray([1.0, -0.5, 0.25, -0.1], np.float32)

    def fwd(qf, kf, v, coeffs):
        return topo_linear_attention(qf, kf, v, coeffs, g="exp",
                                     causal=False, rank=8)

    return fwd, (qf, kf, v, coeffs)


# ---------------------------------------------------------------------------
# models + serve
# ---------------------------------------------------------------------------

def _lm_setup(**over):
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_smoke_config
    from repro.models import api

    cfg = get_smoke_config("llama3_2_1b").replace(dtype="float32", **over)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        _rng().integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    return cfg, params, tokens


@entry("models.lm.train_step", "models", "LM train step (loss+grad+adamw)")
def _lm_train():
    from repro.launch.steps import make_train_step
    from repro.optim.adamw import AdamWConfig, adamw_init

    cfg, params, tokens = _lm_setup()
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10,
                       weight_decay=0.0)
    step = make_train_step(cfg, ocfg)
    return step, (params, adamw_init(params), {"tokens": tokens})


@entry("models.topolm.train_step", "models",
       "topo-attention LM train step (fft mask impl)")
def _topolm_train():
    from repro.launch.steps import make_train_step
    from repro.optim.adamw import AdamWConfig, adamw_init

    cfg, params, tokens = _lm_setup(attention_variant="topo",
                                    topo_attn_impl="fft")
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10,
                       weight_decay=0.0)
    step = make_train_step(cfg, ocfg)
    return step, (params, adamw_init(params), {"tokens": tokens})


@entry("models.topovit.forward", "models",
       "TopoViT forward with the 3-scalar RPE tree mask")
def _vit_forward():
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_smoke_config
    from repro.models import vit

    cfg = get_smoke_config("topovit_b16").replace(dtype="float32")
    integ = vit.build_grid_integrator(cfg)
    params = vit.init_params(cfg, jax.random.PRNGKey(0), num_classes=10,
                             patch_dim=48)
    patches = jnp.asarray(
        _rng().standard_normal((2, cfg.num_prefix_embeddings, 48)),
        jnp.float32)

    def fwd(params, patches):
        return vit.forward(cfg, params, patches, integ)

    return fwd, (params, patches)


@entry("serve.prefill_into_cache", "serve",
       "fused whole-prompt prefill (one call per pow2 bucket)")
def _prefill():
    import jax.numpy as jnp
    from repro.models import api

    cfg, params, tokens = _lm_setup()
    S = 32
    cache = api.init_cache(cfg, 2, S)
    lengths = jnp.asarray([16, 9], jnp.int32)

    def fwd(params, cache, tokens, lengths):
        return api.prefill_into_cache(cfg, params, cache, tokens, lengths, S)

    return fwd, (params, cache, tokens, lengths)


# ---------------------------------------------------------------------------
# sharded paths (>= 8 devices; the CLI forces 8 fake CPU devices)
# ---------------------------------------------------------------------------

@entry("sharded.ftfi.fastmult.tree", "sharded",
       "shard_map executor: 1 all_to_all halo + 1 psum_scatter reduce")
def _sharded_tree():
    import repro.ftfi as ftfi
    from repro.core import cordial as C
    from repro.graphs.graph import random_tree

    mesh = _mesh24()
    spec, params = ftfi.build(random_tree(120, seed=1))
    X = _rng().standard_normal((120, 2), dtype=np.float32)
    fm = ftfi.sharded_fastmult(spec, C.Exponential(-0.5), mesh=mesh)
    return fm, (params, X)


@entry("sharded.ftfi.fastmult.forest", "sharded",
       "sharded forest plan: same two-collective discipline")
def _sharded_forest():
    import repro.ftfi as ftfi
    from repro.core import cordial as C
    from repro.graphs.graph import Forest, random_tree

    mesh = _mesh24()
    fo = Forest([random_tree(40 + 7 * i, seed=i) for i in range(3)])
    spec, params = ftfi.build(fo)
    X = _rng().standard_normal((spec.n, 3), dtype=np.float32)
    fm = ftfi.sharded_fastmult(spec, C.Exponential(-0.4), mesh=mesh)
    return fm, (params, X)


@entry("sharded.models.topovit.forward", "sharded",
       "TopoViT forward with cfg.topo_shard_plan on a (2,4) mesh")
def _sharded_vit():
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_smoke_config
    from repro.launch import sharding as SH
    from repro.models import vit

    mesh = _mesh24()
    cfg = get_smoke_config("topovit_b16").replace(dtype="float32")
    integ = vit.build_grid_integrator(cfg)
    params = vit.init_params(cfg, jax.random.PRNGKey(0), num_classes=10,
                             patch_dim=48)
    patches = jnp.asarray(
        _rng().standard_normal((2, cfg.num_prefix_embeddings, 48)),
        jnp.float32)
    cfg_s = cfg.replace(topo_shard_plan=True)

    def fwd(params, patches):
        with SH.use_sharding(mesh):
            return vit.forward(cfg_s, params, patches, integ)

    return fwd, (params, patches)
