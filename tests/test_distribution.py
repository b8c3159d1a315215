"""Distribution integration tests on an 8-fake-device CPU mesh (subprocess,
so the device-count flag never leaks into the main test session)."""
import os
import subprocess
import sys

import pytest

_ENV = lambda: dict(os.environ, PYTHONPATH=os.path.abspath("src"))


def _run(code: str, timeout=560):
    env = _ENV()
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    return out


def test_sharded_train_step_matches_single_device():
    """pjit train step on a (2,4) mesh == the same step on 1 device."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_smoke_config
from repro.launch import sharding as SH
from repro.launch.steps import make_train_step
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.models import api

cfg = get_smoke_config("llama3_2_1b").replace(dtype="float32")
ocfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10, weight_decay=0.0)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)}
params = api.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params)
step = make_train_step(cfg, ocfg)

# single device reference
p1, o1, m1 = jax.jit(step)(params, opt, batch)

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
with SH.use_sharding(mesh):
    pspecs = SH.tree_param_specs(params)
    pshard = jax.tree.map(SH.named_sharding, pspecs)
    params_s = jax.device_put(params, pshard)
    opt_s = adamw_init(params_s)
    batch_s = jax.device_put(batch, {"tokens": NamedSharding(mesh, P("data", None))})
    p2, o2, m2 = jax.jit(step)(params_s, opt_s, batch_s)

assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (m1["loss"], m2["loss"])
d = max(float(jnp.max(jnp.abs(a - b))) for a, b in
        zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
assert d < 1e-4, d
print("DIST_TRAIN_OK", float(m1["loss"]))
"""
    out = _run(code)
    assert "DIST_TRAIN_OK" in out.stdout, (out.stdout[-1500:], out.stderr[-3000:])


@pytest.mark.parametrize("arch", ["deepseek_v2_lite_16b", "falcon_mamba_7b"])
def test_sharded_smoke_archs(arch):
    """MoE (expert-parallel dispatch) and SSM smoke configs lower + run on
    the 8-device mesh; loss matches the 1-device value."""
    code = rf"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_smoke_config
from repro.launch import sharding as SH
from repro.models import api

cfg = get_smoke_config("{arch}").replace(dtype="float32")
rng = np.random.default_rng(0)
batch = {{"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)}}
params = api.init_params(cfg, jax.random.PRNGKey(0))
l1 = float(api.loss_fn(cfg, params, batch)[0])
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
with SH.use_sharding(mesh):
    pshard = jax.tree.map(SH.named_sharding, SH.tree_param_specs(params))
    params_s = jax.device_put(params, pshard)
    batch_s = jax.device_put(batch, {{"tokens": NamedSharding(mesh, P("data", None))}})
    l2 = float(jax.jit(lambda p, b: api.loss_fn(cfg, p, b)[0])(params_s, batch_s))
assert abs(l1 - l2) < 1e-3, (l1, l2)
print("DIST_ARCH_OK", l1)
"""
    out = _run(code)
    assert "DIST_ARCH_OK" in out.stdout, (out.stdout[-1500:], out.stderr[-3000:])


def test_topovit_pjit_sharded_topo_path():
    """TopoViT forward under pjit with cfg.topo_shard_plan=True: the grid
    plan executor runs under shard_map on the (2,4) mesh, logits match the
    single-device forward, and the forward jaxpr shows exactly the sharded
    executor's collectives — halo all_to_all + reduce_scatter, never an
    all-gather of the field or the plan index arrays."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_smoke_config
from repro.launch import sharding as SH
from repro.models import vit

cfg = get_smoke_config("topovit_b16").replace(dtype="float32")
integ = vit.build_grid_integrator(cfg)
params = vit.init_params(cfg, jax.random.PRNGKey(0), num_classes=10,
                         patch_dim=48)
rng = np.random.default_rng(0)
patches = jnp.asarray(rng.normal(size=(4, cfg.num_prefix_embeddings, 48)),
                      jnp.float32)
ref = vit.forward(cfg, params, patches, integ)

cfg_s = cfg.replace(topo_shard_plan=True)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
with SH.use_sharding(mesh):
    fwd = lambda p, x: vit.forward(cfg_s, p, x, integ)
    # structured census (repro.analysis): each of the 2 layers runs 2 mask
    # fastmults (numerator + denominator), each with the two-collective
    # discipline — and never an all_gather of the field or the index arrays
    from repro.analysis import jaxpr_audit
    rep = jaxpr_audit.assert_clean(
        fwd, params, patches, name="topovit.sharded",
        budget={"collectives": {"all_to_all": 4, "psum_scatter": 4}})
    assert rep.collectives == {"all_to_all": 4, "reduce_scatter": 4}, rep.collectives
    assert rep.prim_counts.get("shard_map", 0) >= 1, "topo path not under shard_map"
    patches_s = jax.device_put(
        patches, NamedSharding(mesh, P("data", None, None)))
    out = jax.jit(fwd)(params, patches_s)
d = float(jnp.max(jnp.abs(out - ref)))
assert d < 1e-4, d

# grads (incl. the 3 mask scalars) survive the sharded path
with SH.use_sharding(mesh):
    g = jax.jit(jax.grad(lambda p, x: jnp.sum(fwd(p, x) ** 2)))(
        params, patches_s)
gsum = sum(float(jnp.sum(jnp.abs(x)))
           for x in jax.tree.leaves(g["blocks"]["topo"]))
assert np.isfinite(gsum) and gsum > 0
print("TOPOVIT_PJIT_OK", d)
"""
    out = _run(code)
    assert "TOPOVIT_PJIT_OK" in out.stdout, (out.stdout[-1500:],
                                             out.stderr[-3000:])


def test_topolm_sharded_train_step():
    """Topological-LM pjit train step on the (2,4) mesh == 1 device: the
    topo attention path's field_batch/heads shard constraints compose with
    the standard param rules."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_smoke_config
from repro.launch import sharding as SH
from repro.launch.steps import make_train_step
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.models import api

cfg = get_smoke_config("llama3_2_1b").replace(
    dtype="float32", attention_variant="topo", topo_attn_impl="fft")
ocfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10, weight_decay=0.0)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                               jnp.int32)}
params = api.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params)
step = make_train_step(cfg, ocfg)
p1, o1, m1 = jax.jit(step)(params, opt, batch)

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
with SH.use_sharding(mesh):
    pshard = jax.tree.map(SH.named_sharding, SH.tree_param_specs(params))
    params_s = jax.device_put(params, pshard)
    opt_s = adamw_init(params_s)
    batch_s = jax.device_put(
        batch, {"tokens": NamedSharding(mesh, P("data", None))})
    p2, o2, m2 = jax.jit(step)(params_s, opt_s, batch_s)

assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (m1["loss"],
                                                           m2["loss"])
d = max(float(jnp.max(jnp.abs(a - b))) for a, b in
        zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
assert d < 1e-3, d
print("TOPOLM_DIST_OK", float(m1["loss"]))
"""
    out = _run(code)
    assert "TOPOLM_DIST_OK" in out.stdout, (out.stdout[-1500:],
                                            out.stderr[-3000:])


def test_dryrun_cell_small_mesh():
    """The dry-run machinery itself (lower+compile+roofline terms) on a tiny
    mesh with a smoke config — exercises analyze-cell wiring end to end."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs.base import get_smoke_config
from repro.launch import sharding as SH
from repro.launch.dryrun import lower_cell_cfg
from repro.roofline.analysis import collective_bytes_from_hlo

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_smoke_config("llama3_2_1b")
# smoke decode cell: shrink the assigned shape via a fake SHAPES entry
from repro.configs import base
base.SHAPES["tiny_train"] = dict(seq_len=64, global_batch=8, kind="train")
lowered, compiled, _, _ = lower_cell_cfg(cfg, "tiny_train", mesh)
mem = compiled.memory_analysis()
cost = compiled.cost_analysis()
coll = collective_bytes_from_hlo(compiled.as_text())
assert cost.get("flops", 0) > 0
assert coll > 0, "expected collectives on a (2,4) mesh"
print("DRYRUN_OK", cost["flops"], coll)
"""
    out = _run(code)
    assert "DRYRUN_OK" in out.stdout, (out.stdout[-1500:], out.stderr[-3000:])
