"""Multi-device FTFI: shard_map executor parity and collective discipline.

Device-count tests run in a subprocess (8 fake CPU devices via XLA_FLAGS)
so the flag never leaks into the main test session — the
tests/test_distribution.py pattern. Single-device concerns (the auto
backend threshold, mesh provenance rejection) run in-process.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

_ENV = lambda: dict(os.environ, PYTHONPATH=os.path.abspath("src"))


def _run(code: str, timeout=560):
    env = _ENV()
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    return out


_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_local_mesh
import repro.ftfi as ftfi
from repro.core import cordial as C

mesh = make_local_mesh(data=2, model=4)
rng = np.random.RandomState(0)

def relerr(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a - b).max() / (np.abs(b).max() + 1e-30)
"""


def test_sharded_apply_parity_tree_and_forest():
    """apply_sharded on the (2,4) mesh == single-device apply at 1e-6 across
    tree + forest plans, exp + Chebyshev crosses, reweighted params,
    update_plan-edited plans, and tree weights."""
    code = _PRELUDE + r"""
from repro.graphs.graph import Forest, random_tree

t = random_tree(257, seed=3)
spec, params = ftfi.build(t, reweightable=True)
X = rng.randn(257, 4).astype(np.float32)
# Exponential rides the structured exp cross engine; the raw callable
# rides the Chebyshev-approximation cross engine
for fn in (C.Exponential(-0.4), lambda s: 1.0 / (1.0 + s * s)):
    Y0 = ftfi.apply(spec, params, fn, X)
    Ys = ftfi.apply_sharded(spec, params, fn, X, mesh=mesh)
    e = relerr(Ys, Y0)
    assert e < 1e-6, (type(fn).__name__, e)

# reweighted params (learnable tree metric)
ew = np.abs(rng.randn(256)).astype(np.float32) + 0.05
pr = ftfi.reweight(spec, jnp.asarray(ew))
fn = C.Exponential(-0.4)
e = relerr(ftfi.apply_sharded(spec, pr, fn, X, mesh=mesh),
           ftfi.apply(spec, pr, fn, X))
assert e < 1e-5, e

# incrementally updated plan (insert + reweight)
s2, p2 = ftfi.update_plan(spec, params, [("insert_leaf", 5, 0.8)])
s2, p2 = ftfi.update_plan(s2, p2, [("reweight",
                                    np.abs(rng.randn(257)).astype(np.float32) + 0.05)])
X2 = rng.randn(s2.n, 4).astype(np.float32)
e = relerr(ftfi.apply_sharded(s2, p2, fn, X2, mesh=mesh),
           ftfi.apply(s2, p2, fn, X2))
assert e < 1e-5, e

# forest: whole trees land per shard; tree weights ride outside shard_map
fo = Forest([random_tree(40 + 7 * i, seed=i) for i in range(5)])
fs, fp = ftfi.build(fo)
import dataclasses
fp = dataclasses.replace(fp, tree_w=jnp.asarray(
    rng.randn(5).astype(np.float32)))
Xf = rng.randn(fs.n, 3).astype(np.float32)
e = relerr(ftfi.apply_sharded(fs, fp, fn, Xf, mesh=mesh),
           ftfi.apply(fs, fp, fn, Xf))
assert e < 1e-6, e
print("PARITY_OK")
"""
    out = _run(code)
    assert "PARITY_OK" in out.stdout, (out.stdout[-1500:], out.stderr[-3000:])


def test_sharded_forward_collectives():
    """Collective discipline, asserted on the forward jaxpr: the shard_map
    body moves halo rows with all_to_all and reduces partial outputs with
    reduce_scatter (psum_scatter) — and never all-gathers the field or the
    plan index arrays."""
    code = _PRELUDE + r"""
from repro.graphs.graph import random_tree

t = random_tree(120, seed=1)
spec, params = ftfi.build(t)
X = rng.randn(120, 2).astype(np.float32)
fm = ftfi.sharded_fastmult(spec, C.Exponential(-0.5), mesh=mesh)
# structured census over the walked jaxpr (not string matching): exactly
# one halo all_to_all + one output psum_scatter, zero all_gather
from repro.analysis import jaxpr_audit
rep = jaxpr_audit.assert_clean(
    fm, params, X, name="sharded_fastmult",
    budget={"collectives": {"all_to_all": 1, "psum_scatter": 1}})
assert rep.collectives == {"all_to_all": 1, "reduce_scatter": 1}, rep.collectives
assert rep.prim_counts.get("shard_map", 0) >= 1, "not under shard_map"
# grad still matches (the transpose MAY all-gather; only forward is gated)
def loss_s(p, x):
    return jnp.sum(fm(p, x) ** 2)
def loss_d(p, x):
    return jnp.sum(ftfi.apply(spec, p, C.Exponential(-0.5), x) ** 2)
gs = jax.grad(loss_s, argnums=1)(params, X)
gd = jax.grad(loss_d, argnums=1)(params, X)
assert relerr(gs, gd) < 1e-5
print("COLLECTIVES_OK")
"""
    out = _run(code)
    assert "COLLECTIVES_OK" in out.stdout, (out.stdout[-1500:],
                                            out.stderr[-3000:])


def test_sharded_kernel_variants():
    """shard_map faces of both kernel families match their single-device
    wrappers bit-for-bit (no collectives in either: bucket/batch/head slabs
    are independent)."""
    code = _PRELUDE + r"""
from repro.kernels.fdist_matvec.ops import (fdist_matvec_batched,
                                            fdist_matvec_batched_sharded)
from repro.kernels.topo_linear_attention.ops import (
    topo_linear_attention, topo_linear_attention_sharded)

B, a, b, d = 5, 16, 24, 3  # ragged bucket count: exercises the pad path
x = rng.randn(B, a).astype(np.float32)
y = rng.randn(B, b).astype(np.float32)
v = rng.randn(B, b, d).astype(np.float32)
coef = np.array([0.3, -0.7], np.float32)
e = relerr(fdist_matvec_batched_sharded(x, y, v, coef, mesh=mesh, mode="exp"),
           fdist_matvec_batched(x, y, v, coef, mode="exp"))
assert e < 1e-6, e

Bq, H, L, m, hd = 4, 8, 64, 8, 16
qf = np.abs(rng.randn(Bq, H, L, m)).astype(np.float32)
kf = np.abs(rng.randn(Bq, H, L, m)).astype(np.float32)
vv = rng.randn(Bq, H, L, hd).astype(np.float32)
co = (rng.randn(H, 2) * 0.1).astype(np.float32)
for causal in (True, False):
    e = relerr(topo_linear_attention_sharded(qf, kf, vv, co, mesh=mesh,
                                             g="exp", causal=causal),
               topo_linear_attention(qf, kf, vv, co, g="exp", causal=causal))
    assert e < 1e-6, (causal, e)
# head count not divisible by model=4: head axis drops, still exact
e = relerr(topo_linear_attention_sharded(qf[:, :3], kf[:, :3], vv[:, :3],
                                         co[:3], mesh=mesh, g="exp"),
           topo_linear_attention(qf[:, :3], kf[:, :3], vv[:, :3], co[:3],
                                 g="exp"))
assert e < 1e-6, e
print("KERNELS_OK")
"""
    out = _run(code)
    assert "KERNELS_OK" in out.stdout, (out.stdout[-1500:],
                                        out.stderr[-3000:])


def test_update_plan_preserves_named_sharding():
    """Mesh-placed PlanParams keep their NamedSharding through update_plan
    (shape-preserving edits re-upload with the same placement)."""
    code = _PRELUDE + r"""
from repro.graphs.graph import random_tree

t = random_tree(130, seed=1)
spec, params = ftfi.build(t, reweightable=True)
params_m = jax.device_put(params, NamedSharding(mesh, P()))
s2, p2 = ftfi.update_plan(spec, params_m, [
    ("reweight", np.abs(rng.randn(129)).astype(np.float32) + 0.1)])
for leaf in jax.tree.leaves(p2):
    assert isinstance(leaf.sharding, NamedSharding), leaf.sharding
# and the sharded executor consumes the surviving placement exactly
fn = C.Exponential(-0.5)
X = rng.randn(130, 3).astype(np.float32)
e = relerr(ftfi.apply_sharded(s2, p2, fn, X, mesh=mesh),
           ftfi.apply(s2, jax.device_get(p2), fn, X))
assert e < 1e-5, e
print("SHARDING_SURVIVES_OK")
"""
    out = _run(code)
    assert "SHARDING_SURVIVES_OK" in out.stdout, (out.stdout[-1500:],
                                                  out.stderr[-3000:])


def test_mesh_mismatch_rejected():
    """A sharded artifact whose recorded mesh cannot be formed here fails
    plan-guard validation with a clear PlanValidationError."""
    import dataclasses

    import jax

    import repro.ftfi as ftfi
    from repro.graphs.graph import random_tree

    t = random_tree(40, seed=0)
    spec, params = ftfi.build(t)
    bad = dataclasses.replace(spec, shard_layout=ftfi.SHARD_LAYOUT_VERSION,
                              mesh_devices=jax.device_count() + 63,
                              mesh_axes=("data", "model"))
    with pytest.raises(ftfi.PlanValidationError, match="mesh_devices"):
        ftfi.validate(bad, params, where="test")
    newer = dataclasses.replace(
        spec, shard_layout=ftfi.SHARD_LAYOUT_VERSION + 1, mesh_devices=1)
    with pytest.raises(ftfi.PlanValidationError, match="shard_layout"):
        ftfi.validate(newer, params, where="test")
    # a matching mesh passes
    ok = dataclasses.replace(spec, shard_layout=ftfi.SHARD_LAYOUT_VERSION,
                             mesh_devices=1, mesh_axes=("data",))
    assert ftfi.validate(ok, params, where="test")


def test_auto_backend_size_threshold():
    """backend="auto" picks the plan executor below AUTO_PALLAS_MIN_N
    (pallas loses there: speedup_int 0.88 at n=1000) and pallas above."""
    from repro.core import cordial as C
    from repro.core import ladder
    from repro.core.cordial import spec_of
    from repro.core.plan_api import build, select_cross
    from repro.graphs.graph import random_tree

    assert ladder.effective_backend("auto", n=1000) == "plan"
    assert ladder.effective_backend("auto",
                                    n=ladder.AUTO_PALLAS_MIN_N) == "pallas"
    assert ladder.effective_backend("auto") == "plan"  # unknown size: safe

    spec, params = build(random_tree(64, seed=0))
    name, _ = select_cross(spec, spec_of(C.Exponential(-0.5)), backend="auto")
    assert "fdist" not in name, name  # small n resolved to the plan engine


def test_save_plan_records_mesh_provenance(tmp_path):
    import repro.ftfi as ftfi
    from repro.graphs.graph import random_tree

    t = random_tree(40, seed=0)
    spec, params = ftfi.build(t)
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    path = tmp_path / "plan.npz"
    ftfi.save_plan(str(path), spec, params, mesh=mesh)
    s2, _ = ftfi.load_plan(str(path))
    assert s2.mesh_devices == 1
    assert s2.mesh_axes == ("data",)
    assert s2.shard_layout == ftfi.SHARD_LAYOUT_VERSION
    assert "mesh_devices" in s2.provenance
