"""Sharding-rule unit tests (single device: specs only, no mesh compute)."""
import os
import subprocess
import sys


def test_sharding_specs_in_subprocess():
    """Rules produce divisibility-safe PartitionSpecs for every arch."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.configs.base import ARCHS, get_config
from repro.launch import sharding as SH
from repro.launch.specs import params_shapes

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
for arch in ARCHS:
    if arch == "topovit_b16":
        continue
    cfg = get_config(arch)
    with SH.use_sharding(mesh):
        shapes = params_shapes(cfg)
        specs = SH.tree_param_specs(shapes)
        flat_s = jax.tree_util.tree_leaves_with_path(shapes)
        flat_p = jax.tree_util.tree_leaves(specs)
        n_sharded = 0
        for (path, leaf), spec in zip(flat_s, flat_p):
            for i, ax in enumerate(spec):
                if ax is None:
                    continue
                total = 1
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    total *= sizes[a]
                assert leaf.shape[i] % total == 0, (arch, path, leaf.shape, spec)
                n_sharded += 1
        assert n_sharded > 0, f"{arch}: nothing sharded"
print("SPECS_OK")
"""
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert "SPECS_OK" in out.stdout, (out.stdout[-800:], out.stderr[-2000:])


def test_logical_rules_no_double_axis():
    """A mesh axis may appear at most once per spec (jax requirement)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch import sharding as SH

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
with SH.use_sharding(mesh):
    # heads and ff both map to model; only the first position may take it
    spec = SH.logical_to_spec(("batch", "heads", "ff"))
    flat = []
    for ax in spec:
        flat += list(ax) if isinstance(ax, tuple) else ([ax] if ax else [])
    assert len(flat) == len(set(flat)), spec
print("NO_DOUBLE_OK")
"""
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "NO_DOUBLE_OK" in out.stdout, (out.stdout[-800:], out.stderr[-2000:])


def test_ftfi_logical_axes():
    """The FTFI plan axes resolve to the data axis (leaf blocks / cross
    groups / trees shard together), field_batch to the batch axes, and
    `plan_axis` survives meshes without a data axis."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.launch import sharding as SH

for name in ("plan_leaves", "cross_src", "cross_tgt", "tree"):
    assert SH.DEFAULT_RULES[name] == "data", name
assert "data" in SH.DEFAULT_RULES["field_batch"]

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
with SH.use_sharding(mesh):
    assert SH.logical_to_spec(("plan_leaves",)) == P("data")
    assert SH.logical_to_spec(("cross_src",)) == P("data")
    assert SH.logical_to_spec(("field_batch", None)) == P(("data",), None)
    # plan_leaves and cross_tgt both bind data: second occurrence drops
    spec = SH.logical_to_spec(("plan_leaves", "cross_tgt"))
    assert spec == P("data", None), spec
    assert SH.plan_axis() == "data"
assert SH.plan_axis(mesh) == "data"
m2 = make_mesh((8,), ("model",))
assert SH.plan_axis(m2) == "model"  # no data axis: first axis fallback
print("FTFI_AXES_OK")
"""
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "FTFI_AXES_OK" in out.stdout, (out.stdout[-800:], out.stderr[-2000:])
