"""Fault tolerance: crash -> restart resumes bit-identically; checkpoint
atomicity; elastic restore under a different sharding."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import get_smoke_config
from repro.train.loop import TrainLoopConfig, run_training


def test_crash_restart_bit_identical(tmp_path):
    cfg = get_smoke_config("llama3_2_1b").replace(dtype="float32")
    common = dict(batch_size=4, seq_len=32, ckpt_every=5, log_every=1000)

    # uninterrupted run
    loopA = TrainLoopConfig(steps=14, ckpt_dir=str(tmp_path / "A"), **common)
    resA = run_training(cfg, loopA, verbose=False)

    # interrupted at step 9 (after the step-5 checkpoint), then restarted
    loopB1 = TrainLoopConfig(steps=14, ckpt_dir=str(tmp_path / "B"),
                             fail_at_step=9, **common)
    with pytest.raises(RuntimeError, match="injected failure"):
        run_training(cfg, loopB1, verbose=False)
    loopB2 = TrainLoopConfig(steps=14, ckpt_dir=str(tmp_path / "B"), **common)
    resB = run_training(cfg, loopB2, verbose=False)

    # identical final params (deterministic data keyed by global step)
    for a, b in zip(jax.tree.leaves(resA["params"]),
                    jax.tree.leaves(resB["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the post-resume loss trajectory matches the uninterrupted one
    np.testing.assert_allclose(resA["losses"][10:], resB["losses"][-4:],
                               rtol=1e-6)


def test_checkpoint_atomicity_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    params = {"a": jnp.arange(5, dtype=jnp.float32),
              "b": {"c": jnp.ones((2, 3))}}
    for s in (5, 10, 15, 20):
        mgr.save(s, params)
    assert mgr.all_steps() == [15, 20]  # keep=2 collected older ones
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))
    res = mgr.restore(params)
    assert res["step"] == 20
    np.testing.assert_array_equal(np.asarray(res["params"]["a"]),
                                  np.arange(5, dtype=np.float32))


def test_restore_roundtrip_structure(tmp_path):
    """NamedTuple opt state + nested dict params roundtrip exactly."""
    from repro.optim.adamw import adamw_init

    params = {"blocks": {"w": jnp.ones((3, 4)), "b": jnp.zeros(4)},
              "embed": {"table": jnp.full((7, 2), 0.5)}}
    opt = adamw_init(params)
    mgr = CheckpointManager(str(tmp_path), keep=1)
    mgr.save(3, params, opt)
    res = mgr.restore(params, opt)
    assert res["step"] == 3
    for a, b in zip(jax.tree.leaves(res["opt"]), jax.tree.leaves(opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_restore_resharding(tmp_path):
    """Save on one device layout, restore under a 8-device mesh sharding —
    the elastic-scaling path. Runs in a subprocess so the 8 fake devices
    don't leak into this test session."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager

d = os.environ["CKPT_DIR"]
params = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
mgr = CheckpointManager(d, keep=1)
mgr.save(1, params)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
shardings = {"w": NamedSharding(mesh, P("data", "model"))}
res = mgr.restore(params, shardings=shardings)
w = res["params"]["w"]
assert len(w.sharding.device_set) == 8, w.sharding
np.testing.assert_array_equal(np.asarray(w),
                              np.arange(64, dtype=np.float32).reshape(8, 8))
print("ELASTIC_OK")
"""
    env = dict(os.environ, CKPT_DIR=str(tmp_path),
               PYTHONPATH=os.path.abspath("src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]
