"""Training substrate: loop convergence, compression, watchdog, optimizer."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import get_smoke_config
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.optim.compress import compress_grads, compressor_init
from repro.train.loop import StragglerWatchdog, TrainLoopConfig, run_training


def test_adamw_quadratic_convergence():
    params = {"w": jnp.asarray([3.0, -2.0])}
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                      total_steps=300, clip_norm=10.0)
    state = adamw_init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(g, state, params, cfg)
    assert float(loss(params)) < 1e-3


def test_adamw_state_dtypes_stable_over_steps():
    """bf16 params keep f32 moments from init on, so a jitted step sees the
    same argument types at every step, and the params stay bf16."""
    params = {"w": jnp.asarray([3.0, -2.0], jnp.bfloat16)}
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                      total_steps=10, clip_norm=1.0)
    state = adamw_init(params)
    dtypes = lambda t: [a.dtype for a in jax.tree.leaves(t)]
    init = dtypes((params, state))
    assert dtypes(state.mu) == dtypes(state.nu) == [jnp.float32]
    loss = lambda p: jnp.sum(p["w"].astype(jnp.float32) ** 2)
    for _ in range(2):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(g, state, params, cfg)
        assert dtypes((params, state)) == init


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(cosine_schedule(jnp.asarray(s), cfg)) for s in range(101)]
    assert lrs[0] < 0.2 and abs(lrs[10] - 1.0) < 1e-6
    assert abs(lrs[100] - 0.1) < 1e-6
    assert all(a >= b - 1e-9 for a, b in zip(lrs[10:], lrs[11:]))  # decay


def test_compression_error_feedback_converges():
    """int8 EF compression still drives the quadratic to zero."""
    params = {"w": jnp.asarray(np.linspace(-2, 2, 16), jnp.float32)}
    cfg = AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=1,
                      total_steps=500, clip_norm=100.0)
    state = adamw_init(params)
    cstate = compressor_init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(500):
        g = jax.grad(loss)(params)
        g, cstate = compress_grads(g, cstate)
        params, state, _ = adamw_update(g, state, params, cfg)
    assert float(loss(params)) < 1e-2


def test_training_loss_decreases(tmp_path):
    cfg = get_smoke_config("llama3_2_1b").replace(dtype="float32")
    loop = TrainLoopConfig(steps=150, batch_size=8, seq_len=64,
                           ckpt_dir=str(tmp_path / "ck"), ckpt_every=1000,
                           log_every=1000)
    opt = AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=150,
                      weight_decay=0.0)
    res = run_training(cfg, loop, opt, verbose=False)
    first = np.mean(res["losses"][:5])
    last = np.mean(res["losses"][-5:])
    assert last < first - 0.3, f"{first} -> {last}"


def test_training_with_microbatches_and_compression(tmp_path):
    cfg = get_smoke_config("qwen2_1_5b").replace(dtype="float32")
    loop = TrainLoopConfig(steps=10, batch_size=4, seq_len=32, microbatches=2,
                           ckpt_dir=str(tmp_path / "ck"), ckpt_every=50,
                           compress_grads=True, log_every=100)
    res = run_training(cfg, loop, verbose=False)
    assert np.isfinite(res["losses"]).all()


def test_straggler_watchdog():
    wd = StragglerWatchdog(factor=2.0, warmup=3)
    for s in range(10):
        wd.observe(s, 0.1)
    assert wd.observe(10, 0.5)  # 5x the EMA -> flagged
    assert wd.events and wd.events[-1][0] == 10
    assert not wd.observe(11, 0.11)
