"""MiniMax-Text-01 through the normal serving path (`api.init_cache`,
`api.prefill_into_cache`, `api.decode_fn`) against the plain float32
reference `repro.testing.minimax_ref`, at `SMOKE_CONFIG` on seeded random
weights: the lightning layer, the 8-layer prefill and decode through the
hybrid cache, the chip-share expert layer, and the 4-device sharded
prefill."""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_config, get_smoke_config
from repro.models import api
from repro.models import attention as A
from repro.models import moe as MOE
from repro.testing import minimax_ref as R


def _cfg(**over):
    return get_smoke_config("minimax_text_01").replace(dtype="float32",
                                                       **over)


def _rel(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


@pytest.mark.parametrize("layer", [0, 7])
def test_lightning_layer_matches_dense_masked_form(layer, rng):
    cfg = _cfg()
    p = A.lightning_init(jax.random.PRNGKey(layer), cfg)
    p["out_norm"] = jnp.asarray(rng.normal(size=p["out_norm"].shape) * 0.1,
                                jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 40, cfg.d_model)), jnp.float32)
    got = A.lightning_attention_train(cfg, p, x, layer)
    ref = R.lightning(cfg, p, x[0], layer)
    assert _rel(got[0], ref) < 1e-5
    # the published slopes at layer l of 80, head h of 64
    full = get_config("minimax_text_01")
    s = np.asarray(A.lightning_slopes(full, layer))
    h = np.arange(64)
    want = 2.0 ** (-8.0 * (h + 1) / 64) * (1 - layer / 79 + 1e-5)
    np.testing.assert_allclose(s, want, rtol=1e-6)
    assert s[0] == pytest.approx(2 ** -0.125 * (1 - layer / 79 + 1e-5))


@pytest.mark.parametrize("causal,window,cap,Lq", [
    (True, 0, 0.0, 48), (True, 20, 0.0, 48), (True, 0, 5.0, 48),
    (False, 0, 0.0, 48), (False, 0, 0.0, 20)])
def test_chunked_attention_matches_masked(causal, window, cap, Lq, rng):
    """`_sdpa_chunked` (query blocks, key blocks skipped past the diagonal
    or the window) equals the masked dense attention, values and
    gradients; Lq = 20 does not tile the blocks of 16 (one query block)."""
    cfg = _cfg(attn_logit_softcap=cap)
    B, Lk, H, KV, hd = 2, 48, 4, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, n, hd)), jnp.float32)
               for L, n in ((Lq, H), (Lk, KV), (Lk, KV)))
    i, j = np.arange(Lq)[:, None], np.arange(Lk)[None, :]
    mask = np.ones((Lq, Lk), bool)
    if causal:
        mask &= i >= j
    if window:
        mask &= i - j < window
    mask = jnp.asarray(mask)[None, None]

    def chunked(q, k, v):
        return A._sdpa_chunked(cfg, q, k, v, causal, window, blk=16)

    def dense(q, k, v):
        return A._sdpa(cfg, q, k, v, mask)

    assert _rel(chunked(q, k, v), dense(q, k, v)) < 1e-5
    w = jnp.asarray(rng.normal(size=(B, Lq, H, hd)), jnp.float32)
    gc = jax.grad(lambda *a: jnp.sum(chunked(*a) * w), (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(gc, gd):
        assert _rel(a, b) < 1e-4


def _prompt(cfg, rng, B, L):
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (B, L)), jnp.int32)


def test_prefill_and_decode_match_reference(rng, monkeypatch):
    """The 8-layer prefill's last logits and 2 decode steps through the
    hybrid cache (lightning states beside a KV cache) equal the reference's
    logits on the extended sequence (expert tiles of 16 rows)."""
    monkeypatch.setattr(MOE, "TILE", 16)
    cfg = _cfg()
    params = api.init_params(cfg, jax.random.PRNGKey(5))
    B, L, n = 2, 48, 2
    toks = _prompt(cfg, rng, B, L + n)
    S = L + n
    cache = api.init_cache(cfg, B, S)
    logits, cache = api.prefill_into_cache(
        cfg, params, cache, toks[:, :L], jnp.full((B,), L, jnp.int32), S)
    steps = [logits]
    for t in range(n):
        lg, cache = api.decode_fn(cfg, params, cache, toks[:, L + t:L + t + 1],
                                  jnp.asarray(L + t, jnp.int32), S)
        steps.append(lg[:, 0])
    V = cfg.vocab_size
    for b in range(B):
        routes = []
        ref = R.forward(cfg, params, toks[b], routes)
        for t, got in enumerate(steps):
            assert _rel(got[b, :V], ref[L - 1 + t, :V]) < 1e-4, (b, t)
        # the route log holds each position's experts, prefill and decode
        for l, kind in enumerate(R.layer_kinds(cfg)):
            log = cache["blocks0"][f"b{l}_{kind}"]["routes"][0, b]
            np.testing.assert_array_equal(np.sort(np.asarray(log), -1),
                                          routes[l], err_msg=str(l))


def test_expert_shares_sum_to_uncut_layer(rng):
    """The 4 shares of 8 experts of a 32-expert layer, each computed by the
    chip-share layer told which experts it holds, add up to the layer that
    holds all 32 (and to the reference's uncut layer)."""
    whole = _cfg(moe_held=tuple(range(32)))
    p = MOE.moe_init(jax.random.PRNGKey(9), whole)
    x = jnp.asarray(rng.normal(size=(2, 24, whole.d_model)), jnp.float32)
    full, _ = MOE.moe_block(whole, p, x)
    parts = []
    for c in range(4):
        held = tuple(range(8 * c, 8 * c + 8))
        share = {k: (v if k == "router" else v[8 * c:8 * c + 8])
                 for k, v in p.items()}
        y, _ = MOE.moe_block(whole.replace(moe_held=held), share, x)
        parts.append(y)
    assert _rel(sum(parts), full) < 1e-5
    with jax.default_matmul_precision("highest"):
        ref = R.moe(whole, p, x.reshape(-1, whole.d_model))
    assert _rel(full.reshape(-1, whole.d_model), ref) < 1e-5


def test_moe_is_dropless(rng, monkeypatch):
    """Every token routed to one held expert: nothing is dropped, however
    many tiles the routed rows take."""
    monkeypatch.setattr(MOE, "TILE", 4)
    cfg = _cfg()
    p = MOE.moe_init(jax.random.PRNGKey(2), cfg)
    # a router that sends every token to expert 3 first and expert 5 second
    r = np.zeros(p["router"].shape, np.float32)
    r[:, 3], r[:, 5] = 50.0, 40.0
    p["router"] = jnp.asarray(r)
    x = jnp.asarray(np.abs(rng.normal(size=(1, 30, cfg.d_model))) + 0.1,
                    jnp.float32)
    got, _ = MOE.moe_block(cfg, p, x)
    with jax.default_matmul_precision("highest"):
        ref = R.moe(cfg, p, x[0])
    assert _rel(got[0], ref) < 1e-5


SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.configs.base import get_smoke_config
from repro.launch import sharding as SH
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.analysis import trace_guard

cfg = get_smoke_config("minimax_text_01").replace(dtype="float32")
params = api.init_params(cfg, jax.random.PRNGKey(3))
rng = np.random.default_rng(4)
B, L, S = 2, 32, 34
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, L)), jnp.int32)
lens = jnp.full((B,), L, jnp.int32)

def run(p):
    cache = api.init_cache(cfg, B, S)
    lg, cache = api.prefill_into_cache(cfg, p, cache, toks, lens, S)
    nxt = toks[:, :1]
    lg2, _ = api.decode_fn(cfg, p, cache, nxt, jnp.asarray(L, jnp.int32), S)
    return lg, lg2[:, 0]

one = jax.jit(run)(params)
mesh = make_mesh((1, 4), ("data", "model"))
with SH.use_sharding(mesh):
    specs = SH.tree_param_specs(params)
    placed = jax.tree.map(lambda a, s: jax.device_put(a, SH.named_sharding(s)),
                          params, specs)
    w = placed["blocks0"]["b0_lightning"]["moe"]["experts_w_gate"]
    assert len(w.sharding.device_set) == 4
    assert w.addressable_shards[0].data.shape[1] == 2, w.sharding
    four = jax.jit(run)(placed)
for a, b in zip(one, four):
    err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))
    assert err < 1e-5, err
print("SHARDED_OK")
"""


def test_sharded_prefill_equals_unsharded():
    """On a data 1 x model 4 mesh of CPU devices (heads and experts split
    4 ways, parameters placed by `tree_param_specs`), prefill and a decode
    step give the single-device logits."""
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                         capture_output=True, text=True, timeout=400)
    assert "SHARDED_OK" in out.stdout, (out.stdout[-800:], out.stderr[-3000:])
