"""The program's own measurement points.

- Device scopes (`jax.named_scope`): the plan executor's phases and the
  TopoViT step's parts must reach the compiled HLO's `op_name` metadata,
  which a device trace carries as each op's name stack, in the forward and
  (for the step) the transposed backward pass.
- Host spans (`trace_guard.span`): the plan build's phases, timed and
  counted, with the in-memory plan cache's hits and misses beside them.
"""
import re
import subprocess
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import ftfi
from repro.analysis import trace_guard
from repro.core import cordial as C
from repro.graphs.graph import grid_graph, random_tree
from repro.graphs.mst import minimum_spanning_tree

EXEC_SCOPES = ("ftfi.leaf", "ftfi.gather", "ftfi.cross", "ftfi.scatter",
               "ftfi.diag")
STEP_SCOPES = ("vit.alg1", "vit.attn", "vit.mlp", "vit.layer_params")


def _op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def _scoped(names, scope: str) -> list:
    tok = re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")
    return [n for n in names if tok.search(n)]


# (tree, f, backend, the cross engine that must serve it)
CASES = {
    "exponential": (lambda: random_tree(300, seed=3), C.Exponential(-0.6),
                    "plan", "exponential"),
    "polynomial": (lambda: random_tree(300, seed=3),
                   C.Polynomial((1.0, -0.2, 0.01)), "plan", "polynomial"),
    "chebyshev": (lambda: random_tree(300, seed=3),
                  C.Rational((1.0,), (1.0, 0.0, 0.5)), "plan", "chebyshev"),
    "hankel_fft": (lambda: minimum_spanning_tree(grid_graph(12, 12)),
                   C.Rational((1.0,), (1.0, 0.0, 0.5)), "plan",
                   "hankel_fft"),
    "pallas": (lambda: random_tree(300, seed=3),
               C.Rational((1.0,), (1.0, 0.0, 0.5)), "pallas",
               "fdist_matvec:rational"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_executor_phases_named_in_compiled_hlo(case):
    """Every executor phase names its compiled ops, whatever cross engine
    the plan hands the buckets to (the pallas kernel in interpret mode on
    the CPU)."""
    make, fn, backend, engine = CASES[case]
    spec, params = ftfi.build(make(), leaf_size=16)
    assert ftfi.describe(spec, fn, backend)["cross_engine"] == engine
    X = jnp.asarray(np.random.default_rng(0).normal(size=(spec.n, 3)),
                    jnp.float32)
    entry = jax.jit(lambda p, x: ftfi.apply(spec, p, fn, x, backend=backend))
    names = _op_names(entry.lower(params, X).compile())
    for scope in EXEC_SCOPES:
        assert _scoped(names, scope), f"{case}: no op under {scope}"
    if backend == "pallas":
        # the kernel's jitted wrapper keeps its name inside the cross scope
        assert any("ftfi.cross/jit(fdist_matvec_batched_pallas)" in n
                   for n in names)


def test_pallas_integrate_counts_the_vpu_path():
    """A d = 3 pallas integrate keeps the kernel's name inside the cross
    scope and traces the narrow (VPU) path once per cross bucket shape,
    never the MXU one."""
    from repro.kernels.fdist_matvec.kernel import D_VPU

    assert D_VPU >= 3
    spec, params = ftfi.build(random_tree(309, seed=3091), leaf_size=16)
    shapes = {(t.shape, s.shape) for t, s in zip(spec.cross_tgt_mask,
                                                  spec.cross_src_mask)}
    fn = C.Rational((1.0,), (1.0, 0.0, 0.5))
    X = jnp.ones((spec.n, 3), jnp.float32)
    jax.clear_caches()  # the kernel's jit traces each shape once a process
    before = trace_guard.snapshot()
    entry = jax.jit(lambda p, x: ftfi.apply(spec, p, fn, x,
                                            backend="pallas"))
    names = _op_names(entry.lower(params, X).compile())
    after = trace_guard.snapshot()

    def grew(key):
        return after.get(key, 0) - before.get(key, 0)

    assert any("ftfi.cross/jit(fdist_matvec_batched_pallas)" in n
               for n in names)
    assert shapes and grew("fdist_matvec:vpu") == len(shapes)
    assert grew("fdist_matvec:mxu") == 0


@pytest.mark.parametrize("remat", [True, False])
def test_vit_step_parts_named_forward_and_backward(remat):
    """A two-layer TopoViT step (microbatch scan, grad, AdamW) names Alg. 1,
    the attention, the MLP and the per-layer weight slice in the forward
    and under `transpose(...)`, and the optimizer update as `adamw`."""
    from repro.configs.topovit_b16 import SMOKE_CONFIG
    from repro.models import vit
    from repro.optim import adamw

    cfg = SMOKE_CONFIG.replace(remat=remat)
    integ = vit.build_grid_integrator(cfg)
    params = vit.init_params(cfg, jax.random.PRNGKey(0), num_classes=10,
                             patch_dim=48)
    opt_state = adamw.adamw_init(params)
    opt_cfg = adamw.AdamWConfig(warmup_steps=1)

    def loss(p, x, y):
        logits = vit.forward(cfg, p, x, integ).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=1) - picked)

    grad_fn = jax.grad(loss)

    def step(p, o, x, y):
        def acc(g, mb):
            return jax.tree.map(jnp.add, g, grad_fn(p, *mb)), None

        zero = jax.tree.map(jnp.zeros_like, p)
        g, _ = jax.lax.scan(acc, zero, (x.reshape(2, 2, 16, 48),
                                        y.reshape(2, 2)))
        return adamw.adamw_update(g, o, p, opt_cfg)[:2]

    x = jnp.ones((4, 16, 48), jnp.float32)
    y = jnp.zeros((4,), jnp.int32)
    names = _op_names(jax.jit(step).lower(params, opt_state, x, y).compile())
    for scope in STEP_SCOPES:
        ops = _scoped(names, scope)
        assert any("transpose(" not in n for n in ops), \
            f"no forward op under {scope}"
        assert any("transpose(" in n.split(scope)[0] for n in ops), \
            f"no backward op under {scope}"
    assert _scoped(names, "adamw")
    # the mask product nests inside Alg. 1 (the 4 x 4 grid is a single
    # small tree, so it is the dense product by f(D))
    assert any("vit.alg1/ftfi.dense" in n for n in names)


def test_span_nests_and_accumulates():
    outer, inner = "test.spans.outer", "test.spans.inner"
    for _ in range(2):
        with trace_guard.span(outer):
            with trace_guard.span(inner):
                time.sleep(0.01)
            time.sleep(0.01)
    spans = trace_guard.stats()["spans"]
    assert spans[outer]["count"] == 2 and spans[inner]["count"] == 2
    assert trace_guard.seconds(inner) >= 0.02
    assert trace_guard.seconds(outer) >= trace_guard.seconds(inner) + 0.02
    assert spans[outer]["seconds"] == trace_guard.seconds(outer)
    assert trace_guard.seconds("test.spans.never") is None
    # a span that raises is still closed, timed and counted
    with pytest.raises(ValueError):
        with trace_guard.span(inner):
            raise ValueError
    assert trace_guard.stats()["spans"][inner]["count"] == 3


def test_span_is_on_the_profiler_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with trace_guard.span("test.spans.traced"):
            jnp.ones(4).block_until_ready()
    path = next(tmp_path.rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in pd.planes for line in plane.lines
             for ev in line.events}
    assert "test.spans.traced" in names


def test_trace_guard_imports_without_jax():
    code = ("import sys; import repro.analysis.trace_guard as t; "
            "assert callable(t.span) and callable(t.seconds); "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_build_records_its_spans_and_the_plan_cache():
    """A build of a new tree runs and times each phase once and misses the
    in-memory plan cache; the same build again hits it and runs no phase
    but the fingerprint."""
    phases = ("ftfi.build.fingerprint", "ftfi.build.decompose",
              "ftfi.build.assemble")
    tree = random_tree(257, seed=2579)  # a tree no other test builds

    def counts():
        st = trace_guard.stats()
        return ([st["spans"].get(p, {"count": 0})["count"] for p in phases],
                trace_guard.compiles("integrate.plan_cache:miss"),
                trace_guard.compiles("integrate.plan_cache:hit"))

    spans0, miss0, hit0 = counts()
    ftfi.build(tree, leaf_size=16)
    spans1, miss1, hit1 = counts()
    assert [b - a for a, b in zip(spans0, spans1)] == [1, 1, 1]
    assert (miss1 - miss0, hit1 - hit0) == (1, 0)
    for p in phases:
        assert trace_guard.seconds(p) > 0
    ftfi.build(tree, leaf_size=16)
    spans2, miss2, hit2 = counts()
    assert [b - a for a, b in zip(spans1, spans2)] == [1, 0, 0]
    assert (miss2 - miss1, hit2 - hit1) == (0, 1)


# ----------------------------------------------------------------------------
# the MiniMax prefill: lightning, softmax, MoE, embed and head scopes and the
# counters of the topo family and the held experts
# ----------------------------------------------------------------------------

LM_SCOPES = ("lm.lightning", "lm.lightning.core", "lm.softmax", "lm.moe",
             "lm.moe.router", "lm.moe.experts", "lm.embed", "lm.head")


def _minimax_prefill():
    from repro.configs.base import get_smoke_config
    from repro.models import api

    cfg = get_smoke_config("minimax_text_01").replace(dtype="float32")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    B, L, S = 2, 16, 18
    cache = api.init_cache(cfg, B, S)
    toks = jnp.zeros((B, L), jnp.int32)
    lens = jnp.full((B,), L, jnp.int32)
    fn = jax.jit(lambda p, c, t, n: api.prefill_into_cache(cfg, p, c, t, n,
                                                           S))
    return cfg, fn.lower(params, cache, toks, lens)


def test_minimax_prefill_scopes_named_in_compiled_hlo():
    _, lowered = _minimax_prefill()
    names = _op_names(lowered.compile())
    for scope in LM_SCOPES:
        assert _scoped(names, scope), scope
    # the decay sweep sits inside its layer's scope
    assert all("lm.lightning/" in n or "lm.lightning)" in n
               for n in _scoped(names, "lm.lightning.core"))


def test_minimax_prefill_counts_decay_family_and_held_experts():
    """Each trace of the prefill records the decay family once per
    lightning layer (7), never the rank family, and the held-expert layer
    once per layer (8) with the 8 experts it holds."""
    before = trace_guard.snapshot()
    cfg, _ = _minimax_prefill()
    after = trace_guard.snapshot()

    def grew(key):
        return after.get(key, 0) - before.get(key, 0)

    assert grew("attention.topo:decay") == 7
    assert grew("attention.topo:rank") == 0
    assert grew(f"moe.held:{len(cfg.moe_held)}") == 8


def test_topo_rank_family_is_counted():
    from repro.kernels.topo_linear_attention.ops import topo_linear_attention

    x = jnp.ones((1, 2, 8, 4), jnp.float32)
    before = trace_guard.compiles("attention.topo:rank")
    topo_linear_attention(x, x, x, jnp.asarray([0.0, -0.1, -0.1]))
    assert trace_guard.compiles("attention.topo:rank") == before + 1


EXCHANGE = r"""
import os, re
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.configs.base import get_smoke_config
from repro.launch import sharding as SH
from repro.launch.mesh import make_mesh
from repro.models import moe as MOE

cfg = get_smoke_config("minimax_text_01").replace(dtype="float32")
p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
x = jnp.ones((2, 16, cfg.d_model), jnp.float32)
with SH.use_sharding(make_mesh((1, 4), ("data", "model"))):
    text = jax.jit(lambda p, x: MOE.moe_block(cfg, p, x)[0]).lower(
        p, x).compile().as_text()
ops = [l for l in text.splitlines() if re.search(r"all-reduce(-start)?\(", l)]
assert ops and all("lm.moe.exchange" in l for l in ops), ops
print("EXCHANGE_OK")
"""


def test_moe_exchange_scope_names_the_all_reduce():
    """On a 4-device mesh the held experts' parts meet in one all-reduce,
    named `lm.moe.exchange`."""
    import os

    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", EXCHANGE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "EXCHANGE_OK" in out.stdout, (out.stdout[-800:],
                                         out.stderr[-3000:])
