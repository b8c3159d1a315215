"""Quickstart: exact fast tree-field integration in five minutes.

  PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np

from repro.core import BTFI, FTFI, Exponential, Polynomial, Rational
from repro.graphs.graph import synthetic_graph
from repro.graphs.mst import minimum_spanning_tree

# 1. A graph: path + random extra edges (paper Sec 4.1). FTFI integrates on
#    trees, so we approximate the graph metric with its MST metric.
n = 6000
graph = synthetic_graph(n, n // 2, seed=0)
tree = minimum_spanning_tree(graph)

# 2. A tensor field on the vertices.
rng = np.random.default_rng(0)
X = rng.normal(size=(n, 8))

# 3. Preprocess once (IntegratorTree, O(N log N)), integrate many times.
#    The recursive numpy FTFI is the host reference (exact per-node LDR
#    engines); step 5 runs the same integrals through the jit-able plan.
t0 = time.perf_counter()
integ = FTFI(tree, leaf_size=256)
t_pre = time.perf_counter() - t0

for fn, name in [(Exponential(-0.5), "exp(-0.5 x)"),
                 (Polynomial((1.0, -0.3, 0.02)), "1 - 0.3x + 0.02x^2"),
                 (Rational((1.0,), (1.0, 0.0, 2.0)), "1/(1+2x^2)")]:
    t0 = time.perf_counter()
    out = integ.integrate(fn, X)
    t_fast = time.perf_counter() - t0
    print(f"f = {name:20s} integrated {n} vertices x 8 channels "
          f"in {t_fast*1e3:7.1f} ms")

# 4. Exactness: identical to brute force (materialized N x N kernel).
t0 = time.perf_counter()
btfi = BTFI(tree, dtype=np.float32)
t_pre_b = time.perf_counter() - t0
fn = Exponential(-0.5)
t0 = time.perf_counter()
ref = btfi.integrate(fn, X)
t_brute = time.perf_counter() - t0
got = integ.integrate(fn, X)
err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
print(f"\nexact vs brute force: rel err = {err:.2e}")
print(f"preprocessing: FTFI {t_pre:.2f}s vs BTFI {t_pre_b:.2f}s "
      f"({t_pre_b/max(t_pre, 1e-9):.1f}x)")

# 5. The plan API: static PlanSpec (pytree aux) + differentiable
#    PlanParams (pytree leaves), one executor on two backends:
#      plan   jit-able bucketed plan executor (exact LDR + Chebyshev)
#      pallas plan executor on the fused fdist_matvec TPU kernel
#    Pure (params, X) -> Y crosses jit boundaries explicitly — vmap over
#    batched fields, checkpoint/serve the plan, and (with
#    reweightable=True) train the tree metric itself.
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import ftfi  # noqa: E402

sub_n = 1500
sub = minimum_spanning_tree(synthetic_graph(sub_n, sub_n // 2, seed=1))
Xs = rng.normal(size=(sub_n, 8))
ref = BTFI(sub).integrate(fn, Xs)
spec, params = ftfi.build(sub, leaf_size=64)
for backend in ("plan", "pallas"):
    t0 = time.perf_counter()
    got = np.asarray(ftfi.apply(spec, params, fn, Xs, backend=backend))
    dt = time.perf_counter() - t0
    err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    engine = ftfi.describe(spec, fn, backend)["cross_engine"]
    print(f"backend={backend:6s} rel err vs BTFI = {err:.2e}  "
          f"({dt*1e3:.1f} ms, engine={engine})")

fm = jax.jit(ftfi.fastmult(spec, fn))
got = np.asarray(fm(params, Xs))
err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
print(f"\nftfi.apply (jitted)   rel err vs BTFI = {err:.2e}  [{spec!r}]")

# learnable tree metric: gradients flow into edge weights via ftfi.reweight
small = minimum_spanning_tree(synthetic_graph(200, 100, seed=3))
rspec, _ = ftfi.build(small, leaf_size=32, reweightable=True)
w = jnp.asarray(small.weights, jnp.float32)
Xp = jnp.asarray(rng.normal(size=(200, 4)), jnp.float32)
g = jax.grad(lambda w_: jnp.sum(
    ftfi.apply(rspec, ftfi.reweight(rspec, w_), fn, Xp) ** 2))(w)
print(f"d(loss)/d(edge weights): shape={g.shape}, "
      f"|g|_1={float(jnp.sum(jnp.abs(g))):.3g}  (tree metric is trainable)")
