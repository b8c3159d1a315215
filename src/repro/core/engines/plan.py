"""`plan` backend: the jit-able IT-plan executor, now a facade over the
functional core (`repro.core.plan_api`).

The executor and the batched cross engines (polynomial / exponential /
hankel_fft / chebyshev) live in `plan_api`; this module keeps the legacy
entry points working on top of them:

  execute_plan(plan, X, fn_eval, ...)   derives the plan's (spec, params)
                                        pair and runs the pure executor
  PlanBackend                           derives (spec, params) lazily from
                                        the compiled plan and caches jitted
                                        closures over plan_api.apply

so every Integrator — and everything stacked on it (masks, ViT grids,
forests, serving) — executes through the same pure
`_execute(spec, params, ...)` path that `ftfi.apply` exposes directly.
"""
from __future__ import annotations

from typing import Callable

from repro.analysis import trace_guard
from repro.core import plan_api
from repro.core.engines.base import register_backend
from repro.core.engines.spec import FamilySpec, spec_of
from repro.core.integrate import (IntegrationPlan, compile_forest_plan,
                                  compile_plan)
# legacy import locations (tests, masks, attention import these from here)
from repro.core.plan_api import (  # noqa: F401
    _lagrange_batched, chebyshev_batched_matvec, exponential_batched_matvec,
    hankel_batched_matvec, polynomial_batched_matvec)
from repro.graphs.graph import Forest


# ----------------------------------------------------------------------------
# executor (legacy entry point over the functional core)
# ----------------------------------------------------------------------------


def execute_plan(plan: IntegrationPlan, X, fn_eval: Callable,
                 batched_matvec: Callable | None = None, degree: int = 32,
                 cross_multiply: Callable | None = None):
    """Integrate field X (n, d) with scalar function `fn_eval` (jnp-traceable).

    Thin shim: splits the plan into its functional (spec, params) pair and
    runs `plan_api._execute`. `cross_multiply(cb, Xp)` (legacy CrossBucket
    form) and `batched_matvec(tgt_d, tgt_mask, src_d, src_mask, Xp)` are
    still accepted; both default to batched Chebyshev interpolation
    (spectral-exact for smooth fn_eval, differentiable w.r.t. fn_eval
    parameters).
    """
    spec, params = plan_api.specialize(plan)
    if cross_multiply is not None:
        legacy = cross_multiply

        def cross(i, tgt_d, tgt_mask, src_d, src_mask, Xp):
            return legacy(plan.cross_buckets[i], Xp)

    elif batched_matvec is not None:
        bm = batched_matvec

        def cross(i, tgt_d, tgt_mask, src_d, src_mask, Xp):
            return bm(tgt_d, tgt_mask, src_d, src_mask, Xp)

    else:
        _, cross = plan_api.select_cross(
            spec, FamilySpec(None, (), fn_eval, None), degree=degree)
    return plan_api._execute(spec, params, fn_eval, cross, X)


# ----------------------------------------------------------------------------
# backend
# ----------------------------------------------------------------------------


def _trace_state_clean() -> bool:
    """True when no jax trace is currently active (safe to memoize)."""
    try:
        import jax

        return jax.core.trace_state_clean()
    except Exception:
        return True


class _PlanFastMult:
    """One cached X -> M_f X closure per (plan, f-family).

    Each executor trace records one `engines.plan.fastmult` compile in
    `trace_guard`: back-to-back jitted calls with the same shapes record
    none, which is exactly the no-retrace property the fastmult cache
    exists for."""

    def __init__(self, eager: Callable, jit_compile: bool):
        import jax

        self.jitted = bool(jit_compile)

        def counted(X):
            if isinstance(X, jax.core.Tracer):  # compile, not an eager call
                trace_guard.record("engines.plan.fastmult")
            return eager(X)

        if jit_compile:
            self._call = jax.jit(counted)
        else:
            self._call = counted

    def __call__(self, X):
        return self._call(X)


@register_backend("plan")
class PlanBackend:
    """Bucketed static-shape executor; cross engine chosen per f family:
    exact polynomial/exponential LDR engines, the exact Hankel/FFT engine on
    grid-aligned trees, Chebyshev interpolation otherwise.

    The (content-cached) plan splits lazily into the functional
    (spec, params) pair — exposed as `.spec` / `.params` for the pure
    `ftfi` entry points — and `fastmult` closures are jitted (when the f
    family is traceable) and cached per family spec, so repeated
    `integrate` calls pay zero re-dispatch/re-trace overhead."""

    name = "plan"

    def __init__(self, tree, leaf_size: int = 64, seed: int = 0,
                 degree: int = 32, detect_grid_spacing: bool = True,
                 reweightable: bool = False, use_cache: bool = True,
                 plan: IntegrationPlan | None = None):
        from repro.core.lru import BoundedLRU

        # a Forest compiles into ONE fused plan over the packed vertex space:
        # the executor below is oblivious to how many trees it covers
        self.forest = tree if isinstance(tree, Forest) else None
        # the single tree the plan was built from (None for a forest or a
        # loaded plan): `masks.make_tree_fastmult` takes its exact
        # all-pairs distances from it for the dense small-tree path
        self.tree = tree if self.forest is None else None
        if plan is not None:  # facade-from-artifact path: zero IT rebuild
            self.plan = plan
        elif self.forest is not None:
            self.plan = compile_forest_plan(
                self.forest, leaf_size=leaf_size, seed=seed,
                detect_grid_spacing=detect_grid_spacing,
                use_cache=use_cache, reweightable=reweightable)
        else:
            self.plan = compile_plan(tree, leaf_size=leaf_size, seed=seed,
                                     detect_grid_spacing=detect_grid_spacing,
                                     use_cache=use_cache,
                                     reweightable=reweightable)
        self.degree = degree
        # the semantically-keyed fastmult memo lives ON the plan object:
        # plans are content-hash cached, so repeated Integrator construction
        # over the same topology (bench steady state, serving, mask rebuilds)
        # reuses the compiled closures instead of re-tracing per instance.
        # Keys are prefixed with the backend name + opts (see fastmult), so
        # differently-configured backends sharing one plan never serve each
        # other's closures. Opaque id()-keyed fns stay in a per-instance
        # memo: sharing them would pin arbitrary closures (and whatever they
        # capture) for the plan-cache lifetime instead of the Integrator's.
        cache = getattr(self.plan, "_fm_cache", None)
        if cache is None:
            cache = BoundedLRU(64)
            self.plan._fm_cache = cache
        self._fm_cache = cache
        self._fm_cache_local = BoundedLRU(64)

    # (spec, params) derive lazily from the plan: construction stays pure
    # host-side bookkeeping, and the first integrate/fastmult call (which
    # pays a jit trace anyway) absorbs the one-time specialize + device
    # transfer. `specialize` memoizes on the plan object, so every property
    # access after the first is a tuple unpack.
    @property
    def spec(self):
        return plan_api.specialize(self.plan)[0]

    @property
    def params(self):
        return plan_api.specialize(self.plan)[1]

    @property
    def grid_h(self):
        return self.spec.grid_h

    def _pallas_opts(self) -> dict | None:
        """Kernel options for plan_api.select_cross (pallas subclass)."""
        return None

    def select_cross(self, spec: FamilySpec):
        """(engine_name, cross_multiply) for this f family."""
        return plan_api.select_cross(self.spec, spec, backend=self.name,
                                     degree=self.degree,
                                     pallas_opts=self._pallas_opts())

    def describe(self, fn) -> dict:
        name, _ = self.select_cross(spec_of(fn))
        d = {"backend": self.name, "cross_engine": name,
             "grid_h": self.grid_h}
        # match the host backend: every Forest-built integrator reports its
        # tree count (incl. single-tree forests); from_plan facades report
        # it whenever the spec covers more than one tree
        if self.forest is not None or self.spec.num_trees > 1:
            d["num_trees"] = self.spec.num_trees
        return d

    def integrate(self, fn, X):
        return self.fastmult(fn)(X)

    def _fm_opts_key(self) -> tuple:
        """Backend-specific options that must key the shared per-plan
        fastmult memo (subclasses with extra knobs override)."""
        return ()

    @staticmethod
    def _jit_ok(fn) -> bool:
        """Jit only f families whose fn_eval is built from concrete floats:
        AnyFn / raw callables may close over numpy-only code (or tracers from
        an enclosing jit), so they stay eager — which is still traceable
        inline by an outer jit."""
        from repro.core import cordial as C

        return (isinstance(fn, C.CordialFn)
                and not isinstance(fn, C.AnyFn)
                and type(fn) is not C.CordialFn)

    def _bind(self, fspec: FamilySpec) -> Callable:
        """X -> M_f X over this backend's own (spec, params): the closure
        form of ftfi.fastmult(spec, fn)(params, X)."""
        _, cross = self.select_cross(fspec)
        fe = fspec.fn_eval
        spec, params = self.spec, self.params

        def eager(X):
            return plan_api._execute(spec, params, fe, cross, X)

        return eager

    def fastmult(self, fn) -> Callable:
        """Cached, jit-compiled closure X -> M_f X (plan arrays are
        trace-time constants). Keyed semantically by (mode, coeffs, scale)
        for the structured families — equal f objects share one compiled
        executor — and by object identity for opaque callables. Opaque
        callables built inside an active jit trace (e.g. mask closures over
        traced coefficients) are NOT cached: pinning them would retain the
        trace's tracers, and their id can never produce a future hit."""
        spec = spec_of(fn)
        jit_ok = self._jit_ok(fn)
        if spec.mode is None and not _trace_state_clean():
            return _PlanFastMult(self._bind(spec), jit_compile=False)
        prefix = (self.name,) + self._fm_opts_key()
        if spec.mode is not None:  # semantic key: shared across instances
            cache = self._fm_cache
            key = prefix + (spec.mode, spec.coeffs, spec.scale, self.degree)
        else:  # id key: per instance, freed with this backend
            cache = self._fm_cache_local
            key = prefix + (None, id(fn), self.degree)
        hit = cache.get(key)
        if hit is not None:
            return hit[0]
        fm = _PlanFastMult(self._bind(spec), jit_compile=jit_ok)
        # pin `fn` alongside: id-based keys must not outlive their object
        cache.put(key, (fm, fn))
        return fm
