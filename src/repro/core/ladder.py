"""Backend degradation ladder: supervised fallback `pallas -> plan -> host`.

The paper's headline is that FTFIs are *exact* — every backend computes the
same M_f X — which makes the slower backends free correctness fallbacks:
a Pallas kernel that fails to compile/launch, or returns non-finite
garbage, should demote to the next rung with a structured warning, never
tear down the request (or the whole continuous-batching tick) it was
serving.

Rungs, from fastest to most conservative:

  pallas   fused fdist_matvec kernel executor (interpret-mode off TPU)
  plan     the jitted XLA gather/segment-sum/scatter executor
  host     the SAME pure executor run eagerly under `jax.disable_jit()` —
           no Pallas, no XLA compilation, op-by-op on host: the terminal
           rung shares no failure domain with the compiled paths

Two failure classes trigger demotion:
  * any exception out of a rung (kernel compile/launch failure, jit
    compile error) — counted in `stats()['errors']`;
  * a non-finite output, caught by a cheap jit-compatible gate
    (`jnp.all(jnp.isfinite(Y))` fused into the rung's jitted closure, one
    scalar read on host) — counted in `stats()['nonfinite']`.

Demotion is sticky per closure (`ResilientFastMult`) so a broken rung is
not retried every call, and can be made global (`block_backend`) so
dispatch sites — `attention.resolve_topo_backend`, the ViT grid
integrator, serving — stop selecting a rung that already failed a probe.
The terminal rung never demotes: a non-finite output there is faithfully
returned with a warning (garbage input, not a backend fault).
"""
from __future__ import annotations

import os
import warnings
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.testing import faults

LADDER = ("pallas", "plan", "host")

# backend="auto" size threshold: below this vertex count the fused pallas
# kernel LOSES to the plan executor (BENCH_ftfi_runtime.json: speedup_int
# 0.88 at n=1000) — kernel launch + padding overheads dominate until the
# cross buckets are wide enough to feed it
AUTO_PALLAS_MIN_N = int(os.environ.get("FTFI_AUTO_PALLAS_MIN_N", "4000"))

_stats = {"demotions": 0, "errors": 0, "nonfinite": 0}
_blocked: dict[str, str] = {}


class BackendDemotionWarning(UserWarning):
    """A backend rung failed and the computation fell through to the next
    one. The message carries (from, to, reason)."""


class LadderExhaustedError(RuntimeError):
    """Every rung failed, including the eager host path."""


def stats() -> dict:
    return {**_stats, "blocked": dict(_blocked)}


def reset_stats() -> None:
    for k in _stats:
        _stats[k] = 0


def chain_from(backend: str) -> tuple:
    """The fallback chain starting at `backend` (host is always terminal)."""
    if backend not in LADDER:
        raise ValueError(f"unknown ladder backend {backend!r}; "
                         f"expected one of {LADDER}")
    return LADDER[LADDER.index(backend):]


def block_backend(name: str, reason: str) -> None:
    """Globally stop selecting rung `name` (e.g. after a failed probe):
    `effective_backend` and every new ladder closure skip it."""
    if name == "host":
        raise ValueError("the host rung is the terminal oracle and cannot "
                         "be blocked")
    if name not in _blocked:
        _blocked[name] = reason
        warnings.warn(f"backend {name!r} blocked for this process: {reason}",
                      BackendDemotionWarning, stacklevel=2)


def unblock_backends() -> None:
    _blocked.clear()


def effective_backend(backend: str, n: int | None = None) -> str:
    """First non-blocked rung at or below `backend` — what dispatch sites
    (topo attention, ViT grids, serving) should actually build with.

    `backend="auto"` resolves by problem size first: pallas at or above
    `AUTO_PALLAS_MIN_N` vertices, else plan (pass `n`; without it auto is
    conservative and picks plan). The resolved rung still rides the blocked
    chain like any explicit choice."""
    if backend == "auto":
        backend = ("pallas" if n is not None and n >= AUTO_PALLAS_MIN_N
                   else "plan")
    for level in chain_from(backend):
        if level not in _blocked:
            return level
    return "host"


def _demote(frm: str, to: str, reason: str, where: str) -> None:
    _stats["demotions"] += 1
    warnings.warn(
        f"{where}: backend {frm!r} demoted to {to!r}: {reason}",
        BackendDemotionWarning, stacklevel=3)


class ResilientFastMult:
    """(params, X) -> Y closure with the fallback chain baked in.

    Each rung's executor is built lazily: the structured f families are
    jitted with the finiteness gate fused in (one extra scalar output), the
    host rung runs the identical pure executor eagerly. Demotion is sticky:
    once rung i fails, calls start at rung i+1 (`reset()` re-arms the full
    chain; `demotions` records (from, to, reason) history)."""

    def __init__(self, spec, fn, *, backend: str = "pallas",
                 degree: int = 32, pallas_opts: dict | None = None,
                 name: str = "ftfi"):
        from repro.core import plan_api

        self._plan_api = plan_api
        self.spec = spec
        self.fn = fn
        self.degree = degree
        self.pallas_opts = pallas_opts
        self.name = name
        self.levels = tuple(
            l for l in chain_from(effective_backend(backend))
            if l == "host" or l not in _blocked)
        self._idx = 0
        self._runners: dict[str, Callable] = {}
        self.demotions: list[tuple] = []

    @property
    def level(self) -> str:
        return self.levels[self._idx]

    def reset(self) -> None:
        self._idx = 0

    def _jit_ok(self) -> bool:
        # only the structured concrete-float families are safe to jit from
        # here; everything else runs eagerly (still traceable inline by an
        # enclosing jit)
        from repro.core import cordial as C

        if isinstance(self.fn, C.FamilySpec):
            return self.fn.mode is not None
        return (isinstance(self.fn, C.CordialFn)
                and not isinstance(self.fn, C.AnyFn)
                and type(self.fn) is not C.CordialFn)

    def _runner(self, level: str) -> Callable:
        run = self._runners.get(level)
        if run is not None:
            return run
        if level == "host":
            def run(params, X):
                with jax.disable_jit():
                    Y = self._plan_api.apply(self.spec, params, self.fn, X,
                                             backend="plan",
                                             degree=self.degree)
                return Y, True
        else:
            fm = self._plan_api.fastmult(
                self.spec, self.fn, backend=level, degree=self.degree,
                pallas_opts=self.pallas_opts)

            def gated(params, X):
                Y = fm(params, X)
                # the jit-compatible NaN/Inf gate: fused into the compiled
                # step, costs one all-reduce + one scalar device->host read
                return Y, jnp.all(jnp.isfinite(Y))

            run = jax.jit(gated) if self._jit_ok() else gated
        self._runners[level] = run
        return run

    def __call__(self, params, X):
        last = len(self.levels) - 1
        while True:
            level = self.levels[self._idx]
            point = f"ladder.{level}"
            try:
                faults.fire(point)
                Y, ok = self._runner(level)(params, X)
                if faults.active(f"ladder.out.{level}"):
                    Y = faults.transform(f"ladder.out.{level}", Y)
                    ok = bool(np.isfinite(np.asarray(Y)).all())
                else:
                    ok = bool(ok)
            except Exception as e:
                _stats["errors"] += 1
                if self._idx >= last:
                    raise LadderExhaustedError(
                        f"{self.name}: every backend rung failed; terminal "
                        f"rung {level!r} raised {type(e).__name__}: {e}"
                    ) from e
                reason = f"{type(e).__name__}: {e}"
                self._record_demotion(level, reason)
                continue
            if ok:
                return Y
            _stats["nonfinite"] += 1
            if self._idx >= last:
                # the host rung IS the oracle: non-finite here means the
                # inputs are bad, which is the caller's (per-request
                # isolation) problem, not a backend fault
                warnings.warn(
                    f"{self.name}: non-finite output at the terminal host "
                    "rung — inputs are non-finite, returning as-is",
                    BackendDemotionWarning, stacklevel=2)
                return Y
            self._record_demotion(level, "non-finite output")

    def _record_demotion(self, frm: str, reason: str) -> None:
        self._idx += 1
        to = self.levels[self._idx]
        self.demotions.append((frm, to, reason))
        _demote(frm, to, reason, self.name)


def resilient_fastmult(spec, fn, *, backend: str = "pallas",
                       degree: int = 32, pallas_opts: dict | None = None,
                       name: str = "ftfi") -> ResilientFastMult:
    """The ladder-supervised twin of `ftfi.fastmult`: same (params, X) -> Y
    signature, but kernel failures and non-finite outputs demote down the
    chain instead of propagating."""
    return ResilientFastMult(spec, fn, backend=backend, degree=degree,
                             pallas_opts=pallas_opts, name=name)


def apply_resilient(spec, params, fn, X, *, backend: str = "pallas",
                    degree: int = 32, pallas_opts: dict | None = None):
    """One-shot `ftfi.apply` under ladder supervision (fresh chain per
    call; use `resilient_fastmult` to keep demotions sticky)."""
    return ResilientFastMult(spec, fn, backend=backend, degree=degree,
                             pallas_opts=pallas_opts)(params, X)


def probe_backend(spec, params, backend: str, *, fn=None) -> str | None:
    """Try one tiny integrate on `backend`; return None when healthy, else
    the failure reason. Dispatch sites use this at build time to demote
    BEFORE a broken rung reaches live traffic."""
    from repro.core import cordial as C

    fn = fn if fn is not None else C.Exponential(-1.0)
    X = np.zeros((spec.n, 1), np.float32)
    X[0, 0] = 1.0
    try:
        faults.fire(f"ladder.{backend}")
        from repro.core import plan_api

        Y = plan_api.apply(spec, params, fn, X, backend=backend)
        Y = faults.transform(f"ladder.out.{backend}", Y)
        if not np.isfinite(np.asarray(Y)).all():
            return "non-finite probe output"
    except Exception as e:
        return f"{type(e).__name__}: {e}"
    return None
