"""Retrace sentinel: compile-count accounting for the shared jit closures.

The repo's hot paths are all served from memoized jit closures — the
`plan_api.fastmult` memos on the backends, the `masks.make_tree_fastmult`
LRU, the serve engine's decode/prefill buckets.  A cache-key bug (or an
unhashable static arg, or a python scalar that should have been an array)
turns any of them into a silent retrace-per-call, which never fails a
correctness test but destroys serving latency.

This module is the cheap tripwire.  Instrumented sites call
:func:`record` from *inside* the traced body, so the counter bumps exactly
once per trace (jax executes the python body only when it compiles).
Cache layers call :func:`record` with an ``event=`` tag for hit/miss
accounting.  Tests and the CLI then wrap a workload in
:func:`expect_stable` (fail on any retrace of a declared-stable site) or
diff :func:`stats` against the ``trace_guard`` section of
``ANALYSIS_BUDGETS.json`` via :func:`check`.

Host phases (the plan build's) are timed with :func:`span`: a
``jax.profiler.TraceAnnotation`` of the same name, so the phase sits on
the device trace's clock whenever a profiler runs, plus its host-clock
seconds and a count here, read through :func:`seconds` and :func:`stats`.

Pure stdlib at import — core modules import this at module scope without
pulling in jax (`span` imports the profiler when it first opens), so
instrumentation adds zero import cost and only trace-time runtime cost
(i.e. none on the cached path).
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager

__all__ = [
    "RetraceError", "record", "compiles", "stats", "reset",
    "declare_stable", "expect_stable", "check", "snapshot", "span",
    "seconds",
]


class RetraceError(AssertionError):
    """A declared-stable entry point retraced."""


_lock = threading.Lock()
_counts: dict[str, int] = {}          # site -> total records
_by_key: dict[tuple[str, str], int] = {}  # (site, detail) -> records
_stable: dict[str, int] = {}          # site -> max allowed compiles
_spans: dict[str, list] = {}          # span name -> [count, host seconds]


def record(site: str, detail: str = "", event: str = "compile") -> None:
    """Record one compile (or cache event) at ``site``.

    Call this from inside a jitted function body: jax only runs the python
    body while tracing, so the count equals the number of compiles.  For
    cache layers, pass ``event="hit"``/``event="miss"`` — those are
    accounted under ``site:hit`` / ``site:miss`` and never trip stability
    checks on ``site`` itself.
    """
    key = site if event == "compile" else f"{site}:{event}"
    with _lock:
        _counts[key] = _counts.get(key, 0) + 1
        if detail:
            _by_key[(key, detail)] = _by_key.get((key, detail), 0) + 1


def compiles(site: str) -> int:
    with _lock:
        return _counts.get(site, 0)


@contextmanager
def span(name: str):
    """Time the block as the host span ``name``: a
    ``jax.profiler.TraceAnnotation`` (on the trace's clock when a profiler
    runs), and its host-clock seconds and one count added under ``name``.
    Spans nest; each adds its own whole duration."""
    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name):
            yield
    finally:
        dt = time.perf_counter() - t0
        with _lock:
            c = _spans.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += dt


def seconds(name: str) -> float | None:
    """Host seconds summed over every closed :func:`span` of ``name``, or
    None where none closed."""
    with _lock:
        c = _spans.get(name)
        return None if c is None else c[1]


def stats() -> dict:
    """Snapshot of all counters: {"sites": {site: n}, "keys": {...},
    "spans": {name: {"count": n, "seconds": s}}}."""
    with _lock:
        keys = {f"{s} [{d}]": n for (s, d), n in sorted(_by_key.items())}
        spans = {k: {"count": c[0], "seconds": c[1]}
                 for k, c in sorted(_spans.items())}
        return {"sites": dict(sorted(_counts.items())), "keys": keys,
                "spans": spans}


def snapshot() -> dict[str, int]:
    with _lock:
        return dict(_counts)


def reset() -> None:
    with _lock:
        _counts.clear()
        _by_key.clear()
        _stable.clear()
        _spans.clear()


def declare_stable(site: str, max_compiles: int = 1) -> None:
    """Declare that ``site`` may compile at most ``max_compiles`` times
    (checked by :func:`check`)."""
    with _lock:
        _stable[site] = int(max_compiles)


@contextmanager
def expect_stable(*sites: str, max_compiles: int = 0):
    """Fail with :class:`RetraceError` if any of ``sites`` compiles more
    than ``max_compiles`` times inside the block.

    ``max_compiles=0`` is the steady-state assertion: the closure was
    already traced, re-running the workload must be pure cache hits.
    """
    before = snapshot()
    yield
    after = snapshot()
    bad = []
    for s in sites:
        delta = after.get(s, 0) - before.get(s, 0)
        if delta > max_compiles:
            bad.append(f"{s}: {delta} compiles (budget {max_compiles})")
    if bad:
        raise RetraceError(
            "retrace budget exceeded: " + "; ".join(bad))


def check(budgets: dict[str, int] | None = None) -> list[str]:
    """Diff recorded compile counts against per-site budgets.

    ``budgets`` maps site -> max compiles; sites previously registered via
    :func:`declare_stable` are merged in.  Returns a list of violation
    strings (empty = clean).
    """
    with _lock:
        merged = dict(_stable)
        counts = dict(_counts)
    if budgets:
        merged.update({k: int(v) for k, v in budgets.items()})
    issues = []
    for site, limit in sorted(merged.items()):
        n = counts.get(site, 0)
        if n > limit:
            issues.append(
                f"trace_guard: {site} compiled {n}x (budget {limit})")
    return issues
