"""Degrade-gracefully shim for `hypothesis`.

When hypothesis is installed (see requirements-dev.txt) this module just
re-exports it. In minimal environments the property tests still collect and
run against a deterministic set of representative examples: the boundary
values of every strategy plus a few seeded random draws. That keeps tier-1
green without the dependency while preserving the property-test shape.

Usage in tests:  from _hypothesis_compat import example, given, settings, st
"""
from __future__ import annotations

try:  # pragma: no cover - exercised implicitly by either branch
    from hypothesis import example, given, settings  # noqa: F401
    from hypothesis import strategies as st  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:
    import inspect
    import random

    HAVE_HYPOTHESIS = False
    _N_RANDOM_EXAMPLES = 5

    class _Strategy:
        """Minimal stand-in: boundary examples + seeded random draws."""

        def __init__(self, boundaries, sampler):
            self.boundaries = list(boundaries)
            self.sampler = sampler

        def examples(self, rng):
            out = list(self.boundaries)
            out += [self.sampler(rng) for _ in range(_N_RANDOM_EXAMPLES)]
            return out

    class st:  # noqa: N801 - mirrors `hypothesis.strategies` usage
        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(
                [min_value, max_value],
                lambda rng: rng.randint(min_value, max_value))

        @staticmethod
        def floats(min_value, max_value):
            return _Strategy(
                [min_value, max_value],
                lambda rng: rng.uniform(min_value, max_value))

        @staticmethod
        def booleans():
            return _Strategy([False, True], lambda rng: rng.random() < 0.5)

    def settings(*_a, **_kw):  # accepts max_examples=, deadline=, ...
        return lambda f: f

    def example(**drawn):
        """A pinned example, run before the drawn ones."""
        def deco(f):
            f._examples = [drawn] + list(getattr(f, "_examples", []))
            return f

        return deco

    def given(**strategies):
        names = sorted(strategies)

        def deco(f):
            def wrapper(*args, **kwargs):
                for drawn in getattr(f, "_examples", []):
                    f(*args, **drawn, **kwargs)
                rng = random.Random(0)
                cols = {k: strategies[k].examples(rng) for k in names}
                rounds = max(len(v) for v in cols.values())
                for i in range(rounds):
                    drawn = {k: cols[k][i % len(cols[k])] for k in names}
                    f(*args, **drawn, **kwargs)

            # expose only the non-strategy params (pytest fixtures) so pytest
            # does not try to inject the drawn arguments as fixtures
            sig = inspect.signature(f)
            remaining = [p for n, p in sig.parameters.items()
                         if n not in strategies]
            wrapper.__signature__ = sig.replace(parameters=remaining)
            wrapper.__name__ = f.__name__
            wrapper.__doc__ = f.__doc__
            return wrapper

        return deco
