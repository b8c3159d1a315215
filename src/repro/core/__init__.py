"""FTFI core: the paper's contribution as composable JAX modules."""
from repro.core.cordial import (  # noqa: F401
    AnyFn, CordialFn, ExpPoly, ExpQuadratic, ExpRational, Exponential,
    Polynomial, Rational, Trigonometric,
)
from repro.core.integrate import BTFI, ExpMP, FTFI  # noqa: F401
from repro.core.itree_flat import (  # noqa: F401
    FlatIT, build_flat_forest, build_flat_it, clear_flat_cache, flat_stats,
    tree_fingerprint,
)
from repro.graphs.graph import Forest  # noqa: F401
from repro.core.plan_api import (  # noqa: F401
    PlanParams, PlanSpec, chebyshev_batched_matvec, clear_plan_cache,
    polynomial_batched_matvec,
)
from repro.core.plan_guard import (  # noqa: F401
    PlanGuardWarning, PlanValidationError,
)
from repro.core.ladder import (  # noqa: F401
    BackendDemotionWarning, LadderExhaustedError,
)
from repro.core.integrator_tree import build_integrator_tree, it_stats  # noqa: F401
from repro.core.toeplitz import (  # noqa: F401
    causal_toeplitz_matvec, symmetric_toeplitz_matvec, toeplitz_dense,
)
from repro.core import masks  # noqa: F401
