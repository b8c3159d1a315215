"""The plan API: the one way to build and run a tree-field integrator.

A plan is a (PlanSpec, PlanParams) pair (`repro.core.plan_api`): the spec
holds the static index arrays, bucket layout and provenance, the params the
distances and per-tree output weights as jnp arrays.

    from repro import ftfi

    spec, params = ftfi.build(tree)                  # or ftfi.build(Forest)
    Y = ftfi.apply(spec, params, Exponential(-0.5), X)
    Y = ftfi.apply(spec, params, fn, X, backend="pallas")  # fdist_matvec
    fm = jax.jit(ftfi.fastmult(spec, fn))            # (params, X) -> Y
    ftfi.describe(spec, fn, "pallas")["cross_engine"]  # e.g. fdist_matvec:exp
    ftfi.save_plan("plan.npz", spec, params)
    spec, params = ftfi.load_plan("plan.npz")        # zero IT rebuild

    # learnable tree metrics
    spec, params = ftfi.build(tree, reweightable=True)
    params = ftfi.reweight(spec, edge_w)             # differentiable in edge_w

    # incremental edits: patch a compiled plan instead of rebuilding
    spec, params = ftfi.update_plan(spec, params, [
        ("insert_leaf", parent, w),   # new leaf under `parent`
        ("delete_leaf", v),           # degree-1 vertex -> zeroed ghost row
        ("reweight", edge_w),         # replace all edge weights
    ])

    # disk-persistent plan cache: set FTFI_PLAN_CACHE=/path (or call
    # ftfi.plan_cache.configure(path)) and every build over a known
    # topology becomes one npz read; LRU-evicted past
    # FTFI_PLAN_CACHE_MAX_MB (default 512)

    # robustness layer (see README "Failure modes and the degradation
    # ladder"): artifacts are validated on load/cache-hit/update under the
    # FTFI_PLAN_GUARD policy (strict|warn|off), and the resilient entry
    # points demote pallas -> plan -> host (the same executor run eagerly)
    # on kernel failure or non-finite output instead of crashing
    ftfi.validate(spec, params)                      # PlanValidationError
    Y = ftfi.apply_resilient(spec, params, fn, X, backend="pallas")
    fm = ftfi.resilient_fastmult(spec, fn)           # sticky demotions

    # multi-device execution (see README "Multi-device execution"): the
    # plan's index space is cut into per-device leaf blocks and run under
    # shard_map — one all_to_all moves the halo rows, one psum_scatter
    # reduces the partial outputs; exact (1e-6 parity vs single device)
    with launch.sharding.use_sharding(mesh):         # or pass mesh=...
        Y = ftfi.apply_sharded(spec, params, fn, X)
        fm = jax.jit(ftfi.sharded_fastmult(spec, fn, mesh=mesh))
    ftfi.shard_stats(spec, num_shards)               # block/halo/work stats

The numpy references the tests hold every path to run without a plan:
`repro.core.integrate.BTFI` (the dense oracle), `FTFI` (the recursive
integrator) and `ExpMP` (two-pass message passing for exp f).
"""
from repro.core import ladder, plan_cache, plan_guard  # noqa: F401
from repro.core.ladder import (  # noqa: F401
    BackendDemotionWarning, apply_resilient, resilient_fastmult)
from repro.core.plan_api import (  # noqa: F401
    KERNEL_MODES, PlanParams, PlanSpec, apply, build, describe, fastmult,
    load_plan, reweight, save_plan, update_plan)
from repro.core.plan_guard import PlanValidationError, validate  # noqa: F401
from repro.core.plan_shard import (  # noqa: F401
    SHARD_LAYOUT_VERSION, apply_sharded, partition_plan, shard_stats,
    sharded_fastmult)
