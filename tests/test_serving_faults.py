"""Serving robustness fault matrix: plan-artifact validation (plan guard),
backend degradation ladder, and ServeEngine fault isolation.

Every fault class injected through `repro.testing.faults` must either
recover (retry/re-queue reproduces the exact greedy output — decode is
deterministic) or degrade to the host-exact result (all FTFI backends
compute the same M_f X, so lower rungs are free correctness oracles).
Nothing here may escape as an unhandled exception.
"""
import warnings

import numpy as np
import pytest
import jax

from repro import ftfi
from repro.configs.base import get_smoke_config
from repro.core import cordial as C
from repro.core import ladder, plan_cache, plan_guard
from repro.core.ladder import BackendDemotionWarning, LadderExhaustedError
from repro.core.plan_guard import PlanGuardWarning, PlanValidationError
from repro.core import clear_flat_cache, clear_plan_cache
from repro.graphs.graph import random_tree
from repro.models import api
from repro.serve.engine import Request, ServeEngine
from repro.testing import faults


@pytest.fixture(autouse=True)
def _clean_robustness_state():
    """Faults disarmed, ladder unblocked, guard policy strict, per test."""
    faults.clear()
    ladder.unblock_backends()
    old = plan_guard.policy()
    plan_guard.set_policy("strict")
    try:
        yield
    finally:
        faults.clear()
        ladder.unblock_backends()
        plan_guard.set_policy(old)


@pytest.fixture(scope="module")
def plan_pair():
    return ftfi.build(random_tree(60, seed=7), leaf_size=8)


def _rel_err(got, ref):
    got, ref = np.asarray(got), np.asarray(ref)
    return float(np.max(np.abs(got - ref))
                 / max(np.max(np.abs(ref)), 1e-12))


# ----------------------------------------------------------------------------
# plan guard: artifact validation
# ----------------------------------------------------------------------------


def test_guard_accepts_healthy_plan(plan_pair):
    spec, params = plan_pair
    assert plan_guard.check_spec(spec, params) == []
    assert ftfi.validate(spec, params) is True


@pytest.mark.parametrize("field", ["src_gather", "tgt_scatter", "pivots",
                                   "src_seg", "tgt_gather"])
def test_guard_catches_flipped_index(plan_pair, field):
    spec, params = plan_pair
    bad = faults.flip_index(spec, field=field)
    with pytest.raises(PlanValidationError, match=field):
        ftfi.validate(bad, params)


def test_guard_catches_nan_params(plan_pair):
    spec, params = plan_pair
    import dataclasses

    dists = list(params.cross_src_d)
    d0 = np.array(dists[0], copy=True)
    d0.reshape(-1)[0] = np.nan
    dists[0] = d0
    bad = dataclasses.replace(params, cross_src_d=tuple(dists))
    with pytest.raises(PlanValidationError, match="finite"):
        ftfi.validate(spec, bad)


def test_guard_warn_policy_rejects_without_raising(plan_pair):
    spec, params = plan_pair
    bad = faults.flip_index(spec, field="src_gather")
    before = plan_guard.stats()
    with pytest.warns(PlanGuardWarning):
        ok = plan_guard.validate(bad, params, policy_override="warn")
    assert ok is False
    after = plan_guard.stats()
    assert after["failures"] == before["failures"] + 1
    assert after["warned"] == before["warned"] + 1


def test_guard_off_policy_skips(plan_pair):
    spec, params = plan_pair
    bad = faults.flip_index(spec, field="src_gather")
    assert plan_guard.validate(bad, params, policy_override="off") is True


# ----------------------------------------------------------------------------
# load_plan on damaged artifacts (satellite: truncated / bit-flipped npz)
# ----------------------------------------------------------------------------


def test_load_plan_truncated_artifact(tmp_path, plan_pair):
    spec, params = plan_pair
    p = tmp_path / "plan.npz"
    ftfi.save_plan(p, spec, params)
    faults.corrupt_file(p, truncate_to=p.stat().st_size // 2)
    with pytest.raises(PlanValidationError, match="corrupt or truncated"):
        ftfi.load_plan(p)


def test_load_plan_bitflipped_artifact(tmp_path, plan_pair):
    spec, params = plan_pair
    p = tmp_path / "plan.npz"
    ftfi.save_plan(p, spec, params)
    faults.corrupt_file(p, flip_bytes=64, seed=11)
    # either the parse fails (wrapped) or the semantic validation trips —
    # both surface as PlanValidationError, never bad indices to the executor
    with pytest.raises(PlanValidationError):
        ftfi.load_plan(p)


def test_load_plan_roundtrip_still_validates(tmp_path, plan_pair):
    spec, params = plan_pair
    p = tmp_path / "plan.npz"
    ftfi.save_plan(p, spec, params)
    spec2, params2 = ftfi.load_plan(p)  # validate=True default
    X = np.random.default_rng(0).normal(size=(spec.n, 2)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(ftfi.apply(spec, params, C.Exponential(-0.5), X)),
        np.asarray(ftfi.apply(spec2, params2, C.Exponential(-0.5), X)))


def test_update_plan_output_is_validated():
    spec, params = ftfi.build(random_tree(40, seed=9), leaf_size=8,
                              reweightable=True)
    before = plan_guard.stats()["validations"]
    spec2, params2 = ftfi.update_plan(spec, params, [("insert_leaf", 0, 0.5)])
    assert plan_guard.stats()["validations"] == before + 1
    assert plan_guard.check_spec(spec2, params2) == []


def test_install_grid_plan_guards_artifact_pairs():
    """Both doors a loaded pair comes in by run the plan guard:
    `ftfi.validate` and the ViT grid's `install_grid_plan`."""
    from repro.models import vit

    spec, params = ftfi.build(vit.grid_mst(8), leaf_size=16)
    ftfi.validate(spec, params)  # healthy pair passes
    assert vit.install_grid_plan(spec, params) == 8
    bad = faults.flip_index(spec)
    with pytest.raises(PlanValidationError):
        ftfi.validate(bad, params)
    with pytest.raises(PlanValidationError):
        vit.install_grid_plan(bad, params)


def test_disk_cache_hit_validates_and_rejects_corruption(tmp_path):
    plan_cache.configure(tmp_path / "plans", max_mb=64)
    clear_flat_cache()
    clear_plan_cache()
    try:
        tree = random_tree(200, seed=5)
        spec1, pp1 = ftfi.build(tree, leaf_size=16)
        [artifact] = list((tmp_path / "plans").glob("ftfi-plan-*.npz"))
        faults.corrupt_file(artifact, flip_bytes=48, seed=3)

        clear_flat_cache()
        clear_plan_cache()
        before = plan_cache.stats()
        spec2, pp2 = ftfi.build(tree, leaf_size=16)  # corrupt hit -> rebuild
        after = plan_cache.stats()
        assert after["hits"] == before["hits"]
        assert after["misses"] == before["misses"] + 1
        assert after["errors"] == before["errors"] + 1
        assert not artifact.exists() or after["stores"] > before["stores"]
        # rebuilt plan is the real one
        X = np.random.default_rng(1).normal(size=(200, 2)).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(ftfi.apply(spec1, pp1, C.Exponential(-0.4), X)),
            np.asarray(ftfi.apply(spec2, pp2, C.Exponential(-0.4), X)))
    finally:
        plan_cache.reset_to_env()
        clear_flat_cache()
        clear_plan_cache()


def test_cache_max_mb_env_parse_is_defensive(monkeypatch, tmp_path):
    monkeypatch.setenv("FTFI_PLAN_CACHE_MAX_MB", "not-a-number")
    plan_cache.configure(tmp_path / "p")
    try:
        with pytest.warns(UserWarning, match="FTFI_PLAN_CACHE_MAX_MB"):
            assert plan_cache.stats()["max_bytes"] == int(512e6)
    finally:
        plan_cache.reset_to_env()


# ----------------------------------------------------------------------------
# degradation ladder
# ----------------------------------------------------------------------------


def test_ladder_kernel_raise_demotes_with_parity(plan_pair):
    spec, params = plan_pair
    fn = C.Exponential(-0.5)
    X = np.random.default_rng(2).normal(size=(spec.n, 3)).astype(np.float32)
    ref = np.asarray(ftfi.apply(spec, params, fn, X, backend="plan"))
    ladder.reset_stats()
    with faults.injected("ladder.pallas", faults.always_raise(
            RuntimeError, "kernel launch failed")):
        with pytest.warns(BackendDemotionWarning, match="pallas.*plan"):
            Y = ftfi.apply_resilient(spec, params, fn, X, backend="pallas")
    assert _rel_err(Y, ref) <= 1e-5
    st = ladder.stats()
    assert st["errors"] == 1 and st["demotions"] == 1


def test_ladder_nan_output_reaches_host_exact(plan_pair):
    spec, params = plan_pair
    fn = C.Exponential(-0.5)
    X = np.random.default_rng(3).normal(size=(spec.n, 2)).astype(np.float32)
    ref = np.asarray(ftfi.apply(spec, params, fn, X, backend="plan"))
    with faults.injected("ladder.pallas", faults.always_raise()), \
            faults.injected("ladder.out.plan", faults.nan_output()), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore", BackendDemotionWarning)
        Y = ftfi.apply_resilient(spec, params, fn, X, backend="pallas")
    assert _rel_err(Y, ref) <= 1e-5  # host rung result, exact


def test_ladder_demotion_is_sticky(plan_pair):
    spec, params = plan_pair
    fm = ftfi.resilient_fastmult(spec, C.Exponential(-0.5), backend="pallas")
    X = np.random.default_rng(4).normal(size=(spec.n, 2)).astype(np.float32)
    ladder.reset_stats()
    with faults.injected("ladder.pallas", faults.always_raise()), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore", BackendDemotionWarning)
        fm(params, X)
        fm(params, X)  # second call starts at "plan": no second error
    assert ladder.stats()["errors"] == 1
    assert fm.level == "plan"
    assert fm.demotions == [("pallas", "plan",
                             "RuntimeError: injected fault")]


def test_ladder_exhaustion_is_structured(plan_pair):
    spec, params = plan_pair
    X = np.zeros((spec.n, 1), np.float32)
    with faults.injected("ladder.pallas", faults.always_raise()), \
            faults.injected("ladder.plan", faults.always_raise()), \
            faults.injected("ladder.host", faults.always_raise()), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore", BackendDemotionWarning)
        with pytest.raises(LadderExhaustedError, match="every backend rung"):
            ftfi.apply_resilient(spec, params, C.Exponential(-0.5), X,
                                 backend="pallas")


def test_block_backend_steers_dispatch():
    from repro.models import attention as A

    cfg = get_smoke_config("qwen2_1_5b").replace(topo_backend="pallas")
    assert A.resolve_topo_backend(cfg) == "pallas"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", BackendDemotionWarning)
        ladder.block_backend("pallas", "probe failed (test)")
    assert ladder.effective_backend("pallas") == "plan"
    assert A.resolve_topo_backend(cfg) == "plan"
    with pytest.raises(ValueError, match="terminal"):
        ladder.block_backend("host", "nope")
    ladder.unblock_backends()
    assert A.resolve_topo_backend(cfg) == "pallas"


def test_probe_backend_reports_failure(plan_pair):
    spec, params = plan_pair
    assert ladder.probe_backend(spec, params, "plan") is None
    with faults.injected("ladder.pallas", faults.always_raise(
            RuntimeError, "no TPU")):
        reason = ladder.probe_backend(spec, params, "pallas")
    assert reason is not None and "no TPU" in reason
    with faults.injected("ladder.out.plan", faults.nan_output()):
        assert "non-finite" in ladder.probe_backend(spec, params, "plan")


# ----------------------------------------------------------------------------
# ServeEngine isolation (fault matrix rows: slot crash, step crash, retry
# exhaustion, deadlines) + the fresh-wave admission regression
# ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_setup():
    cfg = get_smoke_config("qwen2_1_5b").replace(dtype="float32")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
               for n in (3, 7, 5)]
    # single-slot reference outputs (greedy decode is deterministic)
    refs = []
    for p in prompts:
        eng = ServeEngine(cfg, params, batch_slots=1, max_len=64)
        r = Request(rid=0, prompt=p, max_new_tokens=4)
        eng.submit(r)
        eng.run()
        refs.append(list(r.out))
    return cfg, params, prompts, refs


def test_mixed_length_waves_match_reference(serve_setup):
    """Batch-size independence: three mixed-length prompts through 2 slots
    (the third admits MID-WAVE into whichever slot frees first — legal under
    fused prefill + per-slot decode positions) must reproduce their
    single-slot outputs exactly."""
    cfg, params, prompts, refs = serve_setup
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=64)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, ref in zip(reqs, refs):
        assert r.done and r.error is None
        assert r.out == ref
    assert eng.stats()["completed"] == 3


def test_slot_fault_retries_only_that_request(serve_setup):
    cfg, params, prompts, refs = serve_setup
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=64)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts[:2])]
    for r in reqs:
        eng.submit(r)
    with faults.injected("serve.logits", faults.nan_slot_at_tick(slot=1, k=2)):
        eng.run()
    st = eng.stats()
    assert all(r.done and r.error is None for r in reqs)
    assert reqs[0].retries == 0 and reqs[1].retries == 1
    assert reqs[0].out == refs[0]
    assert reqs[1].out == refs[1]  # replayed bit-identically
    assert st["slot_faults"] == 1 and st["evictions"] == 1
    assert st["retries"] == 1 and st["failed"] == 0


def test_step_crash_requeues_wave_engine_survives(serve_setup):
    cfg, params, prompts, refs = serve_setup
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=64)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts[:2])]
    for r in reqs:
        eng.submit(r)
    with faults.injected("serve.step", faults.raise_at_tick(3)):
        eng.run()
    st = eng.stats()
    assert all(r.done and r.error is None for r in reqs)
    for r, ref in zip(reqs, refs):
        assert r.out == ref
    assert st["step_failures"] == 1 and st["evictions"] == 2
    assert st["failed"] == 0


def test_retry_budget_exhaustion_fails_request_not_engine(serve_setup):
    cfg, params, prompts, refs = serve_setup
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=64, max_retries=1)
    doomed = Request(rid=0, prompt=prompts[0], max_new_tokens=4)
    eng.submit(doomed)
    with faults.injected("serve.logits", faults.nan_output()):
        eng.run()
    assert doomed.done and doomed.error is not None
    assert "retries" in doomed.error
    assert eng.stats()["failed"] == 1
    # the engine is still serviceable after exhausting a request
    healthy = Request(rid=1, prompt=prompts[1], max_new_tokens=4)
    eng.submit(healthy)
    eng.run()
    assert healthy.done and healthy.error is None
    assert healthy.out == refs[1]


def test_deadline_expires_queued_request(serve_setup):
    cfg, params, prompts, refs = serve_setup
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=64)
    a = Request(rid=0, prompt=prompts[0], max_new_tokens=4)
    b = Request(rid=1, prompt=prompts[1], max_new_tokens=4, deadline_ticks=2)
    eng.submit(a)
    eng.submit(b)  # stuck behind a's wave, expires in queue
    eng.run()
    assert a.done and a.error is None and a.out == refs[0]
    assert b.done and b.error is not None and "deadline" in b.error
    assert eng.stats()["deadline_expired"] == 1


def test_engine_rejects_corrupt_preloaded_plan(serve_setup, plan_pair):
    cfg, params, _, _ = serve_setup
    spec, pp = plan_pair
    bad = faults.flip_index(spec, field="src_gather")
    with pytest.raises(PlanValidationError):
        ServeEngine(cfg, params, batch_slots=1, max_len=32, plan=(bad, pp))


def test_health_banner_mentions_counters(serve_setup):
    cfg, params, prompts, _ = serve_setup
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=64)
    r = Request(rid=0, prompt=prompts[0], max_new_tokens=4)
    eng.submit(r)
    eng.run()
    line = eng.health_banner()
    assert "done=1" in line and "retries=" in line and "demotions=" in line
