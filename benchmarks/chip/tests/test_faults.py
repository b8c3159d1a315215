"""A run whose timed path is broken underneath comes out not correct:
once for each fault the cell can have, at a small size, past the chip
check. One chip, so no exchange between chips is left out."""
import contextlib

import jax.numpy as jnp
import pytest

import calibrate
import harness
import small

MESH = ["mesh7-rational-pallas"]


@contextlib.contextmanager
def patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def answer_altered(apply):
    """Each integrate's answer off by one part in a thousand."""
    return lambda *a, **k: apply(*a, **k) * (1.0 + 1e-3)


def one_block_altered(apply):
    """The answer's first 64 rows, one leaf block's worth, off by one part
    in a thousand."""
    def f(*a, **k):
        y = apply(*a, **k)
        return y.at[:64].multiply(1.0 + 1e-3)
    return f


def half_the_field(apply):
    """Half of the source rows left out, the rest counted double."""
    def f(spec, params, fn, X, **k):
        h = X.shape[0] // 2
        return 2.0 * apply(spec, params, fn, X.at[h:].set(0.0), **k)
    return f


def state_unchanged(update):
    """The optimizer returns the weights and its state as they came."""
    return lambda grads, state, params, cfg: (params, state, {})


@pytest.mark.parametrize("workload", MESH)
def test_sound_run_is_correct(workload):
    out = small.run(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("workload", MESH)
@pytest.mark.parametrize("fault", [answer_altered, one_block_altered,
                                   half_the_field])
def test_mesh_fault_is_caught(workload, fault):
    from repro import ftfi

    with patched(ftfi, "apply", fault):
        out = small.run(workload)
    assert not out["correct"], out["checks"]


def test_train_sound_run_is_correct():
    out = small.run("topovit-b16-train")
    assert out["correct"], out["checks"]


def test_train_state_unchanged_is_caught():
    from repro.optim import adamw

    with patched(adamw, "adamw_update", state_unchanged):
        out = small.run("topovit-b16-train")
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch_is_caught():
    kind = harness.kind_of("vit_train")
    with calibrate.half_batch(kind):
        out = small.run("topovit-b16-train")
    assert not out["correct"], out["checks"]


def test_nonfinite_answers_are_failed_calls():
    from repro import ftfi

    with patched(ftfi, "apply",
                 lambda apply: lambda *a, **k: apply(*a, **k) * jnp.nan):
        out = small.run("mesh7-rational-pallas")
    assert not out["correct"]
    assert out["checks"]["nonfinite_calls"]["value"] == out["attempted"]
    assert out["failed"] == out["attempted"]


def test_a_metric_that_reads_nothing_fails_the_run(monkeypatch):
    """Where a metric the cell reports reads nothing, a strict run raises
    instead of leaving the metric out in silence."""
    import types

    reader_of = harness.reader_of

    def none_for_p95(name, *a, **k):
        if name == "integrate_p95_ms":
            return types.SimpleNamespace(read=lambda ctx: None)
        return reader_of(name, *a, **k)

    monkeypatch.setattr(harness, "reader_of", none_for_p95)
    with pytest.raises(harness.MissingMetric, match="integrate_p95_ms"):
        small.run("mesh7-rational-pallas", strict=True)
    out = small.run("mesh7-rational-pallas")
    assert "integrate_p95_ms" not in out["metrics"]
    assert "setup_s" in out["metrics"]
