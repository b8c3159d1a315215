"""Disk-persistent plan cache: round trips, corruption tolerance, eviction.

All tests configure the cache programmatically onto a tmp_path and restore
the environment-driven configuration afterwards; the module-level stats
counters are cumulative, so assertions diff them around each operation.
"""
import numpy as np
import pytest

from repro import ftfi
from repro.core import clear_flat_cache, clear_plan_cache, plan_cache
from repro.core import cordial as C
from repro.core.plan_api import load_plan, save_plan
from repro.graphs.graph import random_tree


@pytest.fixture
def cache_dir(tmp_path):
    d = tmp_path / "plans"
    plan_cache.configure(d, max_mb=64)
    clear_flat_cache()
    clear_plan_cache()
    try:
        yield d
    finally:
        plan_cache.reset_to_env()
        clear_flat_cache()
        clear_plan_cache()


def _delta(fn):
    """Run fn, return (result, stats-counter deltas)."""
    before = plan_cache.stats()
    out = fn()
    after = plan_cache.stats()
    keys = ("hits", "misses", "stores", "evictions", "errors")
    return out, {k: after[k] - before[k] for k in keys}


def test_build_stores_then_cold_process_rebuild_hits(cache_dir):
    tree = random_tree(300, seed=0)
    (spec1, pp1), d = _delta(
        lambda: ftfi.build(tree, leaf_size=16, reweightable=True))
    assert d["stores"] == 1 and d["hits"] == 0
    assert plan_cache.stats()["entries"] == 1

    # simulate a fresh process: memory caches gone, disk cache populated
    clear_flat_cache()
    clear_plan_cache()
    (loaded, d) = _delta(
        lambda: ftfi.build(tree, leaf_size=16, reweightable=True))
    spec2, pp2 = loaded
    assert d["hits"] == 1 and d["stores"] == 0
    assert spec2.digest == spec1.digest
    assert spec2.fingerprint == spec1.fingerprint

    # parity through the executor
    rng = np.random.default_rng(1)
    X = rng.normal(size=(300, 3)).astype(np.float32)
    fn = C.Exponential(-0.5)
    np.testing.assert_array_equal(
        np.asarray(ftfi.apply(spec1, pp1, fn, X)),
        np.asarray(ftfi.apply(spec2, pp2, fn, X)))


def test_distinct_compile_keys_get_distinct_artifacts(cache_dir):
    tree = random_tree(120, seed=3)
    ftfi.build(tree, leaf_size=8)
    ftfi.build(tree, leaf_size=16)               # different leaf_size
    ftfi.build(tree, leaf_size=8, reweightable=True)  # different tables
    assert plan_cache.stats()["entries"] == 3


def test_corrupt_artifact_is_deleted_and_rebuilt(cache_dir):
    tree = random_tree(200, seed=5)
    spec1, _ = ftfi.build(tree, leaf_size=16)
    [artifact] = list(cache_dir.glob("ftfi-plan-*.npz"))
    artifact.write_bytes(b"this is not an npz")

    clear_flat_cache()
    clear_plan_cache()
    (rebuilt, d) = _delta(lambda: ftfi.build(tree, leaf_size=16))
    spec2, _ = rebuilt
    # torn artifact -> counted error, treated as miss, deleted, re-stored
    assert d["errors"] == 1 and d["hits"] == 0
    assert d["misses"] >= 1 and d["stores"] == 1
    assert spec2.digest == spec1.digest


def test_lru_eviction_under_tiny_budget(cache_dir):
    plan_cache.configure(cache_dir, max_mb=0.05)  # ~50 KB: a couple plans
    _, d = _delta(lambda: [ftfi.build(random_tree(150, seed=s), leaf_size=8)
                           for s in range(6)])
    assert d["stores"] == 6
    st = plan_cache.stats()
    assert d["evictions"] >= 1
    assert st["bytes"] <= st["max_bytes"]
    assert 0 < st["entries"] < 6


def test_clear_and_disable(cache_dir):
    tree = random_tree(100, seed=7)
    ftfi.build(tree, leaf_size=8)
    assert plan_cache.stats()["entries"] == 1
    plan_cache.clear()
    assert plan_cache.stats()["entries"] == 0

    plan_cache.configure(None)
    assert not plan_cache.enabled()
    clear_flat_cache()
    clear_plan_cache()
    _, d = _delta(lambda: ftfi.build(tree, leaf_size=8))
    # disabled: no disk traffic at all
    assert d == {"hits": 0, "misses": 0, "stores": 0, "evictions": 0,
                 "errors": 0}


@pytest.mark.parametrize("reweightable", [False, True])
def test_save_load_round_trip_update_tables(tmp_path, reweightable):
    """save_plan/load_plan must round-trip the reweight/update tables when
    present and reconstruct None fields when absent (non-reweightable)."""
    tree = random_tree(90, seed=11)
    spec, pp = ftfi.build(tree, leaf_size=8, reweightable=reweightable)
    path = tmp_path / "plan.npz"
    save_plan(path, spec, pp)
    spec2, pp2 = load_plan(path)
    assert spec2.digest == spec.digest
    assert (spec2.edges_u is None) == (spec.edges_u is None)
    assert (spec2.edge_w0 is None) == (spec.edge_w0 is None)
    if reweightable:
        # ...and the loaded plan is actually updatable
        s3, p3 = ftfi.update_plan(spec2, pp2, [("insert_leaf", 4, 0.9)])
        assert s3.n == spec.n + 1
    else:
        with pytest.raises(ValueError, match="reweightable"):
            ftfi.update_plan(spec2, pp2, [("insert_leaf", 4, 0.9)])


def test_disk_round_trip_hands_back_the_loaded_pair(cache_dir, monkeypatch):
    """build -> save_plan -> load_plan -> apply through the disk cache, with
    no conversion step: a cold build returns exactly the pair `load_plan`
    read (same objects), never touches the IT builder, and applies
    bitwise like the original build."""
    import repro.core.itree_flat as itree_flat

    tree = random_tree(150, seed=8)
    spec1, pp1 = ftfi.build(tree, leaf_size=16)
    clear_flat_cache()
    clear_plan_cache()
    loaded = []
    real_load = plan_cache.load

    def spy(keyhex):
        hit = real_load(keyhex)
        loaded.append(hit)
        return hit

    def boom(*args, **kwargs):
        raise AssertionError("a disk-cache hit rebuilt the IT")

    monkeypatch.setattr(plan_cache, "load", spy)
    monkeypatch.setattr(itree_flat, "_build", boom)
    spec2, pp2 = ftfi.build(tree, leaf_size=16)
    [(lspec, lparams)] = loaded
    assert spec2 is lspec and pp2 is lparams
    assert spec2.digest == spec1.digest
    X = np.random.default_rng(2).normal(size=(150, 3)).astype(np.float32)
    fn = C.Rational((1.0,), (1.0, 0.0, 0.5))
    np.testing.assert_array_equal(
        np.asarray(ftfi.apply(spec1, pp1, fn, X, backend="pallas")),
        np.asarray(ftfi.apply(spec2, pp2, fn, X, backend="pallas")))
