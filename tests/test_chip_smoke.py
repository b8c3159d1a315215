"""`chip_smoke.py`'s phases at tiny sizes on the CPU.

The script itself runs only on a TPU; here its phase functions run with the
Pallas kernels in interpret mode, so the oracle comparisons, the gates and
the no-fallback guard are exercised without a chip. The kernel-in-HLO checks
(`tpu_custom_call`) are the chip's alone and are not asserted here.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys
import warnings

import pytest

from repro.core import ladder

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def clean_ladder():
    ladder.reset_stats()
    ladder.unblock_backends()
    yield
    ladder.reset_stats()
    ladder.unblock_backends()


def test_phase_ftfi_tiny(smoke, clean_ladder):
    with smoke.no_fallback():
        rec = smoke.phase_ftfi(subdiv=2, d=4, rows=8)
        smoke.check_ladder_clean()
    engines = {(r["backend"], r["f"]): r["engine"] for r in rec["runs"]}
    assert engines == {("plan", "exp"): "exponential",
                       ("plan", "rational"): "chebyshev",
                       ("pallas", "exp"): "fdist_matvec:exp",
                       ("pallas", "rational"): "fdist_matvec:rational"}
    assert max(r["rel_err"] for r in rec["runs"]) <= 1e-5


def test_phase_topovit_tiny(smoke, clean_ladder):
    from repro.configs.topovit_b16 import SMOKE_CONFIG

    with smoke.no_fallback():
        rec = smoke.phase_topovit(SMOKE_CONFIG, train_layers=1, batch=8,
                                  steps=3, microbatches=2, parity_batch=2,
                                  patch_dim=48, num_classes=10)
        smoke.check_ladder_clean()
    train = rec["train"]
    # the train depth is cut, the parity runs at the config's own depth
    assert (train["layers"], rec["parity"]["layers"]) == (
        1, SMOKE_CONFIG.num_layers)
    assert len(train["losses"]) == 4 and train["losses"][-1] < train["losses"][0]
    # the 4 x 4 grid is a single small tree: both backends apply its mask
    # as the dense product by f(D), not through a plan cross engine
    assert train["engine"] == rec["parity"]["pallas_engine"] == "dense"
    assert rec["parity"]["rel_err"] <= 1e-4


def test_phase_topo_kernel_tiny(smoke, clean_ladder):
    with smoke.no_fallback():
        rec = smoke.phase_topo_kernel(B=1, H=2, L=300, m=8, hd=8)
        smoke.check_ladder_clean()
    assert [(r["mode"], r["causal"]) for r in rec["runs"]] == [
        ("decay", True), ("decay", False), ("rank", True), ("rank", False)]
    assert max(r["rel_err"] for r in rec["runs"]) <= 1e-4


def test_fallback_and_missed_gates_fail_loudly(smoke, clean_ladder):
    with pytest.raises(smoke.SmokeFailure, match="rel_err"):
        smoke.phase_topo_kernel(B=1, H=2, L=64, m=4, hd=4, gate=0.0)
    with smoke.no_fallback():
        with pytest.raises(ladder.BackendDemotionWarning):
            ladder.block_backend("pallas", "probe failed")
    with pytest.raises(smoke.SmokeFailure, match="ladder"):
        smoke.check_ladder_clean()
    with pytest.raises(smoke.SmokeFailure, match="tpu_custom_call"):
        smoke.check_kernel_ran({"tpu_custom_call": False}, "phase A")


def test_main_refuses_cpu(smoke, capsys):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err


def test_phase_sharded_on_four_cpu_devices():
    """Phase S on 4 virtual CPU devices (subprocess, so the device-count
    flag stays out of this session): parity with the single-device apply, one
    all_to_all and one reduce_scatter in the traced program, and the CPU
    compile's exact census: those two plus the all-gather that replicates
    the result."""
    code = (
        "import os, sys, json, importlib.util\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        f"spec = importlib.util.spec_from_file_location('chip_smoke', "
        f"{str(ROOT / 'chip_smoke.py')!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        "with cs.no_fallback():\n"
        "    rec = cs.phase_sharded(devices=4, subdiv=3, d=4, expect_compiled="
        "{'all-to-all': 1, 'reduce-scatter': 1, 'all-gather': 1})\n"
        "    cs.check_ladder_clean()\n"
        "print('PHASE_S', json.dumps(rec, default=float))\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "PHASE_S" in out.stdout, (out.stdout[-1500:], out.stderr[-3000:])
