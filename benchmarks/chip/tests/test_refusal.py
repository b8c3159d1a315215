"""Without a TPU the command exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import harness

CMD = ["--workload", "mesh7-rational-pallas", "--seed", "3000000001",
       "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *CMD],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    p = _run(harness.ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path: Path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
