"""Without a TPU the benchmark exits non-zero and prints no result."""

import json
import os
import shutil
import subprocess
import sys

import chipbench_support as sup


def _run(cwd, root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "benchmarks/chip/run.py"),
         "--workload", "dense64-fine.churn", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            continue
    return True


def test_refuses_to_run_without_a_tpu():
    p = _run(sup.ROOT, sup.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(sup.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(sup.ROOT / "benchmarks/chip", tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__", "_out"))
    p = _run(tmp_path, tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
