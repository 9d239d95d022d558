"""The command refuses to run without a TPU, and without the program."""

import json
import os
import shutil
import subprocess
import sys

from conftest import HERE, ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "l2svm-mnist8m-fit", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_exits_nonzero_without_a_tpu(tmp_path):
    p = _run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert _no_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    p = _run(tmp_path, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "c")})
    assert p.returncode != 0
    assert _no_result(p.stdout)
