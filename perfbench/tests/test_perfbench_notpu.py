"""The benchmark exits non-zero and prints no result line where JAX finds
no TPU, also from a directory that holds only BENCHMARK.json and the
benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import registry

NAME = registry.benchmark()["workloads"][0]["name"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", NAME, "--seed",
         str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_exits_nonzero_without_tpu():
    proc = _run(registry.ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    _no_result(proc)


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(registry.ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    _no_result(proc)
