"""``bench/run.py`` never falls back to the CPU: with no TPU it exits with
another code than 0 and prints no result; so it does too where the checkout
holds only the benchmark and not the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchlib import BENCH, ROOT, SPEC


def _run(cwd, cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cwd / "cache")}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "3000000017", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_no_tpu_no_result(tmp_path, cell):
    shutil.copytree(ROOT / "src", tmp_path / "src")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _run(tmp_path, cell)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    _no_result(proc)


def test_benchmark_alone_is_not_a_system(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _run(tmp_path, SPEC["workloads"][0]["name"])
    assert proc.returncode != 0
    _no_result(proc)
