"""The benchmark's command fails, printing no result, without a TPU, and
in a directory that holds only the benchmark."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from bench_tiny import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", workload,
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    for w in SPEC["workloads"]:
        out = _run(ROOT, w["name"])
        assert out.returncode != 0
        assert out.stdout.strip() == ""
        assert "needs a TPU" in out.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, SPEC["workloads"][0]["name"])
    assert out.returncode != 0
    assert out.stdout.strip() == ""
