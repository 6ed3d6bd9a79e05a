"""The command refuses to measure without a TPU, and cannot run from a
directory that holds only the benchmark."""

import os
import shutil
import subprocess
import sys

from bench_helpers import ROOT

ARGS = ["-m", "bench.run", "--workload", "rosenbrock.batch-paper",
        "--seed", "2147483659", "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return env


def test_no_tpu_no_result():
    out = subprocess.run([sys.executable, *ARGS], cwd=ROOT, env=_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 3, out.stderr
    assert out.stdout == ""
    assert "TPU" in out.stderr


def test_benchmark_alone_cannot_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    out = subprocess.run([sys.executable, *ARGS], cwd=tmp_path, env=_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
