"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

import chip_bench_support as sup

ARGS = ["--workload", "qwen3-4b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    out = _run(sup.REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(sup.REPO / "BENCHMARK.json", tmp_path)
    for p in sup.spec()["paths"]:
        shutil.copytree(sup.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
