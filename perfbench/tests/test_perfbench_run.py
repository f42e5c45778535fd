"""The command as the benchmark is run: no result without a TPU, and none
in a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from perfbench.tests.tiny import ROOT

ARGS = ["--workload", "mamba2-zoo-seq2048", "--seed", str(2 ** 31 + 5),
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "perfbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_with_only_the_benchmark_it_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no system under test" in p.stderr
