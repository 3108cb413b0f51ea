"""The command refuses to run where it cannot measure."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "mamba2_130m.paper_fed", "--seed", "2147483700", "--seconds", "1", "--trace", "0"]


def _run(cwd, env=None):
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, capture_output=True,
                          text=True, timeout=300, env=env)


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(ROOT, env)
    assert r.returncode != 0
    assert "{" not in r.stdout


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "{" not in r.stdout
