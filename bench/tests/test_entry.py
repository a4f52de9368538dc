"""``bench/run.py`` refuses to run without a TPU, and without the program,
and prints no result line either way."""
import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "gpt13b-128.fluid", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def run(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script), *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = run(ROOT, ROOT / "bench" / "run.py")
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "not on a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
