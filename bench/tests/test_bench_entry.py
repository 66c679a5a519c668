"""``bench/run.py`` refuses a CPU backend and a checkout without the
program, printing no result line."""
import os
import shutil
import subprocess
import sys

import benchtiny


def run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "uniform_sat.ft_11k",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(p) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_refuses_cpu_backend():
    p = run_py(benchtiny.ROOT)
    assert p.returncode != 0
    assert no_result(p)
    assert "no TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(benchtiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(benchtiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py(tmp_path)
    assert p.returncode != 0
    assert no_result(p)
