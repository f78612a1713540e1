"""``run.py`` refuses to run without a TPU, and without the program."""

import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "deep100m-exact-4chip", "--seed", "3", "--seconds",
        "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "benchmark/run.py"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    last = out.strip().splitlines()[-1] if out.strip() else ""
    return '"correct"' not in last


def test_fails_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_fails_with_only_the_benchmark(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "raft_tpu" in p.stderr
