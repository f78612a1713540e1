"""utils.compile_cache: the cache directory is placed from outside
(``JAX_COMPILATION_CACHE_DIR``) or sits at one fixed path in the
checkout. Run in child processes — the persistent cache stays off in the
CPU test process itself (tests/conftest.py)."""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
PROBE = ("import jax; from raft_tpu.utils.compile_cache import "
         "enable_persistent_cache as e; d = e(); "
         "print(d); print(jax.config.jax_compilation_cache_dir)")


def _probe(**env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run([sys.executable, "-c", PROBE], cwd=REPO,
                          env=dict(base, JAX_PLATFORMS="cpu", **env),
                          capture_output=True, text=True, timeout=120,
                          check=True)
    returned, configured = proc.stdout.split()[-2:]
    return returned, configured


def test_honours_jax_compilation_cache_dir(tmp_path):
    want = str(tmp_path / "cache")
    returned, configured = _probe(JAX_COMPILATION_CACHE_DIR=want)
    assert returned == configured == want
    assert os.path.isdir(want)


def test_default_is_one_fixed_path_inside_the_checkout():
    first = _probe()
    second = _probe()
    assert first == second
    assert first[0] == str(REPO / ".jax_cache")
