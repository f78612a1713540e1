"""chip_smoke.py off the chip: it must fail, never fall back, and its
phases must still run end to end at a tiny size (the CPU rehearsal of
the chip run — interpret-mode kernels, virtual devices)."""

import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_fails_without_a_tpu_even_when_cpu_is_asked_for(args):
    proc = _run(REPO, *args)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture()
def smoke(monkeypatch):
    from raft_tpu.core import resources

    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.syspath_prepend(str(REPO))
    # the builds draw their keys from the process's default Resources: a
    # fresh one, as in chip_smoke's own process, whatever ran before here
    monkeypatch.setattr(resources, "_default_resources", None)
    import chip_smoke

    return chip_smoke


def test_single_chip_phases_pass_at_tiny_size(smoke):
    phases = smoke.Phases()
    smoke.run_single_chip(smoke.Config(
        rows=3000, queries=48, n_lists=16, n_probes=4, pq_dim=16,
        graph_degree=16, intermediate_graph_degree=32, itopk=32,
        cagra_rows=2000, oracle_queries=24, serve_requests=24, max_batch=8,
        mutable_rows=16, kmeans_n_iters=4), phases)
    assert phases.failed == []


def test_four_chip_phases_pass_at_tiny_size(smoke):
    phases = smoke.Phases()
    smoke.run_four_chips(smoke.Config(
        rows=8000, queries=48, n_lists=16, n_probes=16, pq_dim=32,
        kmeans_n_iters=4), phases)
    assert phases.failed == []


def test_numpy_oracle_matches_a_direct_sort(smoke):
    import numpy as np

    rng = np.random.default_rng(0)
    base = rng.standard_normal((1000, 8)).astype(np.float32)
    q = rng.standard_normal((5, 8)).astype(np.float32)
    d, i = smoke.numpy_knn(base, q, 7, chunk=128)
    full = ((q[:, None, :].astype(np.float64) - base[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(i, np.argsort(full, 1)[:, :7])
    np.testing.assert_allclose(d, np.sort(full, 1)[:, :7], rtol=1e-9)
