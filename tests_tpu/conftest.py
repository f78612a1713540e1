"""On-chip test tier (VERDICT r3 #5): recall/numerics gates that only mean
something on real TPU hardware — the bf16 fast-scan recall collapse
(ROUND_NOTES r3) was invisible to the CPU suite because XLA:CPU upcasts
bf16 matmuls, and the approx/fp8 engines only use their hardware paths on
chip. Run on the chip, one process (the chip belongs to one process):

    python -m pytest tests_tpu/ -x -q -p no:cacheprovider

Unlike ``tests/`` (which forces an 8-device virtual CPU mesh), this
conftest keeps the default platform and SKIPS everything when the active
backend isn't a TPU, so a stray CPU-box run is a no-op instead of a false
green. Reference test pattern: the recall floors of
cpp/test/neighbors/ann_ivf_pq.cuh:510-525.
"""

import numpy as np
import pytest


def pytest_collection_modifyitems(config, items):
    import os

    import jax

    # RAFT_TPU_FORCE_ONCHIP_TESTS=1 runs the bodies on whatever backend
    # JAX_PLATFORMS selects (signature/plumbing debugging only — green on
    # CPU is NOT a gate; the bf16 canary is EXPECTED to fail there)
    if os.environ.get("RAFT_TPU_FORCE_ONCHIP_TESTS"):
        for item in items:
            item.add_marker(pytest.mark.tpu)
        return
    backend = jax.default_backend()
    if backend != "tpu":
        skip = pytest.mark.skip(
            reason=f"requires a real TPU backend (got {backend})")
        for item in items:
            item.add_marker(skip)
    for item in items:
        item.add_marker(pytest.mark.tpu)


@pytest.fixture(scope="session")
def clustered():
    """Clustered data (the regime that exposed the bf16 collapse: small
    distance gaps next to large vector norms)."""
    rng = np.random.default_rng(7)
    centers = rng.standard_normal((64, 96)).astype(np.float32) * 8.0
    assign = rng.integers(0, 64, 50_000)
    base = centers[assign] + rng.standard_normal((50_000, 96)).astype(
        np.float32)
    q_assign = rng.integers(0, 64, 512)
    queries = centers[q_assign] + rng.standard_normal((512, 96)).astype(
        np.float32)
    return base, queries


@pytest.fixture(scope="session")
def gt(clustered):
    base, queries = clustered
    from raft_tpu.neighbors import brute_force

    _, idx = brute_force.knn(queries, base, k=10, metric="sqeuclidean")
    return np.asarray(idx)


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


def recall(ids, gt_ids):
    from raft_tpu.stats import neighborhood_recall

    return float(neighborhood_recall(np.asarray(ids)[:, :gt_ids.shape[1]],
                                     gt_ids))
