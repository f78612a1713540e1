"""Test configuration: force an 8-device virtual CPU platform so sharding /
comms tests run anywhere (the driver separately dry-runs the multi-chip path
via __graft_entry__.dryrun_multichip). Must set flags before jax imports."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# NOTE: do NOT enable the persistent compile cache here. On this image's
# XLA:CPU, cached AOT executables are compiled with machine features the
# loader reports as unsupported on the host ("+prefer-no-scatter … could
# lead to execution errors such as SIGILL"), and cache write/load paths
# have segfaulted mid-suite (ROUND_NOTES "Known flake"). The cache is the
# TPU-deployment feature (utils.enable_persistent_cache) — not a CPU CI
# accelerant.


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


def pytest_collection_modifyitems(config, items):
    """Everything not marked ``slow`` is the fast tier: ``pytest -m fast``
    gives a green signal in a few minutes, ``-m slow`` runs the heavy
    recall/scale suites (the reference's CI-vs-nightly split)."""
    for item in items:
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.fast)


@pytest.fixture()
def res():
    from raft_tpu import Resources

    return Resources(seed=42)
