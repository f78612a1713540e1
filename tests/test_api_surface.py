"""API-surface guard: the pylibraft-parity names and the PARITY.md claims
must keep importing (the analog of the reference's test_doctests.py, which
exercises every public module's docstring surface)."""

import importlib
import pkgutil

import pytest


def _discover_modules():
    """All importable raft_tpu modules, found on disk (no drift as modules
    are added)."""
    import raft_tpu

    names = ["raft_tpu"]
    for info in pkgutil.walk_packages(raft_tpu.__path__, "raft_tpu."):
        # the ctypes-loaded C library is not a Python module
        if "libraft_tpu_native" in info.name:
            continue
        names.append(info.name)
    return sorted(names)


# explicit floor: if discovery somehow regresses, these must still be seen
MODULES = [
    "raft_tpu",
    "raft_tpu.core",
    "raft_tpu.core.bitset",
    "raft_tpu.core.errors",
    "raft_tpu.core.interruptible",
    "raft_tpu.core.logger",
    "raft_tpu.core.operators",
    "raft_tpu.core.resources",
    "raft_tpu.core.resources_manager",
    "raft_tpu.core.serialize",
    "raft_tpu.core.tracing",
    "raft_tpu.ops",
    "raft_tpu.ops.distance",
    "raft_tpu.ops.fused_l2_nn",
    "raft_tpu.ops.kernels",
    "raft_tpu.ops.linalg",
    "raft_tpu.ops.matrix",
    "raft_tpu.ops.pallas_kernels",
    "raft_tpu.ops.rng",
    "raft_tpu.ops.select_k",
    "raft_tpu.sparse",
    "raft_tpu.sparse.convert",
    "raft_tpu.sparse.distance",
    "raft_tpu.sparse.linalg",
    "raft_tpu.sparse.mst",
    "raft_tpu.sparse.neighbors",
    "raft_tpu.sparse.op",
    "raft_tpu.sparse.selection",
    "raft_tpu.sparse.solver",
    "raft_tpu.sparse.spectral",
    "raft_tpu.cluster",
    "raft_tpu.cluster.kmeans",
    "raft_tpu.cluster.kmeans_balanced",
    "raft_tpu.cluster.single_linkage",
    "raft_tpu.neighbors",
    "raft_tpu.neighbors.ball_cover",
    "raft_tpu.neighbors.brute_force",
    "raft_tpu.neighbors.cagra",
    "raft_tpu.neighbors.epsilon_neighborhood",
    "raft_tpu.neighbors.hnsw",
    "raft_tpu.neighbors.ivf_flat",
    "raft_tpu.neighbors.ivf_pq",
    "raft_tpu.neighbors.nn_descent",
    "raft_tpu.neighbors.rbc",
    "raft_tpu.neighbors.refine",
    "raft_tpu.parallel",
    "raft_tpu.parallel.comms",
    "raft_tpu.parallel.sharded",
    "raft_tpu.stats",
    "raft_tpu.bench",
    "raft_tpu.bench.export",
    "raft_tpu.bench.prims",
    "raft_tpu.bench.runner",
    "raft_tpu.native",
    "raft_tpu.common",
    "raft_tpu.distance",
    "raft_tpu.label",
    "raft_tpu.matrix",
    "raft_tpu.random",
    "raft_tpu.solver",
    "raft_tpu.spatial",
    "raft_tpu.utils",
    "raft_tpu.utils.compile_cache",
    "raft_tpu.utils.shape",
]


@pytest.mark.parametrize("mod", sorted(set(MODULES) | set(_discover_modules())))
def test_module_imports(mod):
    importlib.import_module(mod)


def test_discovery_covers_floor():
    assert set(MODULES) <= set(_discover_modules())


def test_pylibraft_parity_names():
    """Names a pylibraft user would reach for (SURVEY.md §2.10)."""
    from raft_tpu.common import DeviceResources, device_ndarray  # noqa: F401
    from raft_tpu.distance import (  # noqa: F401
        DistanceType, pairwise_distance, fused_l2_nn_argmin)
    from raft_tpu.matrix import select_k  # noqa: F401
    from raft_tpu.random import rmat, make_blobs  # noqa: F401
    from raft_tpu.cluster.kmeans import (  # noqa: F401
        KMeansParams, fit, fit_predict, cluster_cost, compute_new_centroids)
    from raft_tpu.neighbors.ivf_pq import (  # noqa: F401
        IndexParams, SearchParams, build, extend, search, serialize,
        deserialize)
    from raft_tpu.neighbors.cagra import build as cagra_build  # noqa: F401
    from raft_tpu.neighbors.hnsw import from_cagra  # noqa: F401
    from raft_tpu.neighbors.refine import refine  # noqa: F401
    from raft_tpu.neighbors.brute_force import knn  # noqa: F401


def test_comms_t_surface():
    """The comms_t method set (core/comms.hpp:127-661)."""
    from raft_tpu.parallel.comms import Comms

    for name in ("allreduce", "allgather", "allgatherv", "gather", "gatherv",
                 "bcast", "reduce", "reducescatter", "alltoall", "ppermute",
                 "shift", "device_send_recv", "device_multicast_sendrecv",
                 "comm_split", "sync", "rank", "size", "run", "shard"):
        assert hasattr(Comms, name), name


def test_round4_surface_names():
    """Round-4 additions stay public: sharded checkpoint/resume, the
    native hnsw-role ef-search, config scaling."""
    from raft_tpu.bench.runner import scale_config  # noqa: F401
    from raft_tpu.native import graph_greedy_search  # noqa: F401
    from raft_tpu.parallel.sharded import (  # noqa: F401
        deserialize_ivf_flat, deserialize_ivf_pq, serialize_ivf_flat,
        serialize_ivf_pq)
    from raft_tpu.utils.shape import as_query_array  # noqa: F401


def test_imports_are_deprecation_clean():
    """Importing the full public surface must not raise DeprecationWarning
    (one subprocess so -W error::DeprecationWarning covers import time)."""
    import os
    import subprocess
    import sys

    mods = sorted(set(MODULES) | set(_discover_modules()))
    code = ("import importlib\n"
            "for m in %r:\n"
            "    importlib.import_module(m)\n" % (mods,))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning", "-c", code],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr


def test_no_cross_package_private_imports():
    """R004 as an API-surface invariant: no raft_tpu package reaches
    another package's underscore-private names (the detail:: layering
    convention); enforced by the same analyzer the graftcheck CI gate
    runs, so a local pytest run fails before CI does."""
    import os

    from raft_tpu.analysis import collect_modules
    from raft_tpu.analysis.layering import check_layering

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    modules, parse_errors = collect_modules(repo, dirs=("raft_tpu",))
    assert parse_errors == []
    findings = check_layering(modules)
    assert findings == [], "\n".join(f.format() for f in findings)
