"""Body of test_sharded_cagra, executed in a fresh subprocess (see the
test's docstring: a fresh process sidesteps an environment-level XLA:CPU
compile segfault that only appears deep into a long-lived test process).
Not collected by pytest (module name starts with an underscore)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax

import numpy as np

from raft_tpu.neighbors import brute_force, cagra
from raft_tpu.parallel import comms as comms_mod, sharded
from raft_tpu.stats import neighborhood_recall


def main():
    comms = comms_mod.init_comms(axis="data")
    assert comms.size == 8
    rng = np.random.default_rng(5)
    # clustered so the graph walk converges quickly
    centers = rng.standard_normal((20, 16)) * 6.0
    db = (centers[rng.integers(0, 20, 2000)]
          + rng.standard_normal((2000, 16))).astype(np.float32)
    q = db[:40] + 0.01 * rng.standard_normal((40, 16)).astype(np.float32)
    _, gt = brute_force.knn(q, db, k=5, metric="sqeuclidean")
    idx = sharded.build_cagra(
        comms, db, cagra.IndexParams(graph_degree=16,
                                     intermediate_graph_degree=32))
    d, i = sharded.search_cagra(idx, q, 5, cagra.SearchParams(itopk_size=32))
    i = np.asarray(i)
    assert i.shape == (40, 5)
    assert (i < 2000).all() and (i >= -1).all()
    recall = float(neighborhood_recall(i, np.asarray(gt)))
    assert recall >= 0.8, f"sharded cagra recall {recall}"
    # merge ladder: every cross-chip merge schedule is bit-identical to
    # the all_gather reference (docs/sharding.md)
    sp = cagra.SearchParams(itopk_size=32)
    d_ref, i_ref = sharded.search_cagra(idx, q, 5, sp,
                                        merge_mode="allgather")
    for mode in ("tree", "ring"):
        dm, im = sharded.search_cagra(idx, q, 5, sp, merge_mode=mode)
        np.testing.assert_array_equal(np.asarray(dm), np.asarray(d_ref),
                                      err_msg=f"cagra {mode} dist")
        np.testing.assert_array_equal(np.asarray(im), np.asarray(i_ref),
                                      err_msg=f"cagra {mode} ids")
    print("SHARDED_CAGRA_OK", recall)


if __name__ == "__main__":
    main()
