"""Vector-search starter app (reference role: cpp/template/src — a
standalone executable against the installed library).

Builds an ANN index over an fbin dataset (or a synthetic one), searches,
reports recall vs the exact oracle and QPS. Everything it touches is the
public surface: ``Resources``, ``neighbors.{brute_force,ivf_flat,ivf_pq,
cagra}``, ``native`` fbin IO, ``stats.neighborhood_recall``.

    raft-tpu-app --algo ivf_pq --n 50000 --dim 64
    raft-tpu-app --algo cagra --base /path/base.fbin --queries q.fbin
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _load_or_make(args):
    from raft_tpu import native

    if args.base:
        db = native.read_bin(args.base)
        q = (native.read_bin(args.queries) if args.queries
             else db[: args.nq])
        return db, q
    rng = np.random.default_rng(0)
    proj = rng.standard_normal((16, args.dim)).astype(np.float32)
    z = rng.standard_normal((args.n + args.nq, 16)).astype(np.float32)
    x = z @ proj
    return x[: args.n], x[args.n:]


def _build_and_search(algo: str, db, q, k, res):
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq

    t0 = time.perf_counter()
    if algo == "brute_force":
        index = brute_force.build(db, metric="sqeuclidean")
        search = lambda: brute_force.search(index, q, k)  # noqa: E731
    elif algo == "ivf_flat":
        index = ivf_flat.build(db, ivf_flat.IndexParams(
            n_lists=max(32, int(len(db) ** 0.5))))
        sp = ivf_flat.SearchParams(n_probes=32)
        search = lambda: ivf_flat.search(index, q, k, sp)  # noqa: E731
    elif algo == "ivf_pq":
        index = ivf_pq.build(db, ivf_pq.IndexParams(
            n_lists=max(32, int(len(db) ** 0.5))))
        sp = ivf_pq.SearchParams(n_probes=32)
        search = lambda: ivf_pq.search(index, q, k, sp)  # noqa: E731
    elif algo == "cagra":
        index = cagra.build(db, cagra.IndexParams(
            intermediate_graph_degree=64, graph_degree=32))
        sp = cagra.SearchParams(itopk_size=64, search_width=2)
        search = lambda: cagra.search(index, q, k, sp)  # noqa: E731
    else:
        raise SystemExit(f"unknown --algo {algo}")
    build_s = time.perf_counter() - t0
    return search, build_s


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--algo", default="ivf_pq",
                    choices=("brute_force", "ivf_flat", "ivf_pq", "cagra"))
    ap.add_argument("--base", help="fbin dataset (default: synthetic)")
    ap.add_argument("--queries", help="fbin queries")
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--nq", type=int, default=1_000)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend")
    args = ap.parse_args(argv)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from raft_tpu import Resources
    from raft_tpu.neighbors import brute_force
    from raft_tpu.stats import neighborhood_recall

    db, q = _load_or_make(args)
    res = Resources(seed=0)
    print(f"dataset {db.shape}, {len(q)} queries, k={args.k}, "
          f"platform={jax.devices()[0].platform}")

    _, gt = brute_force.knn(q, db, k=args.k, metric="sqeuclidean")
    gt = np.asarray(gt)

    search, build_s = _build_and_search(args.algo, db, q, args.k, res)
    d, i = search()  # compile + warm
    jax.block_until_ready((d, i))
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(search())
    dt = (time.perf_counter() - t0) / 3
    rec = float(neighborhood_recall(np.asarray(i), gt))
    print(f"{args.algo}: build {build_s:.2f}s, "
          f"recall@{args.k} {rec:.4f}, {len(q) / dt:.0f} QPS")


if __name__ == "__main__":
    main()
