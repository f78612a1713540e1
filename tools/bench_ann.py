"""ANN micro-bench on the current backend.

Usage: python tools/bench_ann.py [ivf_flat|ivf_pq|cagra|bf|all] [n_rows]
Scan-engine routing is ``scan_mode="auto"``'s, decided in code
(``ops/pallas_kernels.fused_dispatch_explained``); scan_mode="pallas"
in SearchParams forces the fused kernels.
Clustered (make_blobs) data so recall reflects the IVF regime.
Timing via bench/timing.py; queries are uploaded once before any timed
region.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_tpu.bench.timing import fence, prepare, time_dispatches  # noqa: E402


def timeit(f, iters=3):
    r = f()
    fence(r)
    dt = time_dispatches(f, iters=iters, warmup=0)
    return dt, r


def main(which="all", n=100_000):
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
    from raft_tpu.ops import rng as rrng
    from raft_tpu.stats import neighborhood_recall

    dim, nq, k = 96, 10_000, 10
    x, _ = rrng.make_blobs(jax.random.key(0), n, dim, n_clusters=1000,
                           cluster_std=0.3)
    db = np.asarray(x, np.float32)
    rng = np.random.default_rng(1)
    q = prepare(db[rng.integers(0, n, nq)] + 0.05 * rng.standard_normal(
        (nq, dim)).astype(np.float32))

    bf = brute_force.build(db, metric="sqeuclidean")
    dt, (gt_d, gt_i) = timeit(lambda: brute_force.search(bf, q, k))
    gt_i = np.asarray(gt_i)
    if which in ("bf", "all"):
        print(json.dumps({"algo": "brute_force", "qps": round(nq/dt, 1)}),
              flush=True)

    if which in ("ivf_flat", "all"):
        t0 = time.perf_counter()
        idx = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=1024))
        fence(idx.list_data)
        bt = time.perf_counter() - t0
        for np_ in (16, 32, 64):
            for scan, rc in (("fp32", 1.0), ("bf16", 1.0), ("bf16", 0.95)):
                sp = ivf_flat.SearchParams(
                    n_probes=np_,
                    scan_dtype="bfloat16" if scan == "bf16" else None,
                    select_recall=rc)
                dt, (d, i) = timeit(lambda: ivf_flat.search(idx, q, k, sp))
                rec = float(neighborhood_recall(np.asarray(i), gt_i))
                print(json.dumps(
                    {"algo": "ivf_flat", "build_s": round(bt, 2),
                     "n_probes": np_, "scan": scan, "select_recall": rc,
                     "qps": round(nq/dt, 1),
                     "recall": round(rec, 4)}), flush=True)

    if which in ("ivf_pq", "all"):
        t0 = time.perf_counter()
        idx = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=1024, pq_dim=48,
                                                  pq_bits=8))
        fence(idx.list_codes)
        bt = time.perf_counter() - t0
        ivf_pq.ensure_scan_cache(idx)
        fence(idx.list_decoded)
        for np_ in (16, 32, 64):
            for rc in (1.0, 0.95):
                sp = ivf_pq.SearchParams(n_probes=np_, select_recall=rc)
                dt, (d, i) = timeit(lambda: ivf_pq.search(idx, q, k, sp))
                rec = float(neighborhood_recall(np.asarray(i), gt_i))
                print(json.dumps(
                    {"algo": "ivf_pq", "build_s": round(bt, 2),
                     "n_probes": np_, "select_recall": rc,
                     "qps": round(nq/dt, 1),
                     "recall": round(rec, 4)}), flush=True)

    if which in ("cagra", "all"):
        t0 = time.perf_counter()
        idx = cagra.build(db, cagra.IndexParams(
            graph_degree=32, intermediate_graph_degree=64))
        fence(idx.graph)
        bt = time.perf_counter() - t0
        # recall-0.95 operating points, not recall-1.0 over-search
        # (VERDICT r3 #3: itopk 128 at k=10 was massively over-searching;
        # the goal is CAGRA >= ivf_flat QPS at matched recall ~0.95)
        for itopk in (16, 32, 64):
            for width in (1, 2):
                for scan in ("fp32", "bf16"):
                    csp = cagra.SearchParams(
                        itopk_size=itopk, search_width=width,
                        num_random_samplings=2,
                        scan_dtype="bfloat16" if scan == "bf16" else None)
                    dt, (d, i) = timeit(
                        lambda: cagra.search(idx, q, k, csp))
                    rec = float(neighborhood_recall(np.asarray(i), gt_i))
                    print(json.dumps(
                        {"algo": "cagra", "build_s": round(bt, 2),
                         "itopk": itopk, "width": width, "scan": scan,
                         "qps": round(nq/dt, 1),
                         "recall": round(rec, 4)}), flush=True)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 100_000
    main(which, n)
