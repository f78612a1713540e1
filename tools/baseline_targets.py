"""Record benchmark artifacts for ALL five BASELINE.md target configs.

Reference: the five target shapes in BASELINE.md §"Target configs to
reproduce on TPU" (from BASELINE.json). Each run emits one JSON object per
target with build time + throughput/latency QPS + recall (the two
benchmark modes of docs/source/raft_ann_benchmarks.md:154), so perf is
tracked round-over-round on the CPU as well as on the chip.

Usage:
    python tools/baseline_targets.py --scale cpu  --out BENCH_TARGETS.json
    python tools/baseline_targets.py --scale full --out BENCH_TARGETS.json

``--scale cpu`` shrinks row counts so the suite finishes on a single CPU
core (shapes recorded in the artifact); ``--scale full`` runs the real
BASELINE shapes (TPU v5e; needs the dataset files for sift-1M/DEEP/glove,
or falls back to synthetic clustered data of the same shape).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("RAFT_TPU_BENCH_PLATFORM", "cpu") == "cpu":
    # CPU baseline targets unless asked for the default backend
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import numpy as np  # noqa: E402

from raft_tpu.bench.timing import fence, time_dispatches  # noqa: E402


def _clustered(rng, n, dim, **kw):
    from raft_tpu.bench.datagen import low_rank_clusters

    return low_rank_clusters(rng, n, dim, **kw)


def _timed_search(search_fn, nq, iters=3):
    """Single-batch timing: the whole query set is one dispatch;
    ``iters`` passes are dispatched ahead with ONE trailing fence
    (bench/timing.py). ``latency_ms`` is the
    per-PASS time at batch_size = nq under that dispatch-ahead pipeline —
    per-batch latency-mode sweeps live in bench/runner.py's
    ``_run_search``."""
    out = search_fn()
    fence(out)
    dt = time_dispatches(search_fn, iters=iters, warmup=0)
    return {"qps": round(nq / dt, 1), "batch_size": nq,
            "latency_ms": round(1000.0 * dt, 3)}, out


def target1_brute_force(scale, rng):
    """#1 pairwise L2 + brute-force kNN — sift-128 shape."""
    from raft_tpu.neighbors import brute_force
    from raft_tpu.stats import neighborhood_recall

    n = {"cpu": 10_000, "chip": 1_000_000}.get(scale, 1_000_000)
    nq, dim, k = 10_000, 128, 10
    db = rng.standard_normal((n, dim)).astype(np.float32)
    q = rng.standard_normal((nq, dim)).astype(np.float32)
    index = brute_force.build(db, metric="sqeuclidean")
    _, gt = brute_force.search(index, q, k)
    gt = np.asarray(gt)
    stats, out = _timed_search(
        lambda: brute_force.search(index, q, k, scan_dtype="bfloat16"), nq)
    rec = float(neighborhood_recall(np.asarray(out[1]), gt))
    return {"target": "brute_force_sift_l2", "shape": [n, dim], "k": k,
            "scan": "bf16+fp32refine", "recall": round(rec, 5), **stats}


def target2_kmeans_balanced(scale, rng):
    """#2 balanced k-means (IVF coarse-quantizer training) — 1M×128."""
    from raft_tpu import Resources
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams

    n = {"cpu": 100_000, "chip": 1_000_000}.get(scale, 1_000_000)
    dim, n_clusters = 128, 1024 if scale == "cpu" else 8192
    x = _clustered(rng, n, dim, n_centers=n_clusters // 4)
    res = Resources(seed=0)
    params = KMeansBalancedParams(n_iters=10)
    t0 = time.perf_counter()
    centers = kmeans_balanced.fit(res.next_key(), x, n_clusters, params,
                                  res=res)
    fence(centers)
    fit_s = time.perf_counter() - t0
    labels = kmeans_balanced.predict(centers, x, params, res=res)
    sizes = np.bincount(np.asarray(labels), minlength=n_clusters)
    return {"target": "kmeans_balanced", "shape": [n, dim],
            "n_clusters": n_clusters, "fit_s": round(fit_s, 2),
            "rows_per_s": round(n * 10 / fit_s, 1),
            "balance_cv": round(float(sizes.std() / sizes.mean()), 3)}


def target3_ivf_flat(scale, rng):
    """#3 ivf_flat build + search — sift-1M shape, nlist=1024."""
    from raft_tpu import Resources
    from raft_tpu.neighbors import brute_force, ivf_flat
    from raft_tpu.stats import neighborhood_recall

    n = {"cpu": 100_000, "chip": 1_000_000}.get(scale, 1_000_000)
    nq, dim, k = 2_000 if scale == "cpu" else 10_000, 128, 10
    n_lists = 1024
    db = _clustered(rng, n, dim)
    q = _clustered(rng, nq, dim)
    _, gt = brute_force.knn(q, db, k=k, metric="sqeuclidean")
    gt = np.asarray(gt)
    res = Resources(seed=0)
    t0 = time.perf_counter()
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=n_lists),
                           res=res)
    fence(index.list_data)
    build_s = time.perf_counter() - t0
    rows = []
    for nprobe in (32, 128):
        sp = ivf_flat.SearchParams(n_probes=nprobe, scan_dtype="bfloat16")
        stats, out = _timed_search(
            lambda: ivf_flat.search(index, q, k, sp), nq)
        rec = float(neighborhood_recall(np.asarray(out[1]), gt))
        rows.append({"nprobe": nprobe, "recall": round(rec, 4), **stats})
    return {"target": "ivf_flat_sift", "shape": [n, dim],
            "n_lists": n_lists, "build_s": round(build_s, 2),
            "search": rows}


def target4_ivf_pq_sharded(scale, rng):
    """#4 ivf_pq build + search + refine — DEEP-100M shape (pq_dim=64,
    sharded over the mesh; LUT engine = the memory-lean DEEP-100M/8 mode)."""
    from raft_tpu import Resources
    from raft_tpu.neighbors import brute_force, ivf_pq, refine
    from raft_tpu.parallel import comms as cm, sharded
    from raft_tpu.stats import neighborhood_recall

    # "chip" = one v5e: 4M rows (~1.5 GB) keeps the DEEP pipeline shape
    # at one chip's scale; "full" keeps the BASELINE spec for a pod.
    n = {"cpu": 80_000, "chip": 4_000_000}.get(scale, 100_000_000)
    nq, dim, k = {"cpu": 1_000}.get(scale, 10_000), 96, 10
    n_lists = {"cpu": 256, "chip": 4096}.get(scale, 50_000)
    pq_dim = 48 if scale == "cpu" else 64
    db = _clustered(rng, n, dim)
    q = _clustered(rng, nq, dim)
    _, gt = brute_force.knn(q, db, k=k, metric="sqeuclidean")
    gt = np.asarray(gt)
    comms = cm.init_comms(axis="data")
    params = ivf_pq.IndexParams(n_lists=n_lists, pq_dim=pq_dim, pq_bits=5,
                                kmeans_n_iters=10)
    out = {"target": "ivf_pq_sharded_deep", "shape": [n, dim],
           "n_shards": comms.size, "n_lists": n_lists, "pq_dim": pq_dim,
           "pq_bits": 5}
    for mode in ("cache", "lut"):
        t0 = time.perf_counter()
        idx = sharded.build_ivf_pq(comms, db, params, res=Resources(seed=0),
                                   scan_mode=mode)
        comms.sync(idx.list_decoded if mode == "cache" else idx.list_codes)
        build_s = time.perf_counter() - t0
        sp = ivf_pq.SearchParams(n_probes=32, scan_mode=mode)
        stats, res_out = _timed_search(
            lambda: sharded.search_ivf_pq(idx, q, k, sp), nq)
        rec = float(neighborhood_recall(np.asarray(res_out[1]), gt))
        out[f"{mode}_engine"] = {"build_s": round(build_s, 2),
                                 "nprobe": 32, "recall": round(rec, 4),
                                 **stats}
    # refine pass (the reference DEEP config's refine_ratio=2)
    d, i = sharded.search_ivf_pq(
        idx, q, 2 * k, ivf_pq.SearchParams(n_probes=32, scan_mode="lut"))
    _, i_r = refine.refine(db, q, np.asarray(i), k, metric="sqeuclidean")
    out["refine2_recall"] = round(
        float(neighborhood_recall(np.asarray(i_r), gt)), 4)
    return out


def target5_cagra(scale, rng):
    """#5 CAGRA graph build (NN-descent) + search — glove-100 shape."""
    from raft_tpu import Resources
    from raft_tpu.neighbors import brute_force, cagra
    from raft_tpu.stats import neighborhood_recall

    n = ({"cpu": 60_000}.get(scale, 1_183_514))  # glove-100 row count
    nq, dim, k = 2_000 if scale == "cpu" else 10_000, 100, 10
    db = _clustered(rng, n, dim)
    q = _clustered(rng, nq, dim)
    _, gt = brute_force.knn(q, db, k=k, metric="sqeuclidean")
    gt = np.asarray(gt)
    t0 = time.perf_counter()
    index = cagra.build(
        db, cagra.IndexParams(intermediate_graph_degree=64, graph_degree=32),
        res=Resources(seed=0))
    fence(index.graph)
    build_s = time.perf_counter() - t0
    rows = []
    for itopk in (64, 128):
        sp = cagra.SearchParams(itopk_size=itopk, search_width=2,
                                scan_dtype="bfloat16")
        stats, out = _timed_search(lambda: cagra.search(index, q, k, sp), nq)
        rec = float(neighborhood_recall(np.asarray(out[1]), gt))
        rows.append({"itopk": itopk, "recall": round(rec, 4), **stats})
    return {"target": "cagra_glove", "shape": [n, dim],
            "graph_degree": 32, "build_s": round(build_s, 2), "search": rows}


TARGETS = [target1_brute_force, target2_kmeans_balanced, target3_ivf_flat,
           target4_ivf_pq_sharded, target5_cagra]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=("cpu", "chip", "full"), default="cpu")
    ap.add_argument("--out", default=None)
    ap.add_argument("--targets", default="1,2,3,4,5",
                    help="comma-separated subset, e.g. 1,3")
    args = ap.parse_args()

    if args.scale == "cpu" and len(jax.devices()) < 8:
        # target #4 needs a mesh; match the test environment
        raise SystemExit(
            "run with XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "for the sharded target (#4)")

    wanted = {int(t) for t in args.targets.split(",")}
    rows = []
    for i, fn in enumerate(TARGETS, 1):
        if i not in wanted:
            continue
        rng = np.random.default_rng(100 + i)
        t0 = time.perf_counter()
        row = fn(args.scale, rng)
        row.update({"platform": jax.devices()[0].platform,
                    "scale": args.scale,
                    "wall_s": round(time.perf_counter() - t0, 1)})
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"targets": rows}, f, indent=1)


if __name__ == "__main__":
    main()
