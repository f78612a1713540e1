"""Headline benchmark — prints ONE JSON line.

Flagship config (BASELINE.md target #1): pairwise L2 + brute-force kNN,
sift-128-euclidean shape (10k queries × 10k database, dim=128, k=10).
Metric is QPS in throughput mode (all queries batched), matching
raft-ann-bench's QPS definition (docs/source/raft_ann_benchmarks.md:154).
``vs_baseline`` is 1.0 — BASELINE.json publishes no reference numbers
(``published: {}``), so there is nothing to normalize against.

Secondary index metrics (ivf_flat / ivf_pq / cagra QPS + recall on the same
data) ride along in the ``extra`` key; set RAFT_TPU_BENCH_EXTRAS=0 to skip.

The run is for the chip: with no TPU it exits non-zero, unless
``JAX_PLATFORMS=cpu`` asks for the CPU explicitly — and then the line says
``"platform": "cpu"``.
"""

import json
import os
import sys
import time


def main():
    from raft_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"bench: no TPU (JAX found {platform}); set JAX_PLATFORMS=cpu "
              "to run on the CPU explicitly", file=sys.stderr)
        sys.exit(3)

    import numpy as np

    from raft_tpu.bench.timing import fence, prepare, time_dispatches
    from raft_tpu.neighbors import brute_force
    from raft_tpu.stats import neighborhood_recall

    n_db, n_q, dim, k = 10_000, 10_000, 128, 10
    rng = np.random.default_rng(0)
    db = rng.standard_normal((n_db, dim)).astype(np.float32)
    # queries live on device BEFORE any timed region — the host→device
    # copy must never be inside a measurement
    q = prepare(rng.standard_normal((n_q, dim)).astype(np.float32))

    index = brute_force.build(db, metric="sqeuclidean")

    # exact fp32 pass = ground truth + the fallback timing target
    d_e, i_e = brute_force.search(index, q, k)
    fence((d_e, i_e))
    gt = np.asarray(i_e)

    # Fast variants (ordered fastest-first), each gated on recall >= 0.999
    # against the exact pass: bf16 MXU screen + exact fp32 re-rank, with
    # and without APPROX candidate selection (the final re-rank select
    # stays exact either way, so the approx screen only risks candidate
    # misses the gate would catch).
    variants = [
        ({"scan_dtype": "bfloat16", "select_recall": 0.95},
         "bf16+approx95+fp32refine"),
        ({"scan_dtype": "bfloat16"}, "bf16+fp32refine"),
        ({}, "fp32"),
    ]
    best = None  # (dt, recall, kwargs, label) — measured, not assumed:
    # variant ordering flips between platforms (approx wins on TPU's
    # PartialReduce, loses to plain top_k on CPU's exact fallback)
    for kw, name in variants:
        d_f, i_f = brute_force.search(index, q, k, **kw)
        rec = float(neighborhood_recall(np.asarray(i_f), gt))
        if rec < 0.999 and kw:
            continue
        dt_v = time_dispatches(
            lambda: brute_force.search(index, q, k, **kw), iters=2,
            warmup=0)
        if best is None or dt_v < best[0]:
            best = (dt_v, rec, kw, name)
    _, recall, chosen, label = best

    dt = time_dispatches(
        lambda: brute_force.search(index, q, k, **chosen), iters=5,
        warmup=0)
    qps = n_q / dt

    # which select algorithm the winning variant's scan actually used:
    # APPROX when the variant opted in via select_recall, else what AUTO
    # resolves at the scan's true select width (db_tile, not n_db)
    if chosen.get("select_recall", 1.0) < 1.0:
        sel_algo = "approx"
        k_pad = 0
    else:
        from raft_tpu.neighbors.brute_force import _choose_tiles
        from raft_tpu.ops.select_k import _pad_k, _resolve_auto
        from raft_tpu.core.resources import ensure_resources

        _, db_tile = _choose_tiles(
            n_q, n_db, dim, k,
            ensure_resources(None).workspace_limit_bytes)
        sel_algo = _resolve_auto(db_tile, k).value
        # whether a k-pad rule rewrote the requested k
        k_pad = _pad_k(db_tile, k) if sel_algo == "direct" else 0

    row = {
        "metric": "brute_force_knn_qps_sift10k_k10",
        "value": round(qps, 1),
        "unit": "QPS",
        "vs_baseline": 1.0,
        "recall": round(recall, 5),
        "scan": label,
        "select_algo": sel_algo,
        "platform": platform,
    }
    if k_pad and k_pad != k:
        row["select_k_pad"] = k_pad

    if os.environ.get("RAFT_TPU_BENCH_EXTRAS", "1") != "0":
        row["extra"] = _index_extras(k)

    print(json.dumps(row))


def _index_extras(k):
    """ANN-index secondary metrics (BASELINE targets #3/#5 shapes, scaled
    to stay a small fraction of bench wall-clock). Uses clustered data of
    low intrinsic dimension — the real benchmark datasets' regime; both
    iid gaussian and full-dim gaussian clusters concentrate distances
    (vanishing top-k gaps), which measures the generator, not the index."""
    import jax
    import numpy as np

    from raft_tpu import Resources
    from raft_tpu.bench.timing import (chain_perturb, fence, fence_index,
                                       last_info, prepare, time_dispatches,
                                       time_latency_chained)
    from raft_tpu.serving.stats import percentiles
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
    from raft_tpu.stats import neighborhood_recall

    from raft_tpu.bench.datagen import low_rank_clusters

    rng = np.random.default_rng(7)
    n_db, n_q, dim = 10_000, 10_000, 128
    both = low_rank_clusters(rng, n_db + n_q, dim, n_centers=64)
    db, q_host = both[:n_db], both[n_db:]
    db = prepare(db)  # builds are jnp.asarray-based: upload once, reuse
    q = prepare(q_host)
    _, gt_j = brute_force.knn(q, db, k=k, metric="sqeuclidean")
    gt = np.asarray(gt_j)
    res = Resources(seed=0)
    out = {}

    def timed(search_fn):
        d, i = search_fn()  # warmup/compile
        fence((d, i))
        rec = float(neighborhood_recall(np.asarray(i), gt))
        dt = time_dispatches(search_fn, iters=3, warmup=0)
        return {"qps": round(n_q / dt, 1), "recall": round(rec, 4)}

    def lat_ms(entry, name, search_small, batch):
        """Serving latency at tiny batches (VERDICT r2 #7): per-call
        device latency with calls chained by a data dependency, so one
        readback is paid per round and amortized; the query bucketing in each search keeps every batch ≤ 256 on
        one compiled program. Eight fenced rounds feed p50/p95/p99
        alongside the mean — a bare mean hid the r5 host-contention
        skew (6 ms medians with 37-45 ms outlier rounds) until it
        was 6x."""
        q0 = q[:batch]
        dt = time_latency_chained(
            lambda qq: chain_perturb(q0, search_small(qq)),
            q0, iters=8, rounds=8)
        entry[name] = round(dt * 1e3, 3)  # the mean, schema-compatible
        for pct, v in percentiles(last_info["samples_s"]).items():
            entry[f"{name}_{pct}"] = round(v * 1e3, 3)

    def timed_build(build_fn):
        """Cold build (includes trace+compile) and warm build (cached
        executables — the steady-state cost); both fenced, since builds
        end in async device work."""
        t0 = time.perf_counter()
        index = build_fn()
        fence_index(index)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        index = build_fn()
        fence_index(index)
        warm = time.perf_counter() - t0
        return index, round(cold, 2), round(warm, 2)

    fl, fl_cold, fl_warm = timed_build(
        lambda: ivf_flat.build(db, ivf_flat.IndexParams(n_lists=128),
                               res=res))
    sp = ivf_flat.SearchParams(n_probes=32, scan_dtype="bfloat16")
    out["ivf_flat_nprobe32_bf16"] = timed(
        lambda: ivf_flat.search(fl, q, k, sp))
    out["ivf_flat_nprobe32_bf16"]["build_s"] = fl_cold
    out["ivf_flat_nprobe32_bf16"]["build_warm_s"] = fl_warm
    for b in (1, 10):
        lat_ms(out["ivf_flat_nprobe32_bf16"], f"latency_ms_b{b}",
               lambda qq: ivf_flat.search(fl, qq, k, sp), b)

    pq, pq_cold, pq_warm = timed_build(
        lambda: ivf_pq.build(db, ivf_pq.IndexParams(n_lists=128, pq_dim=64),
                             res=res))
    psp = ivf_pq.SearchParams(n_probes=32)
    out["ivf_pq_nprobe32"] = timed(lambda: ivf_pq.search(pq, q, k, psp))
    out["ivf_pq_nprobe32"]["build_s"] = pq_cold
    out["ivf_pq_nprobe32"]["build_warm_s"] = pq_warm
    for b in (1, 10):
        lat_ms(out["ivf_pq_nprobe32"], f"latency_ms_b{b}",
               lambda qq: ivf_pq.search(pq, qq, k, psp), b)

    cg, cg_cold, cg_warm = timed_build(
        lambda: cagra.build(db, cagra.IndexParams(
            graph_degree=32, intermediate_graph_degree=64), res=res))
    csp = cagra.SearchParams(itopk_size=128, search_width=4,
                             scan_dtype="bfloat16")
    out["cagra_itopk128_bf16"] = timed(lambda: cagra.search(cg, q, k, csp))
    out["cagra_itopk128_bf16"]["build_s"] = cg_cold
    out["cagra_itopk128_bf16"]["build_warm_s"] = cg_warm
    for b in (1, 10):
        lat_ms(out["cagra_itopk128_bf16"], f"latency_ms_b{b}",
               lambda qq: cagra.search(cg, qq, k, csp), b)
    return out


if __name__ == "__main__":
    main()
