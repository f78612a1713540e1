"""Offline operating-point sweep — the machinery behind tools/autotune.py.

Per family/shape/k, measure every (params, query_bucket) grid point
**through the public search APIs** (the serving handles' ``search_with``
— the exact code path the engine's adaptive policy replays online)
against an exact numpy oracle, then prune to the Pareto-optimal
QPS-vs-recall frontier (:func:`raft_tpu.planner.adaptive.pareto_prune`).

Each surviving point carries:

- ``qps``: queries/second at its bucket (bucket / best-of-N per-batch
  wall time, fenced per bench/timing.py);
- ``recall``: mean neighborhood recall vs the exact oracle over the
  whole eval query set;
- ``predicted_ms``: the committed per-batch device-time prediction the
  serving policy budgets against (the measured best-of-N batch time);
- ``roofline_min_ms``: the obs/costs roofline floor for the family's
  compiled entrypoint where chip peaks are known (None on CPU) — the
  anchor that flags a prediction promising less than physics allows.

The default grids are deliberately modest (the artifact is refreshed by
a bounded chip run); ``mini=True`` shrinks them
to CI scale (seconds on CPU).
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from raft_tpu.planner import adaptive

__all__ = ["FAMILIES", "default_grid", "exact_oracle", "sweep_family",
           "build_artifact"]

FAMILIES = ("brute_force", "ivf_flat", "ivf_pq", "cagra",
            "tiered_ivf_pq")


def default_grid(family: str, mini: bool = False) -> List[Dict[str, object]]:
    """The params grid per family: every knob combination the sweep
    measures (the frontier prune discards the dominated ones)."""
    if family == "brute_force":
        # exact search: the only speed/recall knob is the select stage's
        # exactness relaxation
        grid = [{"select_recall": 1.0}]
        if not mini:
            grid.append({"select_recall": 0.9})
        return grid
    if family in ("ivf_flat", "ivf_pq", "tiered_ivf_pq"):
        # tiered shares ivf_pq's knob: n_probes trades recall for scan
        # work AND arena churn (more probes -> more distinct lists per
        # batch -> lower hit rate at fixed slots), so the measured
        # frontier already prices the tier's fetch stalls
        probes = (4, 32) if mini else (4, 8, 16, 32, 64)
        return [{"n_probes": int(p)} for p in probes]
    if family == "cagra":
        if mini:
            combos = ((32, 1), (64, 4))
        else:
            combos = ((32, 1), (64, 1), (64, 4), (128, 4))
        # scan_mode is a sweepable knob since the fused Pallas beam
        # engine landed: "auto" follows the committed probe verdict,
        # "pallas" forces the fused walk — sweeping both grows committed
        # Pareto frontiers fused operating points wherever the kernel
        # wins, and keeps an XLA-routed point for replay parity. On
        # hosts with no TPU the forced point measures the silent XLA
        # fallback (identical results, ~identical ms) and the frontier
        # prune discards the duplicate.
        modes = ("auto",) if mini else ("auto", "pallas")
        return [{"itopk_size": int(it), "search_width": int(w),
                 "scan_mode": mode}
                for it, w in combos for mode in modes]
    raise ValueError(f"unknown family {family!r}; expected one of "
                     f"{FAMILIES}")


def _params_key(params: Dict[str, object]) -> str:
    return json.dumps(params, sort_keys=True)


def exact_oracle(db: np.ndarray, queries: np.ndarray,
                 k: int) -> np.ndarray:
    """Ground-truth top-k indices by squared L2, pure numpy (no device,
    no jit — the oracle must not share code with the thing it grades)."""
    d2 = ((queries ** 2).sum(1)[:, None] + (db ** 2).sum(1)[None, :]
          - 2.0 * queries @ db.T)
    part = np.argpartition(d2, kth=k - 1, axis=1)[:, :k]
    order = np.take_along_axis(d2, part, axis=1).argsort(axis=1)
    return np.take_along_axis(part, order, axis=1)


def _build_searcher(family: str, db: np.ndarray, res,
                    mini: bool = False):
    """One index + serving handle per family at sweep-shaped build
    params (mirrors tools/serving_bench.py's bench shapes)."""
    from raft_tpu import serving
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq

    n_lists = 32 if mini else 128
    if family == "brute_force":
        index = brute_force.build(db, metric="sqeuclidean", res=res)
        searcher = serving.brute_force_searcher(index, res=res)
        shape = {}
    elif family == "ivf_flat":
        index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=n_lists),
                               res=res)
        searcher = serving.ivf_flat_searcher(index, res=res)
        shape = {"n_lists": n_lists}
    elif family == "ivf_pq":
        index = ivf_pq.build(
            db, ivf_pq.IndexParams(n_lists=n_lists, pq_dim=32), res=res)
        searcher = serving.ivf_pq_searcher(index, res=res)
        shape = {"n_lists": n_lists, "pq_dim": 32}
    elif family == "cagra":
        index = cagra.build(db, cagra.IndexParams(
            graph_degree=32, intermediate_graph_degree=64), res=res)
        searcher = serving.cagra_searcher(index, res=res)
        shape = {"graph_degree": 32}
    elif family == "tiered_ivf_pq":
        # same index as ivf_pq, lists demoted to host RAM. The arena
        # holds every list (a smaller one could refuse a single batch
        # probing more distinct lists than it has slots): the sweep
        # prices the steady-state HIT path — the slot-indirected scan
        # the planner's operating point actually serves — while arena
        # churn under pressure is serving_bench's tiered arm.
        from raft_tpu.neighbors import tiered
        index = ivf_pq.build(
            db, ivf_pq.IndexParams(n_lists=n_lists, pq_dim=32), res=res)
        t = tiered.TieredIvfPq.from_index(
            index, res=res, arena_slots=n_lists, namespace="sweep")
        searcher = serving.tiered_ivf_pq_searcher(t, res=res)
        shape = {"n_lists": n_lists, "pq_dim": 32,
                 "arena_slots": t.arena.slots}
    else:
        raise ValueError(f"unknown family {family!r}")
    shape.update({"rows": int(db.shape[0]), "dim": int(db.shape[1])})
    return searcher, shape


def _device_peaks():
    """ChipPeaks for the active backend (None on CPU/unknown)."""
    try:
        import jax

        from raft_tpu.obs import costs as obs_costs

        return obs_costs.peaks_for_device_kind(
            jax.devices()[0].device_kind)
    except Exception:
        return None


def _roofline_min_ms(family: str, params: Dict[str, object], shape: dict,
                     bucket: int, peaks) -> Optional[float]:
    """obs/costs roofline floor for one (family, params, bucket) point:
    max(scan bytes / HBM peak, scan FLOPs / MXU peak) per batch — the
    min-attainable device time of the dominant scan phase at this
    operating point (same :func:`raft_tpu.obs.costs.apply_roofline`
    regime rule, applied to the sweep's own workload instead of the
    fixed audit shapes). None on CPU (no peaks table) and for cagra
    (the greedy graph walk is latency-bound, not roofline-bound)."""
    if peaks is None:
        return None
    rows, dim = int(shape["rows"]), int(shape["dim"])
    if family == "brute_force":
        scanned_rows, row_bytes = rows, dim * 4
        flops = 2.0 * bucket * rows * dim
    elif family == "ivf_flat":
        frac = int(params.get("n_probes", 20)) / max(
            int(shape.get("n_lists", 1)), 1)
        scanned_rows, row_bytes = min(frac, 1.0) * rows, dim * 4
        flops = 2.0 * bucket * scanned_rows * dim
    elif family in ("ivf_pq", "tiered_ivf_pq"):
        # the tiered hit path scans decoded slabs through the same
        # cache-core math, so the ivf_pq roofline is its floor too
        frac = int(params.get("n_probes", 20)) / max(
            int(shape.get("n_lists", 1)), 1)
        scanned_rows = min(frac, 1.0) * rows
        row_bytes = int(shape.get("pq_dim", 32))  # one code byte per dim
        flops = 2.0 * bucket * scanned_rows * row_bytes
    else:
        return None
    t_mem = scanned_rows * row_bytes / peaks.hbm_bytes_per_s
    t_flop = flops / peaks.flops_per_s
    return max(t_mem, t_flop) * 1e3


def _time_batch_s(searcher, batch: np.ndarray, k: int,
                  params: Dict[str, object], reps: int) -> float:
    """Best-of-``reps`` fenced wall time for one padded batch (best-of
    kills scheduler hiccups the same way bench_gate's noise rule
    does)."""
    from raft_tpu.bench import timing

    timing.fence(searcher.search_with(batch, k, params))  # warm/compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        timing.fence(searcher.search_with(batch, k, params))
        best = min(best, time.perf_counter() - t0)
    return best


def sweep_family(family: str, db: np.ndarray, queries: np.ndarray,
                 ks: Sequence[int], buckets: Sequence[int],
                 grid: Optional[List[Dict[str, object]]] = None,
                 res=None, reps: int = 3, mini: bool = False,
                 log=None) -> dict:
    """Sweep one family: returns the artifact's per-family payload
    (``shape``, ``build_s``, ``frontier`` keyed ``str(k) -> str(bucket)
    -> [point dicts]``, and sweep accounting)."""
    from raft_tpu.core.resources import ensure_resources

    res = ensure_resources(res)
    grid = grid if grid is not None else default_grid(family, mini=mini)
    t0 = time.perf_counter()
    searcher, shape = _build_searcher(family, db, res, mini=mini)
    build_s = time.perf_counter() - t0
    peaks = _device_peaks()
    n_swept = 0
    frontier: Dict[str, Dict[str, list]] = {}
    eval_bucket = max(buckets)
    for k in ks:
        gt = exact_oracle(db, queries, int(k))
        # recall is per-params, NOT per-bucket: the search cores are
        # row-wise and padding rows are zeros, so a row's result is
        # bucket-invariant (the serving bit-identity guarantee) — grade
        # once at the largest bucket and reuse across the bucket sweep
        recalls: Dict[str, float] = {}
        for params in grid:
            hits, total = 0, 0
            for j in range(0, len(queries), eval_bucket):
                chunk = queries[j:j + eval_bucket]
                batch = np.zeros((eval_bucket, db.shape[1]), np.float32)
                batch[:len(chunk)] = chunk
                _, idx = searcher.search_with(batch, int(k), params)
                idx = np.asarray(idx)[:len(chunk)]
                for row, ref in zip(idx, gt[j:j + eval_bucket]):
                    hits += np.isin(row, ref).sum()
                    total += len(ref)
            recalls[_params_key(params)] = hits / max(total, 1)
        per_bucket: Dict[str, list] = {}
        for bucket in buckets:
            points = []
            for params in grid:
                recall = recalls[_params_key(params)]
                batch = np.zeros((bucket, db.shape[1]), np.float32)
                batch[:] = queries[:bucket] if len(queries) >= bucket \
                    else np.resize(queries, (bucket, db.shape[1]))
                batch_s = _time_batch_s(searcher, batch, int(k), params,
                                        reps)
                points.append(adaptive.OperatingPoint(
                    params=dict(params), bucket=int(bucket),
                    qps=bucket / batch_s, recall=float(recall),
                    predicted_ms=batch_s * 1e3,
                    roofline_min_ms=_roofline_min_ms(
                        family, params, shape, bucket, peaks)))
                n_swept += 1
                if log is not None:
                    log(f"  {family} k={k} b={bucket} {params}: "
                        f"recall={recall:.4f} "
                        f"batch={batch_s * 1e3:.2f} ms")
            pruned = adaptive.pareto_prune(points)
            per_bucket[str(int(bucket))] = [p.to_dict() for p in pruned]
        frontier[str(int(k))] = per_bucket
    return {"shape": shape, "build_s": round(build_s, 2),
            "frontier": frontier, "n_swept": n_swept,
            "grid": [dict(g) for g in grid]}


def build_artifact(platform: str, families: Dict[str, dict],
                   config: Optional[dict] = None) -> dict:
    """Assemble the committed ``PARETO_<platform>.json`` document:
    schema tag, per-family frontiers, and the flat ``"metrics"`` mirror
    bench_gate's generic path reads (the ``frontier`` kind recomputes
    curve summaries from the points themselves)."""
    doc = {
        "schema": adaptive.PARETO_SCHEMA,
        "platform": platform,
        "when": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": dict(config or {}),
        "families": families,
    }
    doc["metrics"] = adaptive.frontier_metrics(doc)
    # round-trip through the loader so a malformed artifact can never be
    # written in the first place
    adaptive.Frontier(doc)
    return doc
