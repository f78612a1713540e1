"""Validate + A/B the Pallas kernels on real Mosaic (runbook steps 4/7).

Every Pallas kernel in this repo had only ever run under the Mosaic
interpreter until round 3; the first hardware attempts exposed missing
lowerings (take_along_axis in the streaming top-k; block-alignment in
the DMA scan). This probes what actually lowers and how it compares to
the XLA paths, writing PALLAS_PROBE_tpu.json (schema v3):

- fused_l2_argmin (k-means assignment kernel) vs the XLA fused_l2_nn
  at n_clusters ∈ {1024, 8192} — the hot loop of every IVF build.
- pallas_select_k (streaming k-extraction) vs DIRECT/APPROX at small k.
- the fused scan+select engines (``scan_mode="pallas"``: VMEM-resident
  top-k carry) vs the XLA two-step through the public search APIs at
  the sift-1M shape grid, one A/B per family — including the fused
  CAGRA beam-search engine (schema v3: the whole graph walk inside one
  kernel, VMEM-resident beam state) vs the XLA beam walk — plus the
  retired per-kernel routes (the unfused DMA ivf_scan, fused_l2_argmin
  inside k-means). Each row ends in a ``fused_wins`` verdict;
  ``ops.pallas_kernels.fused_crossover`` reads the committed artifact's
  verdicts, so THIS FILE is where ``scan_mode="auto"`` routing is
  decided — re-run after kernel or compiler changes.

On a multi-chip (power-of-two) mesh the probe also A/Bs the cross-chip
merge ladder: the Pallas RDMA ring shift vs the XLA ppermute tree merge
(``fused.merge_ring.fused_wins`` is what ``merge_mode="auto"`` consults,
docs/sharding.md). Single-chip hosts write NO merge_ring row, keeping
``ring_merge_verdict()`` at the three-state "no artifact row".

Usage: python tools/pallas_probe.py [--out PALLAS_PROBE_tpu.json]
       [--n 1000000]  (database rows for the fused A/B grid)
       [--require-verdicts]  (exit 2 unless every routing family landed
       a real measured verdict — the TPU-queue guard against silently
       shipping an artifact that leaves auto unrouted)
       [--only cagra[,...]]  (re-measure just the named fused families,
       merging every other row from the existing --out artifact — the
       long 1M cagra graph build can be isolated this way so it cannot
       starve the other rows of a bounded chip run)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

#: families whose fused_wins verdicts ARE auto-mode routing tables
REQUIRED_VERDICT_FAMILIES = (
    "brute_force", "ivf_flat", "ivf_pq", "ivf_scan", "l2_argmin", "cagra")


def missing_verdicts(art: dict, on_tpu: bool, mergeable_mesh: bool) -> list:
    """Routing families whose artifact row is NOT a real measured
    verdict: absent, errored, or produced off-TPU (where scan_mode=
    "pallas" silently falls back and times XLA against itself).
    ``merge_ring`` is required only where it is measurable — a
    power-of-two multi-chip mesh."""
    required = list(REQUIRED_VERDICT_FAMILIES)
    if mergeable_mesh:
        required.append("merge_ring")
    if not on_tpu:
        return required
    fused = art.get("fused", {})
    return [f for f in required
            if not isinstance(fused.get(f), dict)
            or "fused_wins" not in fused[f]
            or "pallas_error" in fused[f]]


def _overlap(i_a, i_b, rows: int = 2048) -> float:
    """Mean per-row fraction of shared neighbor ids (order-insensitive —
    ties at the k boundary reorder freely between engines)."""
    a = np.asarray(i_a)[:rows]
    b = np.asarray(i_b)[:rows]
    return float(np.mean([
        len(np.intersect1d(r, s)) / max(r.shape[0], 1)
        for r, s in zip(a, b)]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="PALLAS_PROBE_tpu.json")
    ap.add_argument("--n", type=int, default=1_000_000,
                    help="database rows for the fused scan+select grid")
    ap.add_argument("--require-verdicts", action="store_true",
                    help="exit 2 unless every auto-routing family landed "
                         "a real measured fused_wins verdict (TPU hosts)")
    ap.add_argument("--only", default=None,
                    help="comma-separated fused families to (re)measure; "
                         "every other row is merged from the existing "
                         "--out artifact instead of re-run")
    ap.add_argument("--skip", default="",
                    help="comma-separated fused families to leave out of "
                         "this run (their rows are simply not written — "
                         "a later --only run fills them in)")
    args = ap.parse_args()
    only = (set(s.strip() for s in args.only.split(",") if s.strip())
            if args.only else None)
    skip = set(s.strip() for s in args.skip.split(",") if s.strip())

    def want(fam: str) -> bool:
        return (only is None or fam in only) and fam not in skip

    import jax

    from raft_tpu.bench.timing import prepare, time_dispatches
    from raft_tpu.ops import fused_l2_nn as fl
    from raft_tpu.ops import pallas_kernels as pk
    from raft_tpu.ops.select_k import SelectAlgo, select_k

    art = {"schema": "raft_tpu.pallas_probe/v3",
           "platform": jax.default_backend(),
           "when": time.strftime("%Y-%m-%dT%H:%M:%S%z")}
    if only is not None and os.path.exists(args.out):
        # partial re-measure: rows NOT named in --only carry over from
        # the committed artifact instead of being dropped
        with open(args.out) as f:
            base = json.load(f)
        for sec in ("fused_l2_argmin", "select_k", "fused"):
            if isinstance(base.get(sec), dict):
                art[sec] = base[sec]
    rng = np.random.default_rng(0)

    # ---- fused L2 argmin (k-means assignment)
    if want("l2_argmin"):
        art["fused_l2_argmin"] = {}
        x = prepare(rng.standard_normal((100_000, 96)).astype(np.float32))
        for n_c in (1024, 8192):
            y = prepare(rng.standard_normal((n_c, 96)).astype(np.float32))
            row = {}
            try:
                d, i = pk.fused_l2_argmin(x, y)
                i_ref = fl.fused_l2_nn_argmin(x, y)[1]
                agree = float(np.mean(np.asarray(i) == np.asarray(i_ref)))
                row["pallas_ms"] = round(time_dispatches(
                    lambda: pk.fused_l2_argmin(x, y), iters=5) * 1e3, 2)
                row["agreement"] = round(agree, 5)
            except Exception as e:  # lowering failure is a finding
                row["pallas_error"] = f"{type(e).__name__}: {e}"[:300]
            row["xla_ms"] = round(time_dispatches(
                lambda: fl.fused_l2_nn_argmin(x, y), iters=5) * 1e3, 2)
            art["fused_l2_argmin"][f"n_clusters_{n_c}"] = row
            print(f"fused_l2_argmin n_c={n_c}: {row}", flush=True)

    # ---- streaming pallas select_k vs DIRECT vs APPROX
    if only is None:
        art["select_k"] = {}
        v = prepare(rng.standard_normal((2048, 16384)).astype(np.float32))
        for k in (10, 32):
            row = {}
            try:
                pv, pi = pk.pallas_select_k(v, k)
                ev, _ = select_k(v, k)
                row["max_val_err"] = float(
                    np.max(np.abs(np.asarray(pv) - np.asarray(ev))))
                row["pallas_ms"] = round(time_dispatches(
                    lambda: pk.pallas_select_k(v, k), iters=5) * 1e3, 2)
            except Exception as e:
                row["pallas_error"] = f"{type(e).__name__}: {e}"[:300]
            row["direct_ms"] = round(time_dispatches(
                lambda: select_k(v, k, algo=SelectAlgo.DIRECT),
                iters=5) * 1e3, 2)
            row["approx95_ms"] = round(time_dispatches(
                lambda: select_k(v, k, algo=SelectAlgo.APPROX),
                iters=5) * 1e3, 2)
            art["select_k"][f"k_{k}"] = row
            print(f"select_k k={k}: {row}", flush=True)

    # ---- fused scan+select engines vs the XLA two-step (sift-1M grid).
    # The fused_wins verdicts below ARE the scan_mode="auto" routing
    # table (pallas_kernels.fused_crossover) once this artifact is
    # committed.
    from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq
    from raft_tpu.ops import rng as rrng

    on_tpu = jax.default_backend() == "tpu"
    art.setdefault("fused", {})
    n, dim, kk = args.n, 128, 100
    need_db = any(want(f) for f in
                  ("brute_force", "ivf_flat", "ivf_scan", "ivf_pq", "cagra"))
    if need_db:
        xb, _ = rrng.make_blobs(jax.random.key(7), n, dim, n_clusters=1024,
                                cluster_std=0.3)
        db = np.asarray(xb, np.float32)
        q = prepare(db[rng.integers(0, n, 1024)]
                    + 0.05 * rng.standard_normal(
                        (1024, dim)).astype(np.float32))

    def fused_ab(fam, run_pallas, run_xla, extra=None):
        row = dict(extra or {})
        try:
            _, pi = run_pallas()
            _, xi = run_xla()
            row["agreement"] = round(_overlap(pi, xi), 5)
            row["pallas_ms"] = round(
                time_dispatches(run_pallas, iters=5) * 1e3, 2)
            row["xla_ms"] = round(
                time_dispatches(run_xla, iters=5) * 1e3, 2)
            row["fused_wins"] = bool(
                on_tpu and row["agreement"] >= 0.99
                and row["pallas_ms"] < row["xla_ms"])
            if not on_tpu:
                # scan_mode="pallas" silently falls back off-TPU, so the
                # timings compare XLA with itself — never a verdict
                row["note"] = "xla-fallback (no TPU): not a verdict"
        except Exception as e:
            row["pallas_error"] = f"{type(e).__name__}: {e}"[:300]
            row["fused_wins"] = False
        art["fused"][fam] = row
        print(f"fused {fam}: {row}", flush=True)

    if want("brute_force"):
        qb = prepare(db[rng.integers(0, n, 10_000)]
                     + 0.05 * rng.standard_normal((10_000, dim)).astype(
                         np.float32))
        bf = brute_force.build(db, metric="sqeuclidean")
        fused_ab(
            "brute_force",
            lambda: brute_force.search(bf, qb, kk, scan_mode="pallas"),
            lambda: brute_force.search(bf, qb, kk, scan_mode="xla"))

    if want("ivf_flat") or want("ivf_scan"):
        fi = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=1024,
                                                     kmeans_n_iters=10))
        sp_p = ivf_flat.SearchParams(n_probes=64, scan_mode="pallas")
        sp_x = ivf_flat.SearchParams(n_probes=64, scan_mode="xla")
    if want("ivf_flat"):
        fused_ab(
            "ivf_flat",
            lambda: ivf_flat.search(fi, q, kk, sp_p),
            lambda: ivf_flat.search(fi, q, kk, sp_x))

    # the retired per-kernel route: the unfused DMA ivf_scan inside the
    # XLA engine, toggled via the crossover hook it is now gated behind
    if want("ivf_scan"):
        key = pk.fused_platform_key()
        try:
            pk.set_fused_crossover(key, {"ivf_scan": True})
            old_ms = round(time_dispatches(
                lambda: ivf_flat.search(fi, q, kk, sp_x), iters=5) * 1e3, 2)
            pk.set_fused_crossover(key, {"ivf_scan": False})
            xla_ms = round(time_dispatches(
                lambda: ivf_flat.search(fi, q, kk, sp_x), iters=5) * 1e3, 2)
            row = {"pallas_ms": old_ms, "xla_ms": xla_ms,
                   "fused_wins": bool(on_tpu and old_ms < xla_ms)}
        except Exception as e:
            row = {"pallas_error": f"{type(e).__name__}: {e}"[:300],
                   "fused_wins": False}
        finally:
            pk.set_fused_crossover(key, None)
        art["fused"]["ivf_scan"] = row
        print(f"fused ivf_scan: {row}", flush=True)

    if want("ivf_pq"):
        pq = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=1024, pq_dim=64,
                                                 pq_bits=8,
                                                 kmeans_n_iters=10))
        sp_pp = ivf_pq.SearchParams(n_probes=64, scan_mode="pallas")
        sp_pc = ivf_pq.SearchParams(n_probes=64, scan_mode="cache")
        sp_pl = ivf_pq.SearchParams(n_probes=64, scan_mode="lut")
        cache_ms = round(time_dispatches(
            lambda: ivf_pq.search(pq, q, kk, sp_pc), iters=5) * 1e3, 2)
        lut_ms = round(time_dispatches(
            lambda: ivf_pq.search(pq, q, kk, sp_pl), iters=5) * 1e3, 2)
        fused_ab(
            "ivf_pq",
            lambda: ivf_pq.search(pq, q, kk, sp_pp),
            (lambda: ivf_pq.search(pq, q, kk, sp_pc)) if cache_ms <= lut_ms
            else (lambda: ivf_pq.search(pq, q, kk, sp_pl)),
            extra={"cache_ms": cache_ms, "lut_ms": lut_ms})

    # ---- fused cagra: the whole beam walk inside one Pallas kernel
    # (VMEM-resident beam state) vs the XLA hop-by-hop walk, A/B'd
    # through the public search API at the same resolved beam plan. The
    # graph build is the longest setup in this probe — the queue's
    # ``cagrafuse`` step re-measures just this row via --only cagra.
    if want("cagra"):
        from raft_tpu.neighbors import cagra as cagra_mod

        cg = cagra_mod.build(db, cagra_mod.IndexParams())
        cg_p = cagra_mod.SearchParams(scan_mode="pallas")
        cg_x = cagra_mod.SearchParams(scan_mode="xla")
        itopk_r, width_r, max_iter_r, n_seeds_r = \
            cagra_mod.resolve_search_plan(cg_p, kk, cg.size)
        fused_ab(
            "cagra",
            lambda: cagra_mod.search(cg, q, kk, cg_p),
            lambda: cagra_mod.search(cg, q, kk, cg_x),
            extra={"itopk": itopk_r, "search_width": width_r,
                   "max_iter": max_iter_r, "n_seeds": n_seeds_r,
                   "graph_degree": cg.graph_degree})

    # per-kernel fused_l2_argmin verdict, derived from the section above
    # (it must win at EVERY probed cluster count to earn the k-means
    # routing — ops/fused_l2_nn.py consults this family)
    if want("l2_argmin"):
        l2_rows = list(art["fused_l2_argmin"].values())
        art["fused"]["l2_argmin"] = {
            "derived_from": "fused_l2_argmin",
            "fused_wins": bool(on_tpu and l2_rows and all(
                "pallas_ms" in r and r["pallas_ms"] < r["xla_ms"]
                for r in l2_rows))}
        print(f"fused l2_argmin: {art['fused']['l2_argmin']}", flush=True)

    # ---- cross-chip merge: Pallas RDMA ring shift vs the XLA ppermute
    # tree (the merge_mode="auto" routing for sharded searches,
    # docs/sharding.md). Only measurable on a power-of-two multi-chip
    # mesh; other hosts write NO row so ring_merge_verdict() stays at
    # the three-state None ("no_ring_verdict" -> tree).
    n_dev = len(jax.devices())
    mergeable = n_dev >= 2 and (n_dev & (n_dev - 1)) == 0
    if mergeable and want("merge_ring"):
        import functools

        from jax.sharding import PartitionSpec as P

        from raft_tpu.parallel import comms as comms_mod

        comms = comms_mod.init_comms(jax.devices(), axis="mergeprobe")
        nq_m, kk_m = 1024, 100
        k_out = min(kk_m, n_dev * kk_m)
        v_g = prepare(rng.standard_normal(
            (n_dev * nq_m, kk_m)).astype(np.float32))
        i_g = prepare(rng.integers(
            0, args.n, (n_dev * nq_m, kk_m)).astype(np.int32))
        in_sp = (P("mergeprobe", None), P("mergeprobe", None))
        out_sp = (P(None, None), P(None, None))
        shift = (functools.partial(pk.pallas_ring_shift, axis="mergeprobe",
                                   size=n_dev) if on_tpu else None)
        row = {"n_devices": n_dev, "nq": nq_m, "kk": kk_m}
        try:
            ring_fn = jax.jit(comms.run(
                lambda v, i: comms.ring_topk_merge(v, i, k_out,
                                                   shift=shift),
                in_sp, out_sp))
            tree_fn = jax.jit(comms.run(
                lambda v, i: comms.tree_topk_merge(v, i, k_out),
                in_sp, out_sp))
            rv, ri = ring_fn(v_g, i_g)
            tv, ti = tree_fn(v_g, i_g)
            identical = bool(
                np.array_equal(np.asarray(rv), np.asarray(tv))
                and np.array_equal(np.asarray(ri), np.asarray(ti)))
            row["agreement"] = 1.0 if identical else round(
                _overlap(ri, ti), 5)
            row["ring_ms"] = round(time_dispatches(
                lambda: ring_fn(v_g, i_g), iters=5) * 1e3, 2)
            row["tree_ms"] = round(time_dispatches(
                lambda: tree_fn(v_g, i_g), iters=5) * 1e3, 2)
            # the ladder is bit-identical by construction; a mismatch is
            # a kernel bug and must never earn the routing
            row["fused_wins"] = bool(on_tpu and identical
                                     and row["ring_ms"] < row["tree_ms"])
            if not on_tpu:
                row["note"] = "xla ring shift (no TPU): not a verdict"
        except Exception as e:
            row["pallas_error"] = f"{type(e).__name__}: {e}"[:300]
            row["fused_wins"] = False
        art["fused"]["merge_ring"] = row
        print(f"fused merge_ring: {row}", flush=True)
    elif not mergeable:
        print(f"merge_ring: not measurable on {n_dev} device(s), "
              "no row written", flush=True)

    # flat mirror for tools/bench_gate.py (its "metrics" document shape):
    # "<section>.<row>.<field>" → number, so queue runs can diff probe
    # rounds with the noise-aware tolerance band. Bools stay out — a
    # verdict flip is a routing decision, not a regression metric.
    flat = {}

    def _flatten(prefix, d):
        for key, val in d.items():
            if isinstance(val, dict):
                _flatten(f"{prefix}{key}.", val)
            elif isinstance(val, (int, float)) and not isinstance(val, bool):
                flat[f"{prefix}{key}"] = val

    for section in ("fused_l2_argmin", "select_k", "fused"):
        _flatten(f"{section}.", art.get(section, {}))
    art["metrics"] = flat

    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"-> {args.out}")

    if args.require_verdicts:
        missing = missing_verdicts(art, on_tpu, mergeable)
        if missing:
            print(f"pallas_probe: REQUIRED VERDICTS MISSING: {missing} — "
                  "the committed artifact would leave scan_mode/"
                  "merge_mode auto unrouted (or routed on a stale row). "
                  + ("Run this on a TPU host." if not on_tpu else
                     "Fix the errored rows above before committing."),
                  file=sys.stderr)
            sys.exit(2)
        print(f"pallas_probe: all required verdicts present "
              f"({len(REQUIRED_VERDICT_FAMILIES) + int(mergeable)} "
              "families)")


if __name__ == "__main__":
    main()
