#!/usr/bin/env python
"""autotune — offline operating-point sweep → committed Pareto frontier.

Per ANN family / shape / k, sweeps the speed-recall knob grid (nprobe,
itopk/search_width, select_recall, query bucket) through the PUBLIC
search APIs against an exact numpy oracle, prunes each (family, k,
bucket) curve to its non-dominated QPS-vs-recall frontier, anchors
every surviving point with an obs/costs roofline floor (where chip
peaks are known), and writes ``PARETO_<platform>.json`` — the artifact
``raft_tpu.planner.AdaptivePlanner`` loads and the serving engine
spends latency budgets against (docs/tuning.md "Adaptive planning").

Artifact discipline: schema tag (``raft_tpu.pareto/v1``), flat ``"metrics"`` mirror, refreshed by a
chip run, diffed curve-aware by
``tools/bench_gate.py`` (frontier kind: hypervolume + per-recall-band
QPS, never pointwise).

Modes::

    python tools/autotune.py                     # full grid, all families
    python tools/autotune.py --families ivf_flat cagra
    python tools/autotune.py --mini              # CI-scale tiny grid
    python tools/autotune.py --check PARETO_cpu.json   # round-trip gate

``--check`` loads a committed artifact through the planner's validating
loader and verifies every frontier is monotone non-dominated — the CI
commit-check that a hand-edited or truncated artifact fails loudly.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_artifact(path: str) -> int:
    """Round-trip gate: validating load + frontier invariants."""
    from raft_tpu.planner import adaptive

    try:
        frontier = adaptive.load_frontier(path)
    except (OSError, ValueError) as e:
        print(f"autotune --check: {path}: {e}", file=sys.stderr)
        return 1
    n_curves = n_points = 0
    for family in frontier.families:
        for k in frontier.ks(family):
            doc = frontier.doc["families"][family]["frontier"][str(k)]
            for b_key, raw in doc.items():
                pts = [adaptive.OperatingPoint.from_dict(p) for p in raw]
                pruned = adaptive.pareto_prune(pts)
                if [p.to_dict() for p in pruned] != \
                        [p.to_dict() for p in pts]:
                    print(f"autotune --check: {path}: {family} k={k} "
                          f"b={b_key}: frontier is not a monotone "
                          f"non-dominated curve", file=sys.stderr)
                    return 1
                for p in pts:
                    if p.predicted_ms <= 0 or not 0 <= p.recall <= 1:
                        print(f"autotune --check: {path}: {family} k={k}"
                              f" b={b_key}: bad point {p.to_dict()}",
                              file=sys.stderr)
                        return 1
                n_curves += 1
                n_points += len(pts)
    print(f"autotune --check: {path}: OK — {len(frontier.families)} "
          f"families, {n_curves} curves, {n_points} points")
    return 0


def main(argv=None) -> int:
    from raft_tpu.planner import sweep as planner_sweep

    ap = argparse.ArgumentParser(
        prog="autotune", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--families", nargs="+",
                    default=list(planner_sweep.FAMILIES),
                    choices=list(planner_sweep.FAMILIES))
    ap.add_argument("--rows", type=int, default=10000,
                    help="synthetic db rows (sift-like low-rank "
                         "clusters; default 10000)")
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--nq", type=int, default=256,
                    help="eval query count (recall is over all of them)")
    ap.add_argument("--ks", type=int, nargs="+", default=[10])
    ap.add_argument("--buckets", type=int, nargs="+", default=None,
                    help="query buckets to sweep (default 8 64; "
                         "--mini: 8)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timing repeats per point (best-of)")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--mini", action="store_true",
                    help="CI-scale: tiny grids, fewer eval queries, one "
                         "bucket (rows stay as --rows)")
    ap.add_argument("--out", default=None,
                    help="output path (default PARETO_<platform>.json)")
    ap.add_argument("--check", metavar="PATH", default=None,
                    help="validate a committed artifact and exit")
    args = ap.parse_args(argv)

    if args.check is not None:
        return check_artifact(args.check)

    import jax

    from raft_tpu.bench import datagen

    platform = jax.default_backend()
    out_path = args.out or f"PARETO_{platform}.json"
    rows = args.rows
    nq = min(args.nq, 64) if args.mini else args.nq
    buckets = args.buckets or ([8] if args.mini else [8, 64])

    rng = np.random.default_rng(args.seed)
    db = datagen.low_rank_clusters(rng, rows + nq, args.dim)
    db, queries = db[:rows], db[rows:]

    t0 = time.perf_counter()
    families = {}
    for family in args.families:
        print(f"autotune: sweeping {family} "
              f"(rows={rows} dim={args.dim} ks={args.ks} "
              f"buckets={buckets})...")
        families[family] = planner_sweep.sweep_family(
            family, db, queries, args.ks, buckets, reps=args.reps,
            mini=args.mini, log=lambda m: print(m, flush=True))
    doc = planner_sweep.build_artifact(
        platform, families,
        config={"rows": rows, "dim": args.dim, "nq": nq,
                "ks": list(args.ks), "buckets": list(buckets),
                "reps": args.reps, "seed": args.seed,
                "mini": bool(args.mini)})
    with open(out_path, "w") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    n_points = sum(
        len(pts)
        for fam in families.values()
        for buckets_doc in fam["frontier"].values()
        for pts in buckets_doc.values())
    print(f"autotune: wrote {out_path} — {len(families)} families, "
          f"{n_points} frontier points, "
          f"{time.perf_counter() - t0:.1f} s")
    return check_artifact(out_path)


if __name__ == "__main__":
    sys.exit(main())
