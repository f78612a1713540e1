"""Explain one query end-to-end: which engine served it, and why.

Builds a small synthetic index per requested family, runs
``search(..., explain=True)``, and pretty-prints the resulting
:class:`raft_tpu.obs.ExplainRecord` — requested vs resolved scan mode,
the reason code (docs/observability.md "Reason vocabulary"), the
planner's tile choices and predicted workspace bytes, and the select_k
resolution note. Finishes with the process's
``raft_tpu_dispatch_total`` histogram so repeated runs show routing
drift at a glance.

This is the triage entry point for "why is my query slow / on XLA":
run it on the same host (TPU or CPU) with the same scan_mode and read
the reason line. ``fused_unmeasured`` on TPU means ``auto`` took XLA
because no fused kernel has a chip measurement beating it
(``ops/pallas_kernels.py``).

Usage: python tools/explain.py [--family all] [--n 4096] [--dim 64]
       [--k 10] [--scan-mode auto] [--out explain.json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

FAMILIES = ("brute_force", "ivf_flat", "ivf_pq", "cagra")


def _build_and_explain(family: str, n: int, dim: int, k: int,
                       scan_mode: str, seed: int = 0):
    """(ExplainRecord, result shapes) for one family on synthetic data."""
    rng = np.random.default_rng(seed)
    db = rng.standard_normal((n, dim), dtype=np.float32)
    q = rng.standard_normal((8, dim), dtype=np.float32)
    if family == "brute_force":
        from raft_tpu.neighbors import brute_force as m

        idx = m.build(db)
        v, i, rec = m.search(idx, q, k, scan_mode=scan_mode, explain=True)
    elif family == "ivf_flat":
        from raft_tpu.neighbors import ivf_flat as m

        idx = m.build(db, m.IndexParams(n_lists=32))
        v, i, rec = m.search(idx, q, k,
                             m.SearchParams(scan_mode=scan_mode),
                             explain=True)
    elif family == "ivf_pq":
        from raft_tpu.neighbors import ivf_pq as m

        idx = m.build(db, m.IndexParams(n_lists=32, pq_dim=dim // 4))
        v, i, rec = m.search(idx, q, k,
                             m.SearchParams(scan_mode=scan_mode),
                             explain=True)
    elif family == "cagra":
        from raft_tpu.neighbors import cagra as m

        idx = m.build(db, m.IndexParams(graph_degree=16))
        v, i, rec = m.search(idx, q, k, explain=True)
    else:
        raise SystemExit(f"unknown family {family!r}")
    return rec, tuple(np.asarray(i).shape)


def _print_record(rec, shape) -> None:
    print(f"  requested scan_mode : {rec.requested}")
    print(f"  resolved engine     : {rec.engine}")
    print(f"  reason              : {rec.reason}")
    for label, d in (("params", rec.params), ("plan", rec.plan)):
        if d:
            body = ", ".join(f"{k}={v}" for k, v in sorted(d.items()))
            print(f"  {label:<20}: {body}")
    for note in rec.notes:
        body = ", ".join(f"{k}={v}" for k, v in sorted(note.items()))
        print(f"  note                : {body}")
    print(f"  result ids shape    : {shape}")


def main():
    ap = argparse.ArgumentParser(
        description="pretty-print one query's execution-plan attribution")
    ap.add_argument("--family", default="all",
                    choices=FAMILIES + ("all",))
    ap.add_argument("--n", type=int, default=4096,
                    help="synthetic database rows")
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--scan-mode", default="auto",
                    help="auto | pallas | xla (family-specific values "
                    "like cache/lut pass through to ivf_pq)")
    ap.add_argument("--out", default=None,
                    help="also write the records as JSON")
    args = ap.parse_args()

    import jax

    from raft_tpu.obs import explain as obs_explain
    from raft_tpu.ops.select_k import select_k_plan

    backend = jax.default_backend()
    print(f"backend={backend}  n={args.n}  dim={args.dim}  k={args.k}  "
          f"scan_mode={args.scan_mode}")
    families = FAMILIES if args.family == "all" else (args.family,)
    doc = {"backend": backend, "scan_mode": args.scan_mode,
           "records": {}}
    for family in families:
        print(f"\n[{family}]")
        rec, shape = _build_and_explain(
            family, args.n, args.dim, args.k, args.scan_mode)
        _print_record(rec, shape)
        doc["records"][family] = rec.to_dict()

    plan = select_k_plan(args.n, args.k)
    print(f"\n[select_k] n={args.n} k={args.k} -> algo={plan['algo']} "
          f"k_pad={plan['k_pad']}")
    doc["select_k_plan"] = plan

    counts = obs_explain.dispatch_counts()
    print("\nraft_tpu_dispatch_total (this process):")
    for (family, engine, reason), cnt in sorted(counts.items()):
        print(f"  {family:<12} {engine:<12} {reason:<22} {cnt}")
    doc["dispatch_total"] = {"/".join(k): v for k, v in counts.items()}

    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
