"""Measure select_k algorithm crossovers at IVF-critical shapes.

VERDICT r2 #6: AUTO's DIRECT/TWO_PHASE decision must come from
measurement, not the old hardcoded 65536. This sweeps batch-2048 rows
(the IVF probe-merge shape: [q_tile, n_probes·list_pad]) across widths
and k ∈ {10, 32, 64, 128, 256} on whatever backend is active, times
DIRECT vs TWO_PHASE vs APPROX, and writes:

  - a full timing grid, and
  - the per-k-band crossover widths in the format of
    ``raft_tpu.ops.select_k``'s ``_BUILTIN_TABLES`` (and
    ``set_auto_table``), for a PR to write into that table.

Run on TPU (a chip run): RAFT_TPU_BENCH_PLATFORM=default
  python tools/select_k_bench.py --out select_k_tpu.json
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_tpu.bench.timing import time_dispatches  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="select_k_crossovers.json")
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--widths", type=int, nargs="*",
                    default=[4096, 16384, 32768, 65536, 131072, 262144])
    ap.add_argument("--ks", type=int, nargs="*",
                    default=[10, 32, 64, 128, 256])
    args = ap.parse_args()

    if os.environ.get("RAFT_TPU_BENCH_PLATFORM") != "default":
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    from raft_tpu.ops.select_k import SelectAlgo, select_k

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0)
    grid = []
    algos = [SelectAlgo.DIRECT, SelectAlgo.TWO_PHASE, SelectAlgo.APPROX]

    def write(partial, **extra):
        """Write the artifact after every row: a timeout kill mid-sweep
        keeps the completed rows (minutes of compiles each).
        ``crossovers`` (in ``extra``) is only present once the grid is
        COMPLETE: sticky_crossover over a width-truncated grid could claim
        wins the missing wider rows would refute."""
        art = {"platform": platform, "batch": args.batch, "grid": grid,
               "when": time.strftime("%Y-%m-%dT%H:%M:%S%z"), **extra}
        if partial:
            art["partial"] = True
        with open(args.out + (".partial" if partial else ""), "w") as f:
            json.dump(art, f, indent=1)

    for n in args.widths:
        x = jax.numpy.asarray(
            rng.standard_normal((args.batch, n)).astype(np.float32))
        for k in args.ks:
            if k * 4 > n:
                continue
            row = {"n": n, "k": k}
            for algo in algos:
                dt = time_dispatches(lambda: select_k(x, k, algo=algo),
                                     iters=args.iters)
                row[algo.value + "_ms"] = round(dt * 1e3, 3)
            grid.append(row)
            print(row, flush=True)
            write(partial=True)

    def sticky_crossover(col):
        """Per-k smallest width where ``col`` beats DIRECT and keeps
        beating it at every larger measured width."""
        by_k = {}
        for k in args.ks:
            rows = [r for r in grid if r["k"] == k and col in r]
            cross = None
            for r in sorted(rows, key=lambda r: r["n"]):
                wins = r[col] < r["direct_ms"]
                if wins and cross is None:
                    cross = r["n"]
                if not wins:
                    cross = None  # must win from here up
            by_k[k] = cross
        return by_k

    def band(by_k):
        """Band per-k crossovers into the AUTO-table format
        (k_max -> width), or None when the algo never wins. A band is
        emitted only when EVERY measured k inside it won, at the widest
        (most conservative) of their crossovers — a win at one k must
        not extend to a k the sweep measured as a loss (or never
        measured): the "inf" band therefore needs the largest measured
        k to have won."""
        out = {}
        small = [c for k, c in by_k.items() if k <= 32]
        mid = [c for k, c in by_k.items() if 32 < k <= 256]
        if small and all(small):
            out["32"] = max(small)
        if mid and all(mid):
            out["256"] = max(mid)
        k_top = max(by_k)
        if by_k.get(k_top):
            out["inf"] = by_k[k_top]
        return out or None

    crossover_by_k = sticky_crossover("two_phase_ms")
    bands = band(crossover_by_k) or {"inf": 1 << 62}
    write(partial=False, crossover_by_k=crossover_by_k, crossovers=bands)
    if os.path.exists(args.out + ".partial"):
        os.remove(args.out + ".partial")
    print(f"-> {args.out}\ncrossovers: {bands}")


if __name__ == "__main__":
    main()
