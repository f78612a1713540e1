"""Map lax.top_k's per-(n, k) cost pointwise — the k-pad decision data.

Both round-3 and round-4 select_k sweeps measured a ~50x pathology in
XLA:TPU's top_k at exactly (n=4096, k=10) (112-120 ms for batch 2048,
vs 2.3 ms at k=32 SAME width, vs 1-3 ms at k=10 on WIDER rows). The
reference's answer to select cost is algorithmic (radix vs warpsort,
select_k-inl.cuh:48); on TPU the lowering is the compiler's, so the
lever we have is the *requested* k: top_k(x, k_pad)[:, :k] is exact for
any k_pad >= k (descending-sorted prefix). This probe times top_k over
a fine (n, k) grid to find which (n, k) cells a pad-to-k' rewrite wins,
and emits TOPK_PAD_<platform>.json, which ``raft_tpu.ops.select_k``
loads from the repo root (``_load_pad_rules``) and applies to DIRECT's
requested k at trace time.

Run (TPU): RAFT_TPU_BENCH_PLATFORM=default python tools/topk_k_probe.py
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
    ap.add_argument("--out", default=None)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--widths", type=int, nargs="*",
                    default=[1024, 2048, 4096, 6144, 8192, 16384, 32768])
    # 40 = refine_mult(4) x k(10): the IVF fast-scan merge width's k —
    # rules match k EXACTLY, so the probe must measure the ks searches use
    ap.add_argument("--ks", type=int, nargs="*",
                    default=[4, 8, 10, 12, 16, 24, 32, 40, 48, 64])
    ap.add_argument("--remeasure", action="store_true",
                    help="re-measure requested widths even for (n, k) "
                         "cells already in the artifact (the default "
                         "merge keeps prior cells, so a measurement "
                         "polluted by host contention would otherwise "
                         "be permanent)")
    args = ap.parse_args()

    if os.environ.get("RAFT_TPU_BENCH_PLATFORM") != "default":
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    platform = jax.devices()[0].platform
    out = args.out or f"TOPK_PAD_{platform}.json"
    rng = np.random.default_rng(0)
    # Seed from an existing artifact: every prior row survives in `grid`
    # from the start — including a requested width with an INCOMPLETE k
    # set (ADVICE r4: dropping it meant a rerun killed before reaching
    # that width clobbered its old partial measurements on the next
    # incremental write). Incomplete widths keep their measured ks and
    # only the missing ks are measured (merged in place).
    grid = []
    done_widths = set()
    requested = set(args.widths)
    try:
        with open(out) as f:
            prev = json.load(f)
        if prev.get("platform") == platform:
            for r in prev.get("grid", []):
                if args.remeasure and r.get("n") in requested:
                    r = {"n": r["n"], "ms": {}}
                grid.append(r)
                wanted = {str(k) for k in args.ks if k * 4 <= r.get("n", 0)}
                if r.get("n") in requested and wanted <= set(r.get("ms", {})):
                    # resume: this width already has every requested k —
                    # don't re-pay its ~per-k compile minutes
                    done_widths.add(r["n"])
            if grid:
                print(f"seeded {len(grid)} rows from existing {out} "
                      f"(resume skips widths {sorted(done_widths)})")
    except (OSError, ValueError, KeyError, TypeError):
        pass

    def extract_rules():
        """For each (n, k) cell, the best strictly-larger measured k'
        with ms[k'] < ms[k] / 2 (pad only for a decisive win — a 2x bar
        keeps noise from flapping the default). select_k matches rules
        by exact k and nearby width at trace time."""
        rules = []
        for row in grid:
            ms = {int(k): v for k, v in row["ms"].items()}
            ks = sorted(ms)
            for k in ks:
                better = [(ms[kp], kp) for kp in ks if kp > k
                          and ms[kp] < ms[k] / 2]
                if better:
                    best = min(better)
                    rules.append({"n": row["n"], "k": k, "k_pad": best[1],
                                  "ms": ms[k], "ms_pad": best[0]})
        return rules

    def write(partial):
        """Per-width incremental write: a timeout kill keeps the measured
        widths. pad_rules are per-width facts (no cross-width dependency,
        unlike select_k_bench's sticky crossovers), so a partial artifact
        is safe to arm — rules for unmeasured widths simply don't fire."""
        art = {"platform": platform, "batch": args.batch, "grid": grid,
               "pad_rules": extract_rules(),
               "when": time.strftime("%Y-%m-%dT%H:%M:%S%z")}
        if partial:
            art["partial"] = True
        # atomic replace: select_k._load_pad_rules globs this file from
        # other processes; a torn in-place write would read as malformed
        # JSON and silently arm zero rules
        with open(out + ".tmp", "w") as f:
            json.dump(art, f, indent=1)
        os.replace(out + ".tmp", out)
        return art

    for n in args.widths:
        if n in done_widths:
            continue
        x = jax.numpy.asarray(
            rng.standard_normal((args.batch, n)).astype(np.float32))
        row = next((r for r in grid if r.get("n") == n), None)
        if row is None:
            row = {"n": n, "ms": {}}
            grid.append(row)
        for k in args.ks:
            if k * 4 > n:
                continue
            if str(k) in row["ms"]:
                continue  # measured by a prior partial run: merge, not redo
            f = jax.jit(lambda v, kk=k: jax.lax.top_k(v, kk))
            dt = time_dispatches(lambda: f(x), iters=args.iters)
            row["ms"][str(k)] = round(dt * 1e3, 3)
            write(partial=True)  # per-k: a kill keeps every measured cell
        print(row, flush=True)

    art = write(partial=False)
    print(f"-> {out}\nrules: {art['pad_rules']}")


if __name__ == "__main__":
    main()
