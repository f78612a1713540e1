"""Decompose small-batch search latency: dispatch overhead vs on-chip time
(VERDICT r3 #6 — "kill the batch-1 latency mystery").

Method: three measurements per (index, batch) point, all RTT-amortized
via raft_tpu.bench.timing:

- ``chained_ms``: per-call latency of N host-dispatched searches
  serialized by a data dependency (the existing latency mode). Includes
  whatever per-dispatch cost the host/runtime adds.
- ``onchip_ms``: per-iteration time of the SAME chained computation run
  entirely inside one jit as a ``lax.fori_loop`` — zero host dispatches,
  so this is pure device execution.
- ``dispatch_ms`` = chained_ms − onchip_ms: the per-call overhead that is
  NOT device compute (host tracing/cache lookup, runtime enqueue,
  readback). The reference's latency mode (raft_ann_benchmarks.md:154) is the
  comparison point.

Also records per-bucket jit compile time (cold) so compile-cache misses
can't masquerade as dispatch overhead. Artifact: LATENCY_TPU.json, plus
a span JSONL (``<out>.spans.jsonl``, docs/observability.md) with one
``build`` record per index and one ``latency_point`` record per
(index, batch) measurement — the same schema ``obs.spans.read_jsonl``
and tools/serving_bench.py consume, so profile runs land in the same
trace tooling as serving runs. ``--spans ''`` disables.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="LATENCY_TPU.json")
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=96)
    ap.add_argument("--batches", type=int, nargs="*", default=[1, 10, 100])
    ap.add_argument("--fori-iters", type=int, default=64)
    ap.add_argument("--spans", default=None,
                    help="span JSONL path (default <out>.spans.jsonl; "
                         "'' disables)")
    args = ap.parse_args()

    if os.environ.get("RAFT_TPU_BENCH_PLATFORM", "default") != "default":
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    from raft_tpu.bench import timing
    from raft_tpu.neighbors import ivf_flat, ivf_pq
    from raft_tpu.obs import spans as obs_spans

    platform = jax.devices()[0].platform
    spans_path = args.spans if args.spans is not None \
        else args.out + ".spans.jsonl"
    # timed_span tolerates sink=None, so '' just turns emission off
    sink = obs_spans.JsonlSink(spans_path) if spans_path else None
    rng = np.random.default_rng(0)
    base = rng.standard_normal((args.rows, args.dim)).astype(np.float32)

    print(f"platform={platform}; building indexes on {args.rows}x{args.dim}",
          flush=True)
    t0 = time.perf_counter()
    with obs_spans.timed_span(sink, "build", index="ivf_flat"):
        flat = ivf_flat.build(base, ivf_flat.IndexParams(n_lists=1024))
        timing.fence_index(flat)
    with obs_spans.timed_span(sink, "build", index="ivf_pq"):
        pq = ivf_pq.build(base, ivf_pq.IndexParams(n_lists=1024, pq_dim=48))
        timing.fence_index(pq)
    print(f"builds done in {time.perf_counter() - t0:.1f}s", flush=True)

    searchers = {
        "ivf_flat": lambda q: ivf_flat.search(
            flat, q, 10, ivf_flat.SearchParams(n_probes=16)),
        "ivf_pq": lambda q: ivf_pq.search(
            pq, q, 10, ivf_pq.SearchParams(n_probes=16)),
    }
    try:
        from raft_tpu.neighbors import cagra

        cag = cagra.build(base, cagra.IndexParams(graph_degree=32))
        timing.fence_index(cag)
        searchers["cagra"] = lambda q: cagra.search(
            cag, q, 10, cagra.SearchParams(itopk_size=64))
    except Exception as e:  # cagra build OOM etc.: profile the IVFs anyway
        print(f"cagra skipped: {e!r}", flush=True)

    results = []
    for name, fn in searchers.items():
        for b in args.batches:
            q0 = timing.prepare(
                rng.standard_normal((b, args.dim)).astype(np.float32))
            row = {"index": name, "batch": b}

            with obs_spans.timed_span(sink, "latency_point",
                                      index=name, batch=b) as span:
                # cold compile cost for this bucket (first trace+compile)
                t0 = time.perf_counter()
                timing.fence(fn(q0))
                row["cold_ms"] = round((time.perf_counter() - t0) * 1e3, 2)

                step = lambda q: timing.chain_perturb(q0, fn(q))  # noqa: E731
                row["chained_ms"] = round(
                    timing.time_latency_chained(step, q0, iters=16) * 1e3, 3)

                # pure on-chip: same chain inside ONE jit (no host dispatch)
                try:
                    n_it = args.fori_iters

                    @jax.jit
                    def fori(q0_, n=n_it, f=fn):
                        def body(_, q):
                            return timing.chain_perturb(q0_, f(q))

                        return jax.lax.fori_loop(0, n, body, q0_)

                    timing.fence(fori(q0))  # compile
                    dt = timing.time_dispatches(lambda: fori(q0), iters=2)
                    row["onchip_ms"] = round(dt / n_it * 1e3, 3)
                    row["dispatch_ms"] = round(
                        row["chained_ms"] - row["onchip_ms"], 3)
                except Exception as e:  # not traceable inside fori
                    row["onchip_error"] = repr(e)[:200]
                span.update(row)
            results.append(row)
            print(row, flush=True)

    art = {"platform": platform, "rows": args.rows, "dim": args.dim,
           "results": results,
           "when": time.strftime("%Y-%m-%dT%H:%M:%S%z")}
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    if sink is not None:
        sink.close()
        print(f"-> {spans_path}")
    print(f"-> {args.out}")


if __name__ == "__main__":
    main()
