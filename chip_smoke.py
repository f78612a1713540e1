#!/usr/bin/env python3
"""Drive the main path once on the chip and check what comes out.

One process, one import of JAX. Default (one chip): raft-ann-bench's
``sift-128-euclidean`` deployment at its published widths — 1M × 128 fp32
base, 10k queries, from ``bench.datagen.low_rank_clusters`` with a fixed
seed (nothing is downloaded) — through the public entry points:

  brute_force → ivf_flat → ivf_pq (scan_mode="auto"), each checked (exact
  ids vs an independent numpy kNN; recall vs the exact result) → the same
  searches with scan_mode="pallas" (the explain record must show the
  compiled Mosaic kernel) → the serving Engine over ivf_pq → a WAL write
  round trip on MutableIvf → the device fence check → cagra over the
  first 250k rows (build, auto, pallas).

``--chips 4`` runs only the sharded MNMG path on a four-chip host: 4M rows,
sharded exact kNN and sharded ivf_pq under every merge mode, against an
exact single-device kNN of the same rows.

This is a check, not a benchmark: the seconds it prints are build and
phase times for orientation, not measurements. Any failed phase makes the
run exit non-zero without the final line. The last line of a passing run
is ``{"ok": true, "device": {...}}`` as JAX reports the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
import traceback

#: recall floors, each the one that family's tests use
#: (tests/test_cagra.py test_search_recall, tests/test_ivf_pq.py, and
#: tests/test_ivf_flat.py's partial-probe floor)
RECALL_FLOOR = {"ivf_flat": 0.9, "ivf_pq": 0.7, "cagra": 0.9}
#: relative distance gap under which two engines may order ids differently
TIE_RTOL = 1e-5
K = 10


@dataclasses.dataclass
class Config:
    rows: int = 1_000_000
    queries: int = 10_000
    dim: int = 128
    seed: int = 0
    n_lists: int = 1024
    n_probes: int = 32
    pq_dim: int = 64
    graph_degree: int = 32
    intermediate_graph_degree: int = 64
    itopk: int = 64
    #: cagra is built over the first 250k rows: its NN-descent build took
    #: 284 s at 250k on one v5e (PR 21), so 1M or 500k would not fit the
    #: run's time limit
    cagra_rows: int = 250_000
    oracle_queries: int = 200
    serve_requests: int = 200
    max_batch: int = 64
    mutable_rows: int = 1000
    kmeans_n_iters: int = 20


def log(*parts) -> None:
    print("chip_smoke:", *parts, flush=True)


class Phases:
    """Runs named phases; a failure is printed and remembered, and the
    run goes on to the phases that do not depend on it."""

    def __init__(self):
        self.failed = []

    def run(self, name, fn) -> None:
        log(f"--- {name}")
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            sys.stdout.flush()
            self.failed.append(name)
            log(f"{name}: FAILED after {time.perf_counter() - t0:.1f}s")
            return
        log(f"{name}: ok in {time.perf_counter() - t0:.1f}s")


def make_data(rows: int, queries: int, dim: int, seed: int):
    """Base and queries from ONE low_rank_clusters draw (the same centers
    and projection), split."""
    import numpy as np

    from raft_tpu.bench.datagen import low_rank_clusters

    x = low_rank_clusters(np.random.default_rng(seed), rows + queries, dim)
    return x[:rows], x[rows:]


def numpy_knn(base, queries, k: int, chunk: int = 131072):
    """Independent exact kNN in float64: (distances, ids) ascending."""
    import numpy as np

    q = queries.astype(np.float64)
    qn = (q * q).sum(1)[:, None]
    best_d = np.zeros((len(q), 0))
    best_i = np.zeros((len(q), 0), np.int64)
    for s in range(0, len(base), chunk):
        b = base[s:s + chunk].astype(np.float64)
        d = np.concatenate([best_d, qn + (b * b).sum(1) - 2.0 * q @ b.T], 1)
        i = np.concatenate(
            [best_i, np.broadcast_to(np.arange(s, s + len(b)),
                                     (len(q), len(b)))], 1)
        top = np.argpartition(d, min(k, d.shape[1]) - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(d, top, 1)
        best_i = np.take_along_axis(i, top, 1)
    order = np.argsort(best_d, 1, kind="stable")
    return (np.take_along_axis(best_d, order, 1),
            np.take_along_axis(best_i, order, 1))


def unexplained_id_diffs(ids, ref_ids, ref_d, dist_of) -> tuple:
    """(differing, unexplained) id counts of ``ids`` vs ``ref_ids``. An id
    absent from the reference row is explained when its distance ties the
    reference's k-th (``dist_of(row, id)`` gives the reference-metric
    distance of any id)."""
    import numpy as np

    differing = unexplained = 0
    for r in range(len(ids)):
        want = set(int(v) for v in ref_ids[r])
        for v in ids[r]:
            if int(v) in want:
                continue
            differing += 1
            kth = float(ref_d[r, -1])
            d = float(dist_of(r, int(v)))
            if not (np.isfinite(d)
                    and abs(d - kth) <= TIE_RTOL * max(abs(kth), 1e-12)):
                unexplained += 1
    return differing, unexplained


def engine_id_diffs(vx, ix, vp, ip) -> tuple:
    """(differing, unexplained) positions between two engines' (dist, id)
    rows: a swap is a tie when the two engines' distances at that rank
    agree within TIE_RTOL."""
    import numpy as np

    vx, ix, vp, ip = (np.asarray(a) for a in (vx, ix, vp, ip))
    diff = ix != ip
    tie = np.abs(vx - vp) <= TIE_RTOL * np.maximum(np.abs(vx), 1e-12)
    in_other = np.array([[ip[r, c] in set(ix[r]) for c in range(ix.shape[1])]
                         for r in range(len(ix))])
    return int(diff.sum()), int((diff & ~tie & ~in_other).sum())


def recall(ids, gt) -> float:
    import numpy as np

    from raft_tpu.stats import neighborhood_recall

    return float(neighborhood_recall(np.asarray(ids), np.asarray(gt)))


def expect_kernel(rec, family: str) -> None:
    """The explain record of a scan_mode="pallas" search: the fused
    kernel ran, forced, compiled (on a chip) — or interpreted off it."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    want_reason = "forced" if on_tpu else "interpret"
    engine = rec.engine
    interp = (rec.plan or {}).get("interpret")
    log(f"{family} pallas explain: engine={engine} reason={rec.reason} "
        f"interpret={interp}")
    if not engine.startswith("pallas"):
        raise AssertionError(f"{family}: engine {engine!r}, not the kernel")
    if rec.reason != want_reason or bool(interp) != (not on_tpu):
        raise AssertionError(
            f"{family}: reason={rec.reason} interpret={interp}, want "
            f"{want_reason} and interpret={not on_tpu}")


def hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


# ------------------------------------------------------------ one chip


def run_single_chip(cfg: Config, phases: Phases) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    base, queries = make_data(cfg.rows, cfg.queries, cfg.dim, cfg.seed)
    log(f"data: base {base.shape} queries {queries.shape} in "
        f"{time.perf_counter() - t0:.1f}s")
    base_d = jax.device_put(base)
    q_d = jax.device_put(queries)
    jax.block_until_ready((base_d, q_d))
    log(f"base on device: {hbm(dev)}")
    st = {}

    def exact():
        st["bf"] = brute_force.build(base_d)
        _, i = brute_force.search(st["bf"], q_d, K)
        st["gt"] = np.asarray(i)
        rows = np.random.default_rng(cfg.seed + 1).choice(
            cfg.queries, min(cfg.oracle_queries, cfg.queries), replace=False)
        od, oi = numpy_knn(base, queries[rows], K)
        qs = queries[rows].astype(np.float64)

        def dist_of(r, v):
            x = base[v].astype(np.float64)
            return float(((qs[r] - x) ** 2).sum())
        diff, bad = unexplained_id_diffs(st["gt"][rows], oi, od, dist_of)
        log(f"brute_force vs numpy oracle ({len(rows)} queries): "
            f"{diff} ids differ, {bad} not at a distance tie")
        if bad:
            raise AssertionError("brute_force ids disagree with numpy")

    def build_flat():
        t = time.perf_counter()
        st["flat"] = ivf_flat.build(base_d, ivf_flat.IndexParams(
            n_lists=cfg.n_lists, kmeans_n_iters=cfg.kmeans_n_iters))
        jax.block_until_ready(st["flat"].list_data)
        log(f"ivf_flat build seconds: {time.perf_counter() - t:.1f}")

    def build_pq():
        t = time.perf_counter()
        st["pq"] = ivf_pq.build(base_d, ivf_pq.IndexParams(
            n_lists=cfg.n_lists, pq_dim=cfg.pq_dim, pq_bits=8,
            kmeans_n_iters=cfg.kmeans_n_iters))
        jax.block_until_ready(st["pq"].list_codes)
        log(f"ivf_pq build seconds: {time.perf_counter() - t:.1f}")

    def searches(mode):
        out = {}
        if "bf" in st:
            out["brute_force"] = brute_force.search(
                st["bf"], q_d, K, scan_mode=mode, explain=True)
        if "flat" in st:
            out["ivf_flat"] = ivf_flat.search(
                st["flat"], q_d, K, ivf_flat.SearchParams(
                    n_probes=cfg.n_probes, scan_mode=mode), explain=True)
        if "pq" in st:
            out["ivf_pq"] = ivf_pq.search(
                st["pq"], q_d, K, ivf_pq.SearchParams(
                    n_probes=cfg.n_probes, scan_mode=mode), explain=True)
        return out

    def auto():
        st["auto"] = searches("auto")
        for fam, (v, i, rec) in st["auto"].items():
            log(f"{fam} auto: engine={rec.engine} reason={rec.reason}")
            if fam == "brute_force":
                continue
            r = recall(i, st["gt"])
            log(f"{fam} recall@{K} (auto): {r:.4f} floor "
                f"{RECALL_FLOOR[fam]}")
            if r < RECALL_FLOOR[fam]:
                raise AssertionError(f"{fam} recall {r} below floor")

    def pallas():
        for fam, (v, i, rec) in searches("pallas").items():
            expect_kernel(rec, fam)
            vx, ix, _ = st["auto"][fam]
            diff, bad = engine_id_diffs(vx, ix, v, i)
            log(f"{fam} pallas vs auto: {diff} ids differ, {bad} not at "
                f"a distance tie")
            if fam != "brute_force":
                log(f"{fam} recall@{K} (pallas): {recall(i, st['gt']):.4f}")
            if bad:
                raise AssertionError(f"{fam}: pallas ids disagree")

    def serve():
        from raft_tpu import serving
        from raft_tpu.serving.engine import compile_count

        searcher = serving.ivf_pq_searcher(
            st["pq"], ivf_pq.SearchParams(n_probes=cfg.n_probes))
        eng = serving.Engine(searcher, serving.EngineConfig(
            max_batch=cfg.max_batch, max_wait_us=2000))
        eng.start()
        try:
            log(f"engine warmup: {eng.warmup_info}")
            c0 = compile_count()
            rng = np.random.default_rng(cfg.seed + 2)
            futs, sent = [], []
            sizes = rng.integers(1, cfg.max_batch + 1, 64)
            for b in sizes:  # bursts of 1..max_batch back-to-back submits
                for _ in range(int(b)):
                    if len(sent) == cfg.serve_requests:
                        break
                    row = int(rng.integers(0, cfg.queries))
                    sent.append(queries[row])
                    futs.append(eng.submit(queries[row], K))
                for f in futs[-int(b):]:
                    f.result(timeout=120)
            results = [f.result(timeout=120) for f in futs]
            compiles = compile_count() - c0
        finally:
            eng.stop()
        buckets = sorted({f.placement[1] for f in futs})
        bad = serving.verify_bit_identity(
            searcher, sent, results, K, [f.placement for f in futs])
        log(f"served {len(futs)} requests over buckets {buckets}: "
            f"{bad} differ from the direct search, {compiles} compiles "
            f"after start()")
        if bad or compiles:
            raise AssertionError("serving mismatch or post-start compile")

    def mutable():
        from raft_tpu.neighbors.mutable import MutableIvf

        rng = np.random.default_rng(cfg.seed + 3)
        new_rows, _ = make_data(cfg.mutable_rows, 0, cfg.dim, cfg.seed + 4)
        doomed = rng.choice(cfg.rows, cfg.mutable_rows, replace=False)
        with tempfile.TemporaryDirectory() as d:
            w = MutableIvf(d, family="ivf_flat", base=st["flat"],
                           search_params=ivf_flat.SearchParams(
                               n_probes=cfg.n_probes))
            try:
                ids = w.add(new_rows)
                w.delete(doomed)
                _, got = w.search(new_rows, K)
                got = np.asarray(got)
                own = int((got[:, 0] == ids).sum())
                _, after = w.search(base[doomed], K)
                resurfaced = int(np.isin(np.asarray(after), doomed).sum())
            finally:
                w.close()
        log(f"mutable: {own}/{len(ids)} inserted rows are their own "
            f"nearest neighbour, {resurfaced} deleted ids came back")
        if own != len(ids) or resurfaced:
            raise AssertionError("mutable write round trip failed")

    def fence():
        n = 8192 if jax.default_backend() == "tpu" else 256
        a = jax.random.normal(jax.random.key(0), (n, n), jnp.bfloat16)
        a = a / jnp.sqrt(jnp.asarray(n, jnp.bfloat16))

        @jax.jit
        def chain(x):
            for _ in range(32):
                x = x @ a
            return x

        jax.block_until_ready(chain(a))
        t = time.perf_counter()
        jax.block_until_ready(chain(a))
        t_bur = time.perf_counter() - t
        t = time.perf_counter()
        np.asarray(chain(a)[:1, :1])
        t_read = time.perf_counter() - t
        log(f"fence: 32 chained {n}x{n} bf16 matmuls: "
            f"block_until_ready {t_bur * 1e3:.2f} ms, readback "
            f"{t_read * 1e3:.2f} ms")

    def build_cagra():
        n = min(cfg.cagra_rows, cfg.rows)
        if n < cfg.rows:
            log(f"cagra rows cut to {n} (of {cfg.rows})")
        t = time.perf_counter()
        st["cagra"] = cagra.build(base_d[:n], cagra.IndexParams(
            graph_degree=cfg.graph_degree,
            intermediate_graph_degree=cfg.intermediate_graph_degree))
        jax.block_until_ready(st["cagra"].graph)
        log(f"cagra build seconds ({n} rows): {time.perf_counter() - t:.1f}")
        if n < cfg.rows:
            _, gi = brute_force.search(brute_force.build(base_d[:n]), q_d, K)
            st["cagra_gt"] = np.asarray(gi)
        else:
            st["cagra_gt"] = st["gt"]

    def search_cagra():
        res = {}
        for mode in ("auto", "pallas"):
            res[mode] = cagra.search(
                st["cagra"], q_d, K, cagra.SearchParams(
                    itopk_size=cfg.itopk, scan_mode=mode), explain=True)
        (vx, ix, rx), (vp, ip, rp) = res["auto"], res["pallas"]
        log(f"cagra auto: engine={rx.engine} reason={rx.reason}")
        r = recall(ix, st["cagra_gt"])
        log(f"cagra recall@{K} (auto): {r:.4f} floor {RECALL_FLOOR['cagra']}")
        expect_kernel(rp, "cagra")
        diff, bad = engine_id_diffs(vx, ix, vp, ip)
        log(f"cagra pallas vs auto: {diff} ids differ, {bad} not at a "
            f"distance tie; recall@{K} (pallas): "
            f"{recall(ip, st['cagra_gt']):.4f}")
        if r < RECALL_FLOOR["cagra"] or bad:
            raise AssertionError("cagra recall or pallas ids")

    phases.run("brute_force exact kNN", exact)
    if "gt" not in st:
        return
    phases.run("ivf_flat build", build_flat)
    phases.run("ivf_pq build", build_pq)
    phases.run("auto searches", auto)
    phases.run("pallas searches", pallas)
    log(f"after searches: {hbm(dev)}")
    if "pq" in st:
        phases.run("serving Engine over ivf_pq", serve)
    if "flat" in st:
        phases.run("MutableIvf write round trip", mutable)
    phases.run("device fence", fence)
    for name in ("flat", "pq", "auto"):
        st.pop(name, None)
    phases.run("cagra build", build_cagra)
    if "cagra" in st:
        phases.run("cagra searches", search_cagra)
    log(f"end: {hbm(dev)}")


# --------------------------------------------------------- four chips


def run_four_chips(cfg: Config, phases: Phases) -> None:
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec

    from raft_tpu.neighbors import brute_force, ivf_pq
    from raft_tpu.parallel import comms as comms_mod
    from raft_tpu.parallel import sharded

    devs = jax.devices()[:4]
    before = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs]
    t0 = time.perf_counter()
    base, queries = make_data(cfg.rows, cfg.queries, cfg.dim, cfg.seed)
    log(f"data: base {base.shape} queries {queries.shape} in "
        f"{time.perf_counter() - t0:.1f}s")
    comms = comms_mod.init_comms(devs, axis="data")
    st = {}

    def exact_one_device():
        x0 = jax.device_put(base, devs[0])
        d, i = brute_force.search(brute_force.build(x0, metric="sqeuclidean"),
                                  jax.device_put(queries, devs[0]), K)
        st["gt_d"], st["gt"] = np.asarray(d), np.asarray(i)
        del x0

    def sharded_knn():
        x = comms.shard(base, PartitionSpec(comms.axis, None))
        shard_devs = {s.device for s in x.addressable_shards}
        log(f"sharded base: {len(x.addressable_shards)} shards on "
            f"{len(shard_devs)} distinct devices")
        if len(shard_devs) != 4:
            raise AssertionError("shards are not on four devices")
        for mode in ("allgather", "tree", "ring"):
            d, i = sharded.knn(comms, queries, x, K, merge_mode=mode)
            diff, bad = engine_id_diffs(st["gt_d"], st["gt"], d, i)
            log(f"sharded knn merge={mode}: {diff} ids differ from the "
                f"single-device exact kNN, {bad} not at a distance tie")
            if bad:
                raise AssertionError(f"sharded knn {mode} ids")
        st["x"] = x  # held, so the memory check below sees it resident

    def sharded_pq():
        t = time.perf_counter()
        idx = sharded.build_ivf_pq(comms, base, ivf_pq.IndexParams(
            n_lists=cfg.n_lists, pq_dim=cfg.pq_dim, pq_bits=8,
            kmeans_n_iters=cfg.kmeans_n_iters))
        log(f"sharded ivf_pq build seconds: {time.perf_counter() - t:.1f}")
        arr = idx.list_decoded if idx.list_decoded is not None \
            else idx.list_codes
        shard_devs = {s.device for s in arr.addressable_shards}
        log(f"sharded ivf_pq lists on {len(shard_devs)} distinct devices")
        if len(shard_devs) != 4:
            raise AssertionError("index shards are not on four devices")
        for mode in ("allgather", "tree", "ring"):
            d, i = sharded.search_ivf_pq(
                idx, queries, K, ivf_pq.SearchParams(n_probes=cfg.n_probes),
                merge_mode=mode)
            r = recall(i, st["gt"])
            log(f"sharded ivf_pq merge={mode}: recall@{K} {r:.4f} floor "
                f"{RECALL_FLOOR['ivf_pq']}")
            if r < RECALL_FLOOR["ivf_pq"]:
                raise AssertionError(f"sharded ivf_pq {mode} recall")
        st["pq"] = idx

    phases.run("exact kNN on one device", exact_one_device)
    if "gt" not in st:
        return
    phases.run("sharded knn", sharded_knn)
    phases.run("sharded ivf_pq", sharded_pq)
    after = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs]
    log(f"bytes_in_use per device before {before} after {after}")
    if jax.default_backend() != "tpu" and not any(after):
        log("this backend reports no device memory; rise not checked")
    elif not all(a > b for a, b in zip(after, before)):
        phases.failed.append("memory rose on every device")
        log("memory did not rise on every device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from raft_tpu.utils.compile_cache import enable_persistent_cache
    except ImportError as e:
        print(f"chip_smoke: the raft_tpu package is not here ({e})",
              file=sys.stderr)
        return 2
    cache_dir = enable_persistent_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"devices: {len(devs)} x {dev.platform} {dev.device_kind}; "
        f"compile cache {cache_dir}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this check "
              "runs only on the chip", file=sys.stderr)
        return 3
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "devices", file=sys.stderr)
        return 3
    phases = Phases()
    if args.chips == 4:
        run_four_chips(Config(rows=4_000_000, queries=1000, seed=args.seed),
                       phases)
    else:
        run_single_chip(Config(seed=args.seed), phases)
    if phases.failed:
        log(f"FAILED phases: {phases.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
