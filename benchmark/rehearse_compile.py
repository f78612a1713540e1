#!/usr/bin/env python3
"""Compile each cell's timed programs at their real shapes for a
*described* TPU v5e (no chip), and print what the compiler says of their
memory (the ``on-chip-measurement`` guide, section 2). Run here, before a
chip call:

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py [ivf_pq] \
        [knn[:<rows a chip>,...]]

``ivf_pq``: the decoded-cache search of ``ivfpq-sift1m-*`` at the batch
and at every serving bucket. ``knn``: ``parallel.sharded.knn`` on a 2x2
mesh at 1000 x 96 queries and several rows per chip, to settle how many
rows of DEEP-100M a chip can scan beside the program's workspace.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

GiB = float(1 << 30)


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes"):
        out[f] = getattr(m, f, None)
    return out


def ivf_pq_search(topo, cfg: dict, list_pad: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.ops.distance import DistanceType

    one = SingleDeviceSharding(topo.devices[0])
    ds, ix, sx = cfg["dataset"], cfg["index"], cfg["search"]
    d, L = int(ds["dim"]), int(ix["nlist"])
    rot = -(-d // int(ix["pq_dim"])) * int(ix["pq_dim"])
    P = int(sx["nprobe"])
    f32 = jnp.float32

    def s(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    workspace = int(15.75e9 * 0.25)  # Resources' 25% of a v5e's limit
    q_tile = ivf_pq.plan_cache_tiles(P, list_pad, rot, workspace)
    for nq in (int(sx["batch"]), 8, 16, 32, 64):
        args = (s((nq, d)), s((L, d)), s((rot, d)), s((L, list_pad, rot)),
                s((L, list_pad)), s((L, list_pad), jnp.int32),
                s((L,), jnp.int32), s((0,), jnp.uint32))
        kw = dict(metric=DistanceType.L2Expanded, k=int(sx["k"]),
                  n_probes=P, q_tile=q_tile, has_filter=False,
                  use_pallas=False, pallas_interpret=False,
                  overflow_decoded=s((0, rot)), overflow_norms=s((0,)),
                  overflow_indices=s((0,), jnp.int32), has_overflow=False)
        c = ivf_pq._search_cache_jit.lower(*args, **kw).compile()
        print(json.dumps({"program": "ivf_pq.search cache f32", "nq": nq,
                          "list_pad": list_pad, "q_tile": q_tile,
                          **_mem(c)}), flush=True)


def sharded_knn(topo, rows_per_chip, nq: int = 1000, dim: int = 96,
                k: int = 100) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu.parallel import comms as comms_mod
    from raft_tpu.parallel import sharded

    comms = comms_mod.init_comms(list(topo.devices), axis="data")
    size = comms.size
    for r in rows_per_chip:
        q = jax.ShapeDtypeStruct((nq, dim), jnp.float32,
                                 sharding=NamedSharding(comms.mesh, P()))
        x = jax.ShapeDtypeStruct((r * size, dim), jnp.float32,
                                 sharding=NamedSharding(comms.mesh,
                                                        P("data", None)))
        row = {"program": "sharded.knn", "rows_per_chip": r, "nq": nq,
               "k": k, "chips": size}
        try:
            c = jax.jit(lambda q, x: sharded.knn(comms, q, x, k)).lower(
                q, x).compile()
            row.update(_mem(c))
            row["per_chip_GiB"] = ((row["argument_size_in_bytes"] or 0)
                                   + (row["temp_size_in_bytes"] or 0)) / GiB
            row["collectives"] = sorted({w for w in (
                "all-gather", "all-reduce", "collective-permute",
                "all-to-all", "reduce-scatter", "tpu_custom_call")
                if w in c.as_text()})
        except Exception as e:  # the compiler's refusal is the finding
            row["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        print(json.dumps(row), flush=True)


def main(argv) -> int:
    from jax.experimental import topologies

    from benchmark import harness

    # "knn:<rows>,<rows>" compiles the sharded scan at those rows a chip
    rows = [int(r) for a in argv if a.startswith("knn:")
            for r in a[4:].split(",")]
    what = {a.split(":")[0] for a in argv} or {"ivf_pq", "knn"}
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if "ivf_pq" in what:
        cfg = harness.load_json(os.path.join(
            harness.BENCH, "configs", "sift1m-ivfpq.json"))
        # 1M rows over 1024 lists: the packer pads to the largest list
        # within 1.5x the rows; the chip runs' traces show 1456
        ivf_pq_search(topo, cfg, list_pad=1456)
    if "knn" in what:
        sharded_knn(topo, rows or [25_000_000, 12_500_000, 6_250_000,
                                   3_750_000, 3_125_000])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
