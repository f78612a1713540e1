"""System under test: raft_tpu's IVF-Flat on one chip — ``ivf_flat.build``
and ``ivf_flat.search`` on staged device batches (closed loop only).

The configuration's ``index`` and ``search`` keys use raft-ann-bench's
names. ``search.scan_dtype`` absent scans the float32 lists at float32;
``"bf16"`` (raft-ann-bench's word, :data:`DTYPES`) takes the bfloat16
screen with its float32 re-rank.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from raft_tpu.bench.timing import fence_index
from raft_tpu.neighbors import ivf_flat

DTYPES = {"bf16": "bfloat16"}


def _params(cfg: dict):
    ix, sx = cfg["index"], cfg["search"]
    build = ivf_flat.IndexParams(n_lists=int(ix["nlist"]),
                                 kmeans_n_iters=int(ix["niter"]))
    scan = sx.get("scan_dtype")
    search = ivf_flat.SearchParams(
        n_probes=int(sx["nprobe"]), scan_mode=sx["scan_mode"],
        scan_dtype=None if scan is None else jnp.dtype(DTYPES[scan]))
    return build, search


class System:
    def __init__(self, cfg: dict, shards, devices, annotate):
        if len(shards) != 1:
            raise ValueError("ivf_flat runs on one chip")
        self.k = int(cfg["search"]["k"])
        self.device = devices[0]
        self.build_params, self.search_params = _params(cfg)
        base = shards[0][0]
        with annotate("bench.build"):
            t = time.perf_counter()
            self.index = ivf_flat.build(base, self.build_params)
            fence_index(self.index)
            self.build_s = time.perf_counter() - t
        self.batches = []

    def stage(self, queries, batch: int) -> int:
        n = len(queries) // batch
        self.batches = [jax.device_put(queries[j * batch:(j + 1) * batch],
                                       self.device) for j in range(n)]
        jax.block_until_ready(self.batches)
        for j in range(n):  # warm the shape (and the cached row norms)
            jax.block_until_ready(self.call(j))
        return n

    def call(self, j: int):
        return ivf_flat.search(self.index, self.batches[j], self.k,
                               self.search_params)

    def release(self) -> None:
        self.index = None
        self.batches = []
