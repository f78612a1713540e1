"""System under test: raft_tpu's exact kNN over a row-sharded collection,
``parallel.sharded.knn`` (local brute-force scan on each chip, top-k,
cross-chip merge), closed loop.

``sharded.knn`` wraps a fresh shard_map closure in ``jax.jit`` on every
call, so each call traces and compiles again. The system jits the public
entry once, with the comms, ``k`` and the merge bound, so that set-up
compiles it and the window holds no compile; the compiled program is
the one ``sharded.knn`` builds.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec

from raft_tpu.parallel import comms as comms_mod
from raft_tpu.parallel import sharded


class System:
    def __init__(self, cfg: dict, shards, devices, annotate):
        self.cfg = cfg
        self.k = int(cfg["search"]["k"])
        self.merge_mode = cfg["search"].get("merge_mode", "auto")
        self.build_s = None
        self.comms = comms_mod.init_comms(list(devices), axis="data")
        n = sum(a.shape[0] for a, _ in shards)
        dim = shards[0][0].shape[1]
        sharding = NamedSharding(self.comms.mesh,
                                 PartitionSpec(self.comms.axis, None))
        by_start = {lo: a for a, lo in shards}
        parts = [by_start[idx[0].start or 0] for _, idx in sorted(
            sharding.addressable_devices_indices_map((n, dim)).items(),
            key=lambda kv: kv[1][0].start or 0)]
        self.x = jax.make_array_from_single_device_arrays(
            (n, dim), sharding, parts)
        self.batches = []
        self._knn = jax.jit(functools.partial(
            sharded.knn, self.comms, k=self.k, merge_mode=self.merge_mode))

    def stage(self, queries, batch: int) -> int:
        n = len(queries) // batch
        self.batches = [self.comms.shard(queries[j * batch:(j + 1) * batch],
                                         PartitionSpec(None, None))
                        for j in range(n)]
        jax.block_until_ready(self.batches)
        jax.block_until_ready(self.call(0))
        return n

    def call(self, j: int):
        return self._knn(self.batches[j], self.x)

    def release(self) -> None:
        self.x = None
        self.batches = []
