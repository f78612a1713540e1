"""Comms — the distributed communication facade over XLA mesh collectives.

Reference: ``raft::comms_t`` (core/comms.hpp:127-661 — virtual comms_iface
with allreduce/bcast/reduce/allgather/gather/reducescatter, device p2p
send/recv, comm_split, sync_stream), its NCCL+UCX implementation
(comms/detail/std_comms.hpp:314-422), the MPI variant (comms/mpi_comms.hpp),
and the Dask bootstrap that injects ``std_comms`` into each worker's handle
(raft_dask/common/comms.py:40).

TPU-native design: the backend is the compiler, not a library. A ``Comms``
object wraps a ``jax.sharding.Mesh`` axis; its collective methods are called
**inside ``shard_map``-decorated functions** and lower to XLA collectives
that ride ICI (intra-pod) / DCN (multi-pod) — psum/all_gather/ppermute do
what ncclAllReduce/ncclAllGather/ncclSend+Recv do, but fused and scheduled
by XLA. The bootstrap role of Dask+NCCL uniqueId rendezvous
(comms.py:138-151) is played by ``jax.distributed.initialize`` +
``jax.devices()`` — ``init_comms`` wraps both the single-process multi-device
case (including the CPU-simulated mesh used in CI — the "mock backend" seam
SURVEY.md §4 calls for) and the true multi-host case.

The reference's ``comms_t`` is injected into ``resources``; ``inject_comms``
mirrors that so algorithms take one ``res`` and find the communicator.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.core.resources import Resources


# ------------------------------------------------------------------ datatypes


class ReduceOp:
    """reference: core/comms.hpp op_t (SUM/PROD/MIN/MAX)."""

    SUM = "sum"
    PROD = "prod"
    MIN = "min"
    MAX = "max"


def _lex_topk(v, pos, i, k: int, select_min: bool):
    """The ``k`` lexicographically-smallest (value, pos) candidates per row,
    sorted — the tie rule ``select_k``'s stable engines implement, made
    explicit so partial merges compose in any order. ``pos`` is each
    candidate's position in the virtual rank-order concatenation (unique,
    so the sort key is a total order and stability is moot)."""
    key = v if select_min else -v
    sv, sp, si = jax.lax.sort((key, pos, i), dimension=1, num_keys=2)
    sv, sp, si = sv[:, :k], sp[:, :k], si[:, :k]
    return (sv if select_min else -sv), sp, si


@dataclasses.dataclass(frozen=True)
class Comms:
    """A communicator = a mesh + the axis it communicates over.

    ``size``/``rank`` mirror comms_t::get_size/get_rank (core/comms.hpp:252).
    The collective methods are *traceable* — call them inside a function run
    via :meth:`run` (shard_map) or your own shard_map/pjit.
    """

    mesh: Mesh
    axis: str = "data"

    # ---- topology ---------------------------------------------------------
    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]

    def rank(self) -> jax.Array:
        """Per-shard rank — traced value, valid inside shard_map (analog of
        get_rank, core/comms.hpp:257)."""
        return jax.lax.axis_index(self.axis)

    # ---- collectives (traceable; inside shard_map) ------------------------
    def allreduce(self, x, op: str = ReduceOp.SUM):
        """ncclAllReduce analog (std_comms.hpp:314) → psum/pmax/pmin lowered
        onto ICI."""
        if op == ReduceOp.SUM:
            return jax.lax.psum(x, self.axis)
        if op == ReduceOp.MAX:
            return jax.lax.pmax(x, self.axis)
        if op == ReduceOp.MIN:
            return jax.lax.pmin(x, self.axis)
        if op == ReduceOp.PROD:
            # gather + prod: exact for zeros/negatives (a log-psum trick
            # would NaN); PROD traffic is rare so the extra bytes are fine
            g = jax.lax.all_gather(x, self.axis)
            return jax.tree.map(lambda a: jnp.prod(a, axis=0), g)
        raise ValueError(f"unknown reduce op {op!r}")

    def allgather(self, x, axis: int = 0, tiled: bool = True):
        """ncclAllGather analog (std_comms.hpp:~360): concatenate shards
        along ``axis``."""
        return jax.lax.all_gather(x, self.axis, axis=axis, tiled=tiled)

    def reducescatter(self, x, scatter_dimension: int = 0):
        """ncclReduceScatter analog: sum across ranks, scatter along dim."""
        return jax.lax.psum_scatter(
            x, self.axis, scatter_dimension=scatter_dimension, tiled=True)

    def bcast(self, x, root: int = 0):
        """ncclBroadcast analog: every rank gets root's value. On a mesh the
        value is materialized on all ranks already; select root's shard."""
        gathered = jax.lax.all_gather(x, self.axis)
        return jax.tree.map(lambda g: g[root], gathered)

    def reduce(self, x, root: int = 0, op: str = ReduceOp.SUM):
        """ncclReduce analog: full reduction, non-root ranks get zeros (the
        typed comms_t contract only defines the root's value)."""
        full = self.allreduce(x, op)
        is_root = jax.lax.axis_index(self.axis) == root
        return jax.tree.map(lambda f: jnp.where(is_root, f, jnp.zeros_like(f)),
                            full)

    def gather(self, x, root: int = 0):
        """ncclGather analog — allgather then non-root zeroing (XLA has no
        rooted gather; the extra ICI traffic is negligible vs the fusion
        win)."""
        g = jax.lax.all_gather(x, self.axis)
        is_root = jax.lax.axis_index(self.axis) == root
        return jax.tree.map(lambda f: jnp.where(is_root, f, jnp.zeros_like(f)),
                            g)

    def allgatherv(self, x, counts: Sequence[int], axis: int = 0):
        """ncclAllGatherv-equivalent (core/comms.hpp allgatherv): shards
        contribute ``counts[rank]`` valid rows each (the rest of the static
        shard is padding). Returns the concatenation of every rank's valid
        rows, padded to sum(counts) with trailing zeros removed by the
        caller if needed. ``counts`` must be host-known (static shapes)."""
        counts = [int(c) for c in counts]
        cap = x.shape[axis]
        if max(counts) > cap:
            raise ValueError(f"counts {counts} exceed shard capacity {cap}")
        g = jax.lax.all_gather(x, self.axis)  # [size, ...]
        parts = [jax.lax.index_in_dim(g, r, axis=0, keepdims=False)
                 for r in range(self.size)]
        parts = [jax.lax.slice_in_dim(p, 0, counts[r], axis=axis)
                 for r, p in enumerate(parts)]
        return jnp.concatenate(parts, axis=axis)

    def gatherv(self, x, counts: Sequence[int], root: int = 0,
                axis: int = 0):
        """ncclGatherv analog: allgatherv, non-root ranks zeroed (the typed
        comms_t contract defines only the root's value)."""
        full = self.allgatherv(x, counts, axis=axis)
        is_root = jax.lax.axis_index(self.axis) == root
        return jax.tree.map(
            lambda f: jnp.where(is_root, f, jnp.zeros_like(f)), full)

    def device_send_recv(self, x, dest_of_rank: Sequence[int]):
        """device_sendrecv analog (core/comms.hpp device p2p): rank r's value
        is delivered to ``dest_of_rank[r]``; every rank receives from the
        rank that names it. The table must be a permutation (XLA ppermute
        contract — matching pairwise send/recv like the reference's
        group_start/end blocks)."""
        dests = [int(d) for d in dest_of_rank]
        if sorted(dests) != list(range(self.size)):
            raise ValueError(f"dest table {dests} is not a permutation")
        return jax.lax.ppermute(x, self.axis,
                                perm=[(r, d) for r, d in enumerate(dests)])

    def device_multicast_sendrecv(self, x, root: int, dests: Sequence[int]):
        """device_multicast_sendrecv analog: ``root``'s value is delivered to
        every rank in ``dests``; other ranks keep their own value (multicast
        over ICI is an allgather+select the compiler prunes)."""
        g = jax.lax.all_gather(x, self.axis)  # [size, ...]
        me = jax.lax.axis_index(self.axis)
        in_dests = jnp.zeros((self.size,), bool
                             ).at[jnp.asarray(list(dests))].set(True)[me]
        return jax.tree.map(
            lambda gg: jnp.where(in_dests, gg[root], gg[me]), g)

    def ppermute(self, x, perm: Sequence[tuple[int, int]]):
        """device_sendrecv analog (core/comms.hpp device p2p): point-to-point
        pairs (src, dst) as one fused ICI permute."""
        return jax.lax.ppermute(x, self.axis, perm=list(perm))

    def shift(self, x, offset: int = 1):
        """Ring shift by ``offset`` — the p2p pattern ring algorithms use."""
        n = self.size
        perm = [(i, (i + offset) % n) for i in range(n)]
        return jax.lax.ppermute(x, self.axis, perm=perm)

    def alltoall(self, x):
        """ncclAllToAll analog: x [size, ...] per rank → transpose across
        ranks (used by all-to-all sequence/context parallelism)."""
        return jax.lax.all_to_all(x, self.axis, split_axis=0, concat_axis=0,
                                  tiled=False)

    # ---- streaming cross-chip top-k merge (traceable; inside shard_map) ----
    #
    # The MNMG merge (knn_merge_parts across ranks) without the all_gather
    # slab: candidates are tagged with their position in the virtual
    # rank-order concatenation and merged by lexicographic (value, pos)
    # selection. Stable-by-position selection is associative AND
    # commutative over candidate sets, so any merge order — hypercube
    # tree, neighbor ring — produces the identical replicated output, and
    # that output is bit-identical to ``select_k(allgather(v), k)`` + id
    # gather (select_k's engines are all position-stable on ties: DIRECT
    # is lax.top_k, TWO_PHASE merges tile-ordered survivors). Peak cross-chip bytes drop from S·nq·kk to
    # nq·k·log₂S (tree) / nq·kk per step (ring).

    def tree_topk_merge(self, v, i, k: int, select_min: bool = True):
        """Hypercube top-k merge in log₂(size) ``ppermute`` rounds.

        ``v``/``i`` are this shard's [nq, kk] candidates (ids global;
        invalid candidates must already carry ±inf values). Each round
        exchanges carries with the rank's XOR partner and re-selects down
        to ``min(k, candidates_so_far)`` — live candidate sets halve each
        round while per-device carry bytes stay O(nq·k). Requires a
        power-of-two ``size`` (the dispatch layer falls back to
        all_gather otherwise). Returns replicated (values, ids) of width
        ``min(k, size·kk)``, bit-identical to the all_gather merge."""
        size = self.size
        if size & (size - 1):
            raise ValueError(f"tree merge needs a power-of-two mesh axis, "
                             f"got size={size}")
        nq, kk = v.shape
        k_out = min(int(k), size * kk)
        pos0 = self.rank() * kk + jnp.arange(kk, dtype=jnp.int32)
        cv, cp, ci = v, jnp.broadcast_to(pos0[None, :], (nq, kk)), i
        width = kk
        step = 1
        while step < size:
            perm = [(r, r ^ step) for r in range(size)]
            pv = self.ppermute(cv, perm)
            pp = self.ppermute(cp, perm)
            pi = self.ppermute(ci, perm)
            width = min(k_out, 2 * width)
            cv, cp, ci = _lex_topk(
                jnp.concatenate([cv, pv], axis=1),
                jnp.concatenate([cp, pp], axis=1),
                jnp.concatenate([ci, pi], axis=1), width, select_min)
            step *= 2
        if size == 1:  # no rounds ran: still honor the sort+truncate contract
            cv, cp, ci = _lex_topk(cv, cp, ci, k_out, select_min)
        return cv, ci

    def ring_topk_merge(self, v, i, k: int, select_min: bool = True,
                        shift=None):
        """Neighbor-ring top-k merge: size-1 steps, each rotating the
        ORIGINAL [nq, kk] candidate block one hop while folding the block
        received last step into the local carry — the streaming schedule
        whose per-step traffic (one fixed-shape block to one neighbor) a
        ``make_async_remote_copy`` kernel can overlap with the local
        probe-tile scan. ``shift`` maps one packed [3, nq, kk] f32 buffer
        to its +1 ring rotation (default: XLA ``ppermute``; the Pallas
        RDMA kernel slots in here). Works for any ``size``. Returns
        replicated (values, ids) of width ``min(k, size·kk)``,
        bit-identical to the all_gather merge (the lex merge is
        commutative, so per-device rotation order doesn't matter)."""
        size = self.size
        nq, kk = v.shape
        k_out = min(int(k), size * kk)
        if shift is None:
            shift = functools.partial(self.shift, offset=1)
        if v.dtype != jnp.float32:
            raise ValueError(f"ring merge packs candidates as float32 "
                             f"words, got values dtype {v.dtype}")
        pos0 = self.rank() * kk + jnp.arange(kk, dtype=jnp.int32)
        pos = jnp.broadcast_to(pos0[None, :], (nq, kk))
        block = jnp.stack([
            v, jax.lax.bitcast_convert_type(pos, jnp.float32),
            jax.lax.bitcast_convert_type(i.astype(jnp.int32), jnp.float32)])
        cv, cp, ci = _lex_topk(v, pos, i, min(k_out, kk), select_min)
        for s in range(size - 1):
            block = shift(block)
            bv = block[0]
            bp = jax.lax.bitcast_convert_type(block[1], jnp.int32)
            bi = jax.lax.bitcast_convert_type(block[2], jnp.int32)
            cv, cp, ci = _lex_topk(
                jnp.concatenate([cv, bv], axis=1),
                jnp.concatenate([cp, bp], axis=1),
                jnp.concatenate([ci, bi], axis=1),
                min(k_out, (s + 2) * kk), select_min)
        return cv, ci

    # ---- split ------------------------------------------------------------
    def comm_split(self, color_axis: str) -> "Comms":
        """comms_t::comm_split analog (std_comms.hpp:156-162): a communicator
        over another mesh axis (the mesh factorization IS the color/key)."""
        if color_axis not in self.mesh.axis_names:
            raise ValueError(f"axis {color_axis!r} not in mesh "
                             f"{self.mesh.axis_names}")
        return Comms(self.mesh, color_axis)

    # ---- host-side helpers -------------------------------------------------
    def run(self, fn: Callable, in_specs, out_specs, check_vma: bool = False):
        """shard_map ``fn`` over this comms' mesh (the "enqueue a collective
        program" entry point; analog of launching NCCL ops on the handle's
        stream)."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)

    def shard(self, x, spec: P):
        """Place ``x`` with a NamedSharding on this mesh. In a
        multi-controller deployment the host value (assumed identical on
        every process, like queries broadcast in raft-dask) is sliced
        per-process via ``make_array_from_callback`` — ``device_put`` of a
        host array onto a global sharding is single-controller-only."""
        sharding = NamedSharding(self.mesh, spec)
        if jax.process_count() > 1:
            arr = np.asarray(x)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])
        return jax.device_put(x, sharding)

    def sync(self, *arrays) -> None:
        """sync_stream analog: block on arrays / fence dispatch."""
        if arrays:
            for a in jax.tree_util.tree_leaves(arrays):
                if isinstance(a, jax.Array):
                    a.block_until_ready()
        else:
            jax.effects_barrier()


# ------------------------------------------------------------------ bootstrap


def init_comms(
    devices: Optional[Sequence[jax.Device]] = None,
    axis: str = "data",
    mesh_shape: Optional[Sequence[int]] = None,
    axis_names: Optional[Sequence[str]] = None,
) -> Comms:
    """Build a communicator from local (or all-process) devices.

    The role of raft-dask's ``Comms.init`` (raft_dask/common/comms.py:173):
    on a multi-host deployment call ``jax.distributed.initialize`` first
    (the NCCL-uniqueId rendezvous analog); here the device list already spans
    hosts. With ``mesh_shape``/``axis_names`` a multi-axis mesh is built
    (axis 0 is the comms axis unless ``axis`` says otherwise).
    """
    devs = list(devices) if devices is not None else jax.devices()
    if mesh_shape is None:
        mesh = Mesh(np.array(devs), (axis,))
    else:
        names = tuple(axis_names) if axis_names else tuple(
            f"ax{i}" if i else axis for i in range(len(mesh_shape)))
        if axis not in names:
            raise ValueError(
                f"comms axis {axis!r} not in axis_names {names}")
        mesh = Mesh(np.array(devs).reshape(tuple(mesh_shape)), names)
    return Comms(mesh, axis)


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    axis: str = "data",
) -> Comms:
    """Multi-host bootstrap: ``jax.distributed.initialize`` + global-device
    mesh (the jax-native analog of NCCL-uniqueId + Dask RPC rendezvous,
    raft_dask/common/comms.py:138-151)."""
    if coordinator_address is not None:
        jax.distributed.initialize(coordinator_address, num_processes,
                                   process_id)
    return init_comms(jax.devices(), axis=axis)


def inject_comms(res: Resources, comms: Comms) -> Resources:
    """Attach a communicator to a Resources (analog of
    ``inject_comms_on_handle`` — raft_dask common/comms_utils.pyx:258)."""
    res._comms = comms
    res.mesh = comms.mesh
    return res


# ------------------------------------------------------------------ self-test


def test_collective_allreduce(comms: Comms) -> bool:
    """Smoke tests mirroring raft::comms::test_collective_* helpers
    (comms/comms_test.hpp:34-156) — callable from any deployment to verify
    the comms fabric."""
    x = jnp.ones((comms.size, 8), jnp.float32)
    x = comms.shard(x, P(comms.axis))

    def body(xs):
        return comms.allreduce(jnp.sum(xs))

    out = jax.jit(comms.run(body, P(comms.axis), P()))(x)
    return bool(np.isclose(float(out), comms.size * 8))


def test_collective_allgather(comms: Comms) -> bool:
    x = jnp.arange(comms.size, dtype=jnp.float32)[:, None]
    x = comms.shard(x, P(comms.axis))

    def body(xs):
        return comms.allgather(xs)

    out = jax.jit(comms.run(body, P(comms.axis), P()))(x)
    return bool(np.allclose(np.asarray(out).ravel(), np.arange(comms.size)))


def test_collective_reducescatter(comms: Comms) -> bool:
    x = jnp.ones((comms.size, comms.size), jnp.float32)
    x = comms.shard(x, P(comms.axis))

    def body(xs):
        return comms.reducescatter(xs[0])

    out = jax.jit(comms.run(body, P(comms.axis), P(comms.axis)))(x)
    return bool(np.allclose(np.asarray(out), comms.size))


def test_pointToPoint_simple_send_recv(comms: Comms) -> bool:
    """Ring send/recv analog of comms_test.hpp send_recv tests."""
    x = jnp.arange(comms.size, dtype=jnp.float32)[:, None]
    x = comms.shard(x, P(comms.axis))

    def body(xs):
        return comms.shift(xs, 1)

    out = np.asarray(jax.jit(comms.run(body, P(comms.axis), P(comms.axis)))(x))
    want = np.roll(np.arange(comms.size), 1)
    return bool(np.allclose(out.ravel(), want))
