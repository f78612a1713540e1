"""CAGRA — graph-based ANN index (build + greedy graph search).

Reference: ``raft::neighbors::cagra`` (neighbors/cagra.cuh:299-376; types
cagra_types.hpp:48-189; build detail/cagra/cagra_build.cuh:43-296; graph
pruning detail/cagra/graph_core.cuh; search plan detail/cagra/search_plan.cuh
+ single-CTA kernel detail/cagra/search_single_cta_kernel-inl.cuh).

Build = (1) all-neighbors kNN graph at ``intermediate_graph_degree`` via
IVF-PQ build+search+refine batches (cagra_build.cuh:43-160) or NN-descent
(:241-258); (2) ``optimize``: detour-count based pruning to ``graph_degree``
with reverse-edge augmentation (graph_core.cuh).

TPU-native design:
- **optimize** is pure gather/compare tensor algebra: the 2-hop detour count
  of edge (i→a) is #{b<a : G[i,a] ∈ G[G[i,b]]}, computed per node tile as a
  [tile, K, K, K] membership reduction (XLA fuses the compare+reduce; no
  atomics), then a stable top-``graph_degree`` by (count, rank). Reverse
  edges fill the tail slots, as in graph_core.cuh's rev-edge pass.
- **search** replaces the CTA-resident loop + hashmap visited-set with a
  functional beam state per query: an itopk buffer (dist, id) + a fixed-size
  expanded-parents list (the visited set — parents are the only nodes that
  matter for termination, mirroring search_single_cta's parent bitmask trick
  cagra_types: itopk entries carry a "visited" flag). Each iteration:
  pick ``search_width`` best unexpanded entries → gather their graph rows →
  mask already-expanded/duplicate targets → batched einsum distances (MXU) →
  merge into the buffer by a sort. Fixed ``max_iterations`` under
  ``lax.fori_loop`` with per-query done-masking keeps it one XLA program;
  queries batch along the leading axis (the batch analog of one CTA/query).
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import serialize as ser
from raft_tpu.core import tracing
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.neighbors.brute_force import fused_ineligible_reason
from raft_tpu.obs import explain as obs_explain
from raft_tpu.ops.distance import (
    DistanceType,
    gathered_distances,
    resolve_metric,
)
from raft_tpu.ops.select_k import merge_topk_dedup_flagged
from raft_tpu.utils.shape import (as_query_array, cdiv, pad_rows,
                                  query_bucket)


class BuildAlgo(enum.IntEnum):
    """reference: cagra_types.hpp graph_build_algo."""

    IVF_PQ = 0
    NN_DESCENT = 1


@dataclasses.dataclass
class IndexParams:
    """reference: cagra_types.hpp:48-63 index_params."""

    intermediate_graph_degree: int = 128
    graph_degree: int = 64
    build_algo: BuildAlgo = BuildAlgo.NN_DESCENT
    nn_descent_niter: int = 20
    metric: DistanceType = DistanceType.L2Expanded

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)
        if self.metric not in (DistanceType.L2Expanded,
                               DistanceType.L2SqrtExpanded,
                               DistanceType.InnerProduct):
            raise ValueError(
                f"cagra supports L2Expanded/L2SqrtExpanded/InnerProduct, got "
                f"{self.metric.name}")


@dataclasses.dataclass
class SearchParams:
    """reference: cagra_types.hpp:66-116 search_params (the single-CTA-
    relevant subset; algo/team_size dispatch is an XLA concern here)."""

    itopk_size: int = 64
    search_width: int = 1
    max_iterations: int = 0  # 0 → auto heuristic (search_plan.cuh:31-123)
    num_random_samplings: int = 1
    rand_xor_mask: int = 0x128394
    #: None = fp32-accurate scan. "bfloat16" gathers beam candidates from a
    #: cached bf16 dataset copy (half the HBM gather bytes, single MXU pass)
    #: and exactly re-ranks the final buffer in fp32 — the TPU analog of the
    #: reference's half-precision compute_distance teams
    #: (detail/cagra/compute_distance.hpp).
    scan_dtype: Optional[object] = None
    #: "auto" routes XLA (the fused Pallas beam-search engine has no chip
    #: measurement beating it, ``pallas_kernels.fused_dispatch_explained``);
    #: "pallas"/"xla" force an engine. Same contract as the other fused
    #: families (docs/tuning.md fallback matrix).
    scan_mode: str = "auto"


class Index:
    """dataset + fixed-degree neighbor graph (cagra_types.hpp:127-189)."""

    def __init__(self, params: IndexParams, dataset, graph):
        self.params = params
        self.dataset = dataset  # [n, dim]
        self.graph = graph  # [n, graph_degree] int32
        self._dataset_bf16 = None  # lazy bf16 copy for scan_dtype searches

    def ensure_scan_dataset(self):
        if self._dataset_bf16 is None:
            self._dataset_bf16 = self.dataset.astype(jnp.bfloat16)
        return self._dataset_bf16

    @property
    def metric(self) -> DistanceType:
        return self.params.metric

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]

    @property
    def graph_degree(self) -> int:
        return self.graph.shape[1]


# ------------------------------------------------------------------ optimize


@functools.partial(jax.jit, static_argnames=("node_tile",))
def _detour_counts_jit(graph, node_tile: int):
    """count[i, a] = #{b < a : G[i,a] ∈ G[G[i,b]]} — 2-hop detour count
    (functional analog of graph_core.cuh's detourable-edge counting).

    Blocked formulation: the [tile, K, K] membership matrix is accumulated
    over chunks of the 2-hop axis c (compare + any fused per chunk), so
    scratch is O(K²) per node — the [tile, K, K, K] tensor of the naive
    broadcast never exists and ``member`` traffic drops by the chunk
    factor. Semantics are exactly any-over-c, so results match the naive
    formulation bit-for-bit (duplicate ids included)."""
    n, k = graph.shape
    n_tiles = cdiv(n, node_tile)
    pad = n_tiles * node_tile - n
    gp = jnp.pad(graph, ((0, pad), (0, 0)), constant_values=-1)
    chunk = min(16, k)
    kc = cdiv(k, chunk) * chunk  # pad c axis to a whole number of chunks

    def body(gt):  # [t, K] neighbor ids of one node tile
        t = gt.shape[0]
        nb = jnp.maximum(gt, 0)
        g2 = graph[nb.reshape(-1)].reshape(t, k, k)  # [t, b, c] 2-hop ids
        # invalid b rows (padded edges) contribute nothing
        g2 = jnp.where((gt >= 0)[:, :, None], g2, -1)
        g2 = jnp.pad(g2, ((0, 0), (0, 0), (0, kc - k)),
                     constant_values=-1)
        g2r = g2.reshape(t, k, kc // chunk, chunk)

        def step(j, member):
            col = jax.lax.dynamic_slice_in_dim(g2r, j, 1, axis=2)[:, :, 0]
            hit = jnp.any(col[:, :, :, None] == gt[:, None, None, :], axis=2)
            return member | hit  # member[t, b, a]

        member = jax.lax.fori_loop(
            0, kc // chunk, step, jnp.zeros((t, k, k), bool))
        member = member & (gt[:, None, :] >= 0) & (gt[:, :, None] >= 0)
        ltm = jnp.tril(jnp.ones((k, k), bool), -1).T  # [b, a]: b < a
        return (member & ltm[None]).sum(1).astype(jnp.int32)

    if n_tiles == 1:
        counts = body(gp)
    else:
        counts = jax.lax.map(
            body, gp.reshape(n_tiles, node_tile, k)).reshape(-1, k)
    return counts[:n]


@functools.partial(jax.jit, static_argnames=("out_degree",))
def _prune_jit(graph, counts, out_degree: int):
    """Keep the ``out_degree`` edges with the smallest (detour count, rank)
    per node (graph_core.cuh prune pass)."""
    n, k = graph.shape
    # composite key: count major, original rank minor; invalid edges last
    key = counts.astype(jnp.float32) * (k + 1) + jnp.arange(k)[None, :]
    key = jnp.where(graph >= 0, key, jnp.inf)
    _, sel = jax.lax.top_k(-key, out_degree)
    sel = jnp.sort(sel, axis=1)  # preserve rank order among survivors
    return jnp.take_along_axis(graph, sel, axis=1)


@functools.partial(jax.jit, static_argnames=("max_rev",))
def _reverse_graph_jit(graph, max_rev: int):
    """Reverse adjacency with per-node cap (graph_core.cuh rev-edge pass).
    Collision policy: random slot, later writers win."""
    n, d = graph.shape
    rev = jnp.full((n, max_rev), -1, jnp.int32)
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, d))
    # invalid edges route out of bounds (dropped) instead of hitting node 0
    tgt = jnp.where(graph >= 0, graph, n)
    # deterministic pseudo-random slots: Knuth multiplicative hash in uint32
    slots = ((src.astype(jnp.uint32) * jnp.uint32(2654435761)
              + jnp.arange(d, dtype=jnp.uint32)[None, :] * jnp.uint32(40503))
             % jnp.uint32(max_rev)).astype(jnp.int32)
    rev = rev.at[tgt.reshape(-1), slots.reshape(-1)].set(
        src.reshape(-1), mode="drop")
    return rev


@functools.partial(jax.jit, static_argnames=())
def _augment_reverse_jit(pruned, rev):
    """Replace tail slots of the pruned graph with reverse edges not already
    present (graph_core.cuh: forward edges keep priority, reverse edges fill
    up to half the degree)."""
    n, d = pruned.shape
    n_rev = rev.shape[1]
    # dedupe reverse edges against forward ones
    dup = jnp.any(rev[:, :, None] == pruned[:, None, :], axis=2)
    rev = jnp.where(dup | (rev == jnp.arange(n)[:, None]), -1, rev)
    # compact valid reverse edges to the front
    order = jnp.argsort(rev < 0, axis=1, stable=True)
    rev_c = jnp.take_along_axis(rev, order, axis=1)
    n_valid = jnp.sum(rev_c >= 0, axis=1)
    n_replace = jnp.minimum(n_valid, d // 2)  # at most half the degree
    slot = jnp.arange(d)[None, :]
    take_rev = slot >= (d - n_replace)[:, None]
    rev_idx = jnp.clip(slot - (d - n_replace)[:, None], 0, n_rev - 1)
    out = jnp.where(take_rev,
                    jnp.take_along_axis(rev_c, rev_idx, axis=1), pruned)
    return out


@tracing.range("cagra.optimize")
def optimize(knn_graph, graph_degree: int,
             res: Optional[Resources] = None) -> jax.Array:
    """Prune an intermediate kNN graph to ``graph_degree`` (reference:
    cagra::optimize, cagra_build.cuh:266-285 → graph_core.cuh)."""
    res = ensure_resources(res)
    g = jnp.asarray(knn_graph, jnp.int32)
    n, k = g.shape
    if graph_degree >= k:
        return g
    # scratch per node: g2 + its padded copy (2×4·K² i32), member (K² bool),
    # and the per-chunk hit tensor ([K, 16, K] bool = 16·K²) ≈ 25·K² bytes;
    # modest tiles keep member cache/VMEM-resident (measured fastest 64-256)
    per_node = 25 * k * k
    node_tile = int(np.clip(res.workspace_limit_bytes // max(per_node, 1),
                            8, 256))
    node_tile -= node_tile % 8 or 0
    counts = _detour_counts_jit(g, max(node_tile, 8))
    pruned = _prune_jit(g, counts, int(graph_degree))
    rev = _reverse_graph_jit(pruned, int(graph_degree))
    return _augment_reverse_jit(pruned, rev)


# --------------------------------------------------------------------- build


@tracing.range("cagra.build")
def build(
    dataset,
    params: Optional[IndexParams] = None,
    res: Optional[Resources] = None,
) -> Index:
    """Build (reference: cagra::build, cagra.cuh → cagra_build.cuh:296):
    kNN graph at intermediate degree, then optimize to graph_degree."""
    params = params or IndexParams()
    res = ensure_resources(res)
    dataset = jnp.asarray(dataset)
    n, dim = dataset.shape
    k_inter = int(min(params.intermediate_graph_degree, n - 1))

    if params.build_algo == BuildAlgo.NN_DESCENT:
        from raft_tpu.neighbors import nn_descent

        nd_params = nn_descent.IndexParams(
            graph_degree=k_inter,
            intermediate_graph_degree=min(int(k_inter * 1.5), n - 1),
            max_iterations=params.nn_descent_niter,
            metric=params.metric,
        )
        knn = nn_descent.build(dataset, nd_params, res=res).graph
    else:
        knn = _build_knn_graph_ivf_pq(dataset, k_inter, params, res)

    graph = optimize(knn, int(min(params.graph_degree, k_inter)), res=res)
    return Index(params, dataset, graph)


def _build_knn_graph_ivf_pq(dataset, k_inter: int, params: IndexParams,
                            res: Resources) -> jax.Array:
    """IVF-PQ path (cagra_build.cuh:43-160): build ivf_pq on the dataset,
    batched self-search for top (k_inter+1), refine with exact distances,
    drop self."""
    from raft_tpu.neighbors import ivf_pq as ivf_pq_mod
    from raft_tpu.neighbors import refine as refine_mod

    n, dim = dataset.shape
    n_lists = int(np.clip(int(np.sqrt(n) * 2), 16, 8192))
    n_lists = min(n_lists, max(n // 64, 16))
    ipq = ivf_pq_mod.IndexParams(
        n_lists=n_lists,
        metric=(DistanceType.L2Expanded
                if params.metric != DistanceType.InnerProduct
                else DistanceType.InnerProduct),
        pq_dim=max(8, (dim // 2 + 7) // 8 * 8),
    )
    index = ivf_pq_mod.build(dataset, ipq, res=res)
    top = k_inter + 1
    sp = ivf_pq_mod.SearchParams(n_probes=max(min(n_lists, 32), n_lists // 16))
    # Device-resident pipeline (VERDICT r2 #5 — the old loop staged every
    # batch through np.asarray + a numpy argsort, a device→host→device
    # round-trip per 8192 rows; the reference keeps the whole build on
    # device, cagra_build.cuh:43-160): search → refine → jitted drop-self
    # all stay on device; the host loop only slices the next batch. The
    # tail batch is padded to the batch shape so every step reuses one
    # compiled program.
    batch = min(8192, n)
    dataset_j = jnp.asarray(dataset)
    parts = []
    for s in range(0, n, batch):
        hi = min(s + batch, n)
        q = jax.lax.dynamic_slice_in_dim(
            dataset_j, min(s, n - batch), batch)  # tail overlaps, static shape
        row0 = min(s, n - batch)
        _, cand = ivf_pq_mod.search(index, q, min(top * 2, n), sp, res=res)
        _, refined = refine_mod.refine(dataset_j, q, cand, top,
                                       metric=params.metric, res=res)
        keep = _drop_self_jit(refined, row0, k_inter)
        parts.append(keep if row0 == s else keep[s - row0:])
    return jnp.concatenate(parts, axis=0)


@functools.partial(jax.jit, static_argnames=("k_inter",))
def _drop_self_jit(refined, row0: int, k_inter: int):
    """Drop each row's own id where present, else the last slot — a stable
    argsort pushes the dropped slot past everything (device analog of the
    reference's self-exclusion in the graph fill)."""
    r = refined
    rows = jnp.arange(r.shape[0]) + row0
    is_self = r == rows[:, None]
    drop = jnp.where(is_self.any(1)[:, None], is_self,
                     jnp.arange(r.shape[1])[None, :] == r.shape[1] - 1)
    order = jnp.argsort(drop, axis=1, stable=True)
    keep = jnp.take_along_axis(r, order, axis=1)[:, :k_inter]
    return keep.astype(jnp.int32)


# -------------------------------------------------------------------- search


@functools.partial(
    jax.jit,
    static_argnames=("metric", "k", "itopk", "width", "max_iter",
                     "has_filter", "fast_scan"),
)
def _search_jit(queries, dataset, scan_data, graph, seed_ids, filter_words,
                metric: DistanceType, k: int, itopk: int, width: int,
                max_iter: int, has_filter: bool = False,
                fast_scan: bool = False):
    nq, dim = queries.shape
    n, degree = graph.shape
    minimize = metric != DistanceType.InnerProduct
    bad = jnp.inf

    qf = queries.astype(jnp.float32)
    # fast scan: bf16 query + bf16 gathered rows → gathered_distances picks
    # the single-pass MXU einsum (its HIGHEST request is fp32-data-only)
    q_scan = qf.astype(jnp.bfloat16) if fast_scan else qf
    # distances are minimized internally; IP negates, L2Sqrt defers the sqrt
    inner_metric = (DistanceType.L2Expanded
                    if metric == DistanceType.L2SqrtExpanded else metric)

    def dists_to(ids):  # ids [nq, C] → [nq, C] (minimized quantity)
        vecs = scan_data[jnp.maximum(ids, 0)]
        d = gathered_distances(q_scan, vecs, inner_metric)
        if metric == DistanceType.InnerProduct:
            d = -d
        if has_filter:
            # filtered nodes never enter the candidate buffer — the
            # reference's filtered search skips them at topk insertion
            safe = jnp.maximum(ids, 0)
            words = filter_words[jnp.minimum(
                safe // 32, filter_words.shape[0] - 1)]
            bits = ((words >> (safe % 32).astype(jnp.uint32)) & 1
                    ).astype(bool)
            d = jnp.where(bits, d, bad)
        return jnp.where(ids < 0, bad, d)

    # ---- init: random seed nodes (random_samplings, search_plan.cuh)
    init_ids = seed_ids  # [nq, S]
    init_d = dists_to(init_ids)
    init_fl = jnp.zeros_like(init_ids, dtype=bool)
    buf_ids, buf_d, buf_fl = merge_topk_dedup_flagged(
        init_ids, init_d, init_fl, itopk)

    # The "expanded" flag rides the itopk buffer instead of a growing visited
    # array (the reference's hashmap): the buffer is monotone under the
    # merge, so a node that falls out of the top-itopk can never re-enter —
    # buffer-resident flags are a complete visited set.
    rows = jnp.arange(nq)[:, None]

    # The per-iteration merge is THE cost of the TPU beam walk (r3 on-chip:
    # sort-class primitives run at a few GB/s effective). The old body paid
    # three of them per hop — top_k(parent pick), argsort-by-id (dedup),
    # top_k(merge). This body keeps the buffer SORTED BY DISTANCE as a loop
    # invariant (merge_topk_dedup_flagged establishes it at init), so:
    # - parent pick is an argmin (width=1) or a tiny top_k over itopk;
    # - dedup happens BEFORE the merge with two small membership compares
    #   (targets vs buffer, targets vs earlier targets) — valid because
    #   the buffer is dup-free by induction, so post-concat adjacency
    #   tricks aren't needed;
    # - the merge is ONE lax.sort of the [itopk + W·D] concat, sliced back
    #   to itopk. Same semantics as merge_topk_dedup_flagged (a target
    #   equal to a buffer entry is dropped, keeping the buffer copy's
    #   expanded flag — the OR of the copies' flags, since target copies
    #   are never flagged).
    wd = width * degree

    def body(state):
        it, buf_ids, buf_d, buf_fl, done = state
        # pickup_next_parents: best `width` unexpanded buffer entries
        cand_d = jnp.where(buf_fl | (buf_ids < 0), bad, buf_d)
        if width == 1:
            p_sel = jnp.argmin(cand_d, axis=1)[:, None]
            valid_p = jnp.isfinite(
                jnp.take_along_axis(cand_d, p_sel, axis=1))
        else:
            p_d, p_sel = jax.lax.top_k(-cand_d, width)
            valid_p = jnp.isfinite(-p_d)
        parents = jnp.take_along_axis(buf_ids, p_sel, axis=1)  # [nq, W]
        valid_p = valid_p & (parents >= 0) & ~done[:, None]
        has_parent = valid_p[:, 0]
        newly_done = ~has_parent
        parents = jnp.where(valid_p, parents, -1)

        # mark picked parents expanded in the buffer
        mark = jnp.zeros_like(buf_fl).at[rows, p_sel].max(valid_p)
        buf_fl = buf_fl | mark

        # expand: gather graph rows of parents
        targets = graph[jnp.maximum(parents, 0)].reshape(-1, wd)
        targets = jnp.where(
            jnp.repeat(parents < 0, degree, axis=1), -1, targets)
        # drop targets already in the buffer (the visited-set test) and
        # copies among the targets themselves (parents sharing neighbors)
        in_buf = jnp.any(targets[:, :, None] == buf_ids[:, None, :], axis=2)
        if wd > 1:
            earlier = jnp.tril(jnp.ones((wd, wd), bool), -1)
            dup_t = jnp.any((targets[:, :, None] == targets[:, None, :])
                            & earlier[None], axis=2)
            in_buf = in_buf | dup_t
        targets = jnp.where(in_buf, -1, targets)
        t_d = dists_to(targets)

        new_d = jnp.concatenate([buf_d, t_d], axis=1)
        new_ids = jnp.concatenate([buf_ids, targets], axis=1)
        new_fl = jnp.concatenate(
            [buf_fl, jnp.zeros_like(targets, dtype=bool)], axis=1)
        sd, si, sf = jax.lax.sort((new_d, new_ids, new_fl), dimension=1,
                                  num_keys=1)
        # frozen queries keep their state
        keep = done[:, None]
        buf_ids = jnp.where(keep, buf_ids, si[:, :itopk])
        buf_d = jnp.where(keep, buf_d, sd[:, :itopk])
        buf_fl = jnp.where(keep, buf_fl, sf[:, :itopk])
        done = done | newly_done
        return it + 1, buf_ids, buf_d, buf_fl, done

    # while_loop with an all-done exit instead of a fixed fori_loop: once
    # every query's buffer has no unexpanded parent, further iterations
    # are pure wasted HBM gathers (the batch converges well before the
    # max_iter bound in practice; the reference's terminate_flag plays the
    # same role, search_single_cta_kernel-inl.cuh)
    done0 = jnp.zeros((nq,), bool)
    _, buf_ids, buf_d, buf_fl, _ = jax.lax.while_loop(
        lambda s: (s[0] < max_iter) & ~jnp.all(s[4]),
        body, (jnp.int32(0), buf_ids, buf_d, buf_fl, done0))

    if fast_scan:
        # exact fp32 re-rank of the whole itopk buffer (nq×itopk×dim — tiny
        # next to the beam walk) so returned order/distances are exact
        vecs = dataset[jnp.maximum(buf_ids, 0)]
        ex = gathered_distances(qf, vecs, inner_metric)
        if metric == DistanceType.InnerProduct:
            ex = -ex
        ex = jnp.where(buf_ids < 0, bad, ex)
        ex, sel = jax.lax.top_k(-ex, k)
        out_d, out_i = -ex, jnp.take_along_axis(buf_ids, sel, axis=1)
    else:
        out_d, out_i = buf_d[:, :k], buf_ids[:, :k]
    if metric == DistanceType.InnerProduct:
        out_d = -out_d
    elif metric == DistanceType.L2SqrtExpanded:
        out_d = jnp.sqrt(jnp.maximum(out_d, 0.0))
    return out_d, out_i


#: public traceable-core name — the cross-package contract for the
#: sharded engine (parallel/sharded.py); the underscore spelling stays
#: package-private (R004 layering, docs/analysis.md)
search_core = _search_jit


def _search_fused_core(queries, dataset, graph, seed_ids,
                       metric: DistanceType, k: int, itopk: int, width: int,
                       max_iter: int, ct: int, interpret: bool = False):
    """Fused-engine traceable core: the whole beam walk inside one Pallas
    kernel (``ops.pallas_kernels.fused_cagra_topk`` — VMEM-resident beam
    state, in-kernel gather DMAs), plus the metric epilogue the kernel
    defers (it minimizes squared L2; L2SqrtExpanded takes the sqrt here,
    exactly as ``_search_jit`` does on its sliced buffer). Eligibility —
    L2 metrics, unfiltered, fp32, itopk ≤ 1024 — is the caller's job
    (``fused_ineligible_reason``); semantics inside that envelope are
    bit-checked against ``search_core`` (tests/test_pallas_fused.py)."""
    from raft_tpu.ops import pallas_kernels as pk

    v, i = pk.fused_cagra_topk(queries, dataset, graph, seed_ids, k,
                               itopk, width, max_iter, ct=ct,
                               interpret=interpret)
    if metric == DistanceType.L2SqrtExpanded:
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


_search_fused_jit = jax.jit(
    _search_fused_core,
    static_argnames=("metric", "k", "itopk", "width", "max_iter", "ct",
                     "interpret"),
)

#: public traceable-core name for the fused path (R004; audited by
#: graftcheck --jaxpr-audit at the canonical 1M shape, interpret=True)
search_fused_core = _search_fused_core


def resolve_search_plan(params: SearchParams, k: int, size: int):
    """The resolved beam plan — (itopk, width, max_iter, n_seeds) — shared
    by both engines' dispatch records so EXPLAIN artifacts are replayable
    (the ``max_iterations=0`` auto-clip and the seed-pool sizing used to
    be recomputed inline and never surfaced uniformly)."""
    itopk = max(int(params.itopk_size), int(k))
    width = max(int(params.search_width), 1)
    max_iter = int(params.max_iterations)
    if max_iter <= 0:
        # auto heuristic (search_plan.cuh:31-123): enough hops to drain the
        # itopk buffer, bounded
        max_iter = int(np.clip(itopk // width + 10, 16, 200))
    n_rand = max(int(params.num_random_samplings), 1)
    n_seeds = min(max(itopk, 32) * n_rand, int(size))
    return itopk, width, max_iter, n_seeds


@tracing.range("cagra.search")
def search(
    index: Index,
    queries,
    k: int,
    params: Optional[SearchParams] = None,
    filter=None,
    res: Optional[Resources] = None,
    explain: bool = False,
):
    """Greedy graph search (reference: cagra::search, cagra.cuh:299 →
    search_single_cta_kernel-inl.cuh). Returns (distances, indices); with
    ``explain=True`` a third element carries the dispatch
    :class:`raft_tpu.obs.explain.ExplainRecord` — which engine ran the
    beam walk (fused Pallas vs XLA) and why, plus the resolved beam plan
    (itopk/width/max_iter/n_seeds) in both branches so the artifact is
    replayable.

    ``filter`` is an optional :class:`raft_tpu.core.bitset.Bitset` over
    dataset row ids; cleared bits are excluded from results (and from the
    candidate buffer, as in the reference's filtered search)."""
    params = params or SearchParams()
    res = ensure_resources(res)
    queries = as_query_array(queries)  # host inputs stay host-side: the
    if queries.ndim == 1:              # jit call transfers the padded
        queries = queries[None]        # batch in ONE dispatch
    if queries.shape[1] != index.dim:
        raise ValueError(
            f"query dim {queries.shape[1]} != index dim {index.dim}")
    nq = queries.shape[0]
    queries = pad_rows(queries, query_bucket(nq))  # serving batch bucket
    # num_random_samplings multiplies the random seed pool (the reference's
    # random init batches, search_plan.cuh) — the recall lever when the
    # dataset has many well-separated clusters: a kNN graph cannot walk
    # across disconnected components, so a query's component must be
    # seeded. Seeds beyond itopk are fine: they enter through the merge.
    itopk, width, max_iter, n_seeds = resolve_search_plan(
        params, k, index.size)
    # deterministic pseudo-random seeds per query (rand_xor_mask analog):
    # a stratified lattice rotated by a per-row draw. Row q's seed set
    # depends only on q and the mask — never on the (padded) batch size —
    # so batch 1 and batch 64 see the same seeds for the same query, and
    # the lattice guarantees every size/n_seeds stretch of the dataset
    # (hence every graph component that large) holds a seed, which a
    # bare uniform draw cannot promise on clustered data.
    base = jnp.asarray(
        (np.arange(n_seeds, dtype=np.int64) * index.size) // n_seeds,
        jnp.int32)
    key = jax.random.key(params.rand_xor_mask & 0x7FFFFFFF)
    offsets = jax.vmap(
        lambda row: jax.random.randint(
            jax.random.fold_in(key, row), (), 0, index.size, jnp.int32)
    )(jnp.arange(queries.shape[0], dtype=jnp.uint32))
    seed_ids = (base[None, :] + offsets[:, None]) % index.size
    fast_scan = params.scan_dtype is not None
    if fast_scan:
        if jnp.dtype(params.scan_dtype) != jnp.bfloat16:
            raise ValueError(
                f"scan_dtype={params.scan_dtype!r}: only bfloat16 is "
                "supported")
        if index.dataset.dtype != jnp.float32:
            raise ValueError("scan_dtype requires an fp32 dataset")
    scan_data = index.ensure_scan_dataset() if fast_scan else index.dataset
    from raft_tpu.ops import pallas_kernels as pk

    scan_mode = getattr(params, "scan_mode", "auto")
    if scan_mode not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"scan_mode={scan_mode!r}: expected 'auto', 'xla' or 'pallas'")
    # ---- fused Pallas beam-search engine (the VMEM-resident beam carry).
    # Fallback matrix (docs/tuning.md): L2 metrics, no filter (no in-carry
    # filter epilogue), no bf16 fast scan, itopk ≤ 1024.
    use_fused, fused_interp, dreason = pk.fused_dispatch_explained(
        "cagra", scan_mode)
    ineligible = fused_ineligible_reason(
        index.metric, index.dataset.dtype, itopk, filter is not None,
        fast_scan)
    pk.require_compiled_kernel("cagra", scan_mode, ineligible)
    ex_params = {"k": int(k), "nq": nq, "bucket": queries.shape[0],
                 "metric": index.metric.name, "graph_degree":
                 index.graph_degree, "fast_scan": fast_scan}
    # resolved beam plan recorded identically by BOTH engines — an EXPLAIN
    # artifact replays without re-deriving the auto-clips
    ex_plan = {"itopk": itopk, "search_width": width, "max_iter": max_iter,
               "n_seeds": n_seeds}
    with contextlib.ExitStack() as stack:
        cap = stack.enter_context(obs_explain.capture()) if explain else None
        if use_fused and ineligible is None:
            ct = pk.plan_fused_cagra_tile(
                itopk, width, index.graph_degree, index.dim, n_seeds)
            obs_explain.record_dispatch(
                "cagra", scan_mode, "pallas", dreason, params=ex_params,
                plan={**ex_plan, "ct": ct, "interpret": fused_interp,
                      "predicted_workspace_bytes":
                      pk.fused_cagra_workspace_bytes(
                          queries.shape[0], index.size, index.dim,
                          index.graph_degree, itopk, width, n_seeds,
                          int(k), ct)})
            v, i = _search_fused_jit(
                queries, index.dataset, index.graph, seed_ids,
                index.metric, int(k), itopk, width, max_iter, ct,
                fused_interp)
        else:
            reason = ineligible if (use_fused and ineligible) else dreason
            obs_explain.record_dispatch(
                "cagra", scan_mode, "xla", reason, params=ex_params,
                plan=ex_plan)
            v, i = _search_jit(
                queries, index.dataset, scan_data, index.graph, seed_ids,
                filter.words if filter is not None
                else jnp.zeros((0,), jnp.uint32),
                index.metric, int(k), itopk, width, max_iter,
                filter is not None, fast_scan)
    if explain:
        return v[:nq], i[:nq], cap.last
    return v[:nq], i[:nq]


_SERIAL_VERSION = 1


def serialize(index: Index, file, include_dataset: bool = True) -> None:
    """reference: detail/cagra/cagra_serialize.cuh. Paths are written
    atomically (tmp + os.replace) with per-record crc framing."""
    with ser.writer_for(file) as stream:
        w = ser.IndexWriter(stream, "cagra", _SERIAL_VERSION)
        w.scalar(int(index.metric), "<i4")
        w.scalar(index.graph_degree, "<i4")
        w.scalar(1 if include_dataset else 0, "<i4")
        w.array(index.graph)
        if include_dataset:
            w.array(index.dataset)
        w.finish()


def deserialize(file, dataset=None, res: Optional[Resources] = None) -> Index:
    ensure_resources(res)
    with ser.reader_for(file) as stream:
        r = ser.IndexReader(stream, "cagra", _SERIAL_VERSION)
        metric = DistanceType(r.scalar())
        graph_degree = r.scalar()
        has_ds = bool(r.scalar())
        graph = jnp.asarray(r.array())
        if has_ds:
            ds = jnp.asarray(r.array())
        elif dataset is not None:
            ds = jnp.asarray(dataset)
        else:
            raise ValueError(
                "index file has no dataset; pass dataset= to deserialize")
        r.finish()
        params = IndexParams(graph_degree=graph_degree, metric=metric)
        return Index(params, ds, graph)
