"""IVF-Flat — inverted-file index over raw vectors.

Reference: ``raft::neighbors::ivf_flat`` (neighbors/ivf_flat-inl.cuh:65-647;
build detail/ivf_flat_build.cuh; search detail/ivf_flat_search-inl.cuh +
interleaved scan detail/ivf_flat_interleaved_scan-inl.cuh; types
ivf_flat_types.hpp). Build: balanced k-means on a trainset subsample →
predict labels → fill per-list storage in an interleaved group-of-32,
veclen-chunked layout. Search: coarse top-``n_probes`` clusters via pairwise
distance + select_k, then a fused per-cluster scan feeding warpsort queues,
then a final select_k across probes.

TPU-native design:
- **List layout**: padded dense ``[n_lists, list_pad, dim]`` (plus int32 row
  ids), lane-aligned padding instead of the GPU's 32-row interleaving — the
  balanced quantizer keeps max/avg list length near 1, so padding waste is
  small and every probe scan is a dense, MXU/VPU-friendly block.
- **Search**: coarse scores = one queries×centers matmul (+ select_k);
  probed lists are gathered to ``[q_tile, n_probes, list_pad, dim]`` and
  scanned with one einsum; invalid padding rows get ±inf; one select_k over
  ``n_probes·list_pad`` candidates finishes (two-stage selection like the
  reference's per-probe queues + final select_k). Query batches stream
  through ``lax.map`` sized by the workspace budget.
- Optional ``Bitset`` filter masks candidates by source row id (reference:
  bitset_filter, sample_filter_types.hpp:27-82).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import serialize as ser
from raft_tpu.core import tracing
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.bitset import filter_mask as bitset_filter_mask
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
from raft_tpu.neighbors import list_packing
from raft_tpu.neighbors.brute_force import fused_ineligible_reason
from raft_tpu.obs import explain as obs_explain
from raft_tpu.ops.distance import (DistanceType, gathered_distances,
                                    resolve_metric, row_norms_sq)
from raft_tpu.ops.select_k import (refine_multiplier, select_k,
                                   select_k_maybe_approx)
from raft_tpu.ops import rng as rrng
from raft_tpu.utils.shape import (as_query_array, cdiv, pad_rows,
                                  query_bucket)


@dataclasses.dataclass
class IndexParams:
    """reference: ivf_flat_types.hpp:57-99 index_params."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    adaptive_centers: bool = False
    add_data_on_build: bool = True
    # Padded-storage budget: list capacity is capped so L·pad plus the
    # overflow block stays within this multiple of the raw row count; rows
    # spilled from hot lists land in the overflow block, scanned
    # brute-force by every query (a candidate superset — recall can only
    # improve). The reference pays only group-of-32 padding on ragged
    # lists (ivf_list.hpp); this bounds the dense-layout analog.
    list_pad_expansion: float = 1.5

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)
        if self.list_pad_expansion < 1.0:
            raise ValueError(
                f"list_pad_expansion must be >= 1.0, got "
                f"{self.list_pad_expansion}")


@dataclasses.dataclass
class SearchParams:
    """reference: ivf_flat_types.hpp search_params.

    ``scan_dtype``: None scans at the data dtype (fp32 data → fp32-accurate
    MXU passes). ``"bfloat16"`` runs the fine scan's matmul as a bf16 MXU
    screen over ~4k candidates followed by an exact fp32 re-rank — the TPU
    analog of the reference's int8/dp4a fast scans
    (ivf_flat_interleaved_scan-inl.cuh:99-251). The re-rank is required:
    an unrefined bf16 expanded-L2 scan cancels catastrophically when
    distance gaps are small next to vector norms (measured recall
    0.9997 → 0.57 on clustered data on v5e). The re-rank recovers most
    but not all of it — bf16 rounding of the *inputs* can push true
    neighbors outside the ``refine_ratio·k`` screen when gaps are far
    below vector norms (near-duplicate regimes measure ~0.95 at the
    default ratio; widen ``refine_ratio`` or use the fp32 scan when
    exactness matters)."""

    n_probes: int = 20
    scan_dtype: Optional[object] = None
    # bf16 screen width as a multiple of k for the exact fp32 re-rank
    # (scan_dtype="bfloat16" only); wider = higher recall, more re-rank
    refine_ratio: float = 4.0
    # "pallas" requests the fused Pallas scan+select (probed slabs DMA'd to
    # VMEM, top-k carried in-kernel — docs/tuning.md); "auto" picks it on
    # TPU where the committed probe artifact shows it winning; unsupported
    # combinations (non-L2 metric, filter, bf16 fast scan, k > 1024) fall
    # back to the XLA engine silently
    scan_mode: str = "auto"
    # <1.0 routes internal top-k through the TPU PartialReduce engine
    # (ops.select_k APPROX) at this per-element recall target — measured
    # 10-40x faster than exact top_k at IVF shapes on v5e; the recall
    # trade is the searcher's, like the reference's lut_dtype dial
    select_recall: float = 1.0


class Index:
    """IVF-Flat index (reference: ivf_flat_types.hpp:142-165 — per-list data
    + indices + sizes, centers, center norms)."""

    def __init__(self, params: IndexParams, centers, list_data, list_indices,
                 list_sizes, n_rows: int, overflow_data=None,
                 overflow_indices=None):
        self.params = params
        self.centers = centers  # [n_lists, dim] fp32
        self.list_data = list_data  # [n_lists, list_pad, dim]
        self.list_indices = list_indices  # [n_lists, list_pad] int32, -1 pad
        self.list_sizes = list_sizes  # [n_lists] int32
        self.n_rows = int(n_rows)
        # rows spilled past the capped list_pad (choose_list_pad): scanned
        # brute-force by every query and merged into the final select_k.
        # [n_over_pad, dim] / [n_over_pad] int32 (-1 = padding); empty in
        # the balanced common case.
        dim = centers.shape[1] if centers is not None else 0
        dt = list_data.dtype if list_data is not None else jnp.float32
        self.overflow_data = (overflow_data if overflow_data is not None
                              else jnp.zeros((0, dim), dt))
        self.overflow_indices = (
            overflow_indices if overflow_indices is not None
            else jnp.zeros((0,), jnp.int32))
        # lazy per-row squared norms for the Pallas fused scan (the
        # reference's center_norms analog at list granularity)
        self._row_norms = None

    def ensure_row_norms(self):
        if self._row_norms is None:
            self._row_norms = jnp.sum(
                self.list_data.astype(jnp.float32) ** 2, -1)
        return self._row_norms

    @property
    def metric(self) -> DistanceType:
        return self.params.metric

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def size(self) -> int:
        return self.n_rows


def _pack_lists(dataset: np.ndarray, labels: np.ndarray, n_lists: int,
                ids: Optional[np.ndarray] = None,
                max_expansion: float = 1.5):
    """Pack rows into padded [n_lists, pad, dim] storage via the native C++
    packer (host-side; analog of build_index_kernel's list fill,
    detail/ivf_flat_build.cuh:123-160). ``pad`` is budget-capped
    (list_packing.choose_list_pad); rows past a hot list's cap spill to
    the returned overflow block.

    Returns (data, idxs, sizes, overflow_rows, overflow_ids)."""
    from raft_tpu import native

    sizes = np.bincount(labels, minlength=n_lists).astype(np.int32)
    pad = list_packing.choose_list_pad(sizes, max_expansion)
    if ids is None:
        ids = np.arange(len(dataset), dtype=np.int32)
    if int(sizes.max(initial=0)) <= pad:
        data, idxs, sizes = native.pack_lists(dataset, labels, n_lists, pad,
                                              ids)
        return data, idxs, sizes, *list_packing.pad_overflow_block(
            dataset[:0], ids[:0])
    keep = list_packing.fit_mask(labels, n_lists, pad)
    data, idxs, sizes = native.pack_lists(
        np.ascontiguousarray(dataset[keep]), labels[keep], n_lists, pad,
        np.ascontiguousarray(ids[keep]))
    over_rows, over_ids = list_packing.pad_overflow_block(
        np.ascontiguousarray(dataset[~keep]),
        np.ascontiguousarray(ids[~keep]))
    return data, idxs, sizes, over_rows, over_ids


@tracing.range("ivf_flat.build")
def build(
    dataset,
    params: Optional[IndexParams] = None,
    res: Optional[Resources] = None,
) -> Index:
    """Build the index (reference: ivf_flat::build, ivf_flat-inl.cuh:65)."""
    params = params or IndexParams()
    res = ensure_resources(res)
    dataset = jnp.asarray(dataset)
    n_rows, dim = dataset.shape
    if params.n_lists > n_rows:
        raise ValueError(f"n_lists={params.n_lists} > n_rows={n_rows}")

    # trainset subsample (reference: detail/ivf_flat_build.cuh build())
    n_train = max(int(n_rows * params.kmeans_trainset_fraction), params.n_lists)
    n_train = min(n_train, n_rows)
    trainset = rrng.subsample_rows(res.next_key(), dataset, n_train)

    km_params = KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=params.metric
    )
    centers = kmeans_balanced.fit(res.next_key(), trainset, params.n_lists,
                                  km_params, res=res)
    index = Index(params, centers, None, None, None, 0)
    if params.add_data_on_build:
        index = extend(index, dataset, res=res)
    return index


@tracing.range("ivf_flat.extend")
def extend(index: Index, new_vectors, new_indices=None,
           res: Optional[Resources] = None) -> Index:
    """Add vectors (reference: ivf_flat::extend, ivf_flat-inl.cuh:195;
    optional adaptive_centers recomputes centroids from list means,
    ivf_flat_types.hpp:57-68)."""
    res = ensure_resources(res)
    new_vectors = jnp.asarray(new_vectors)
    km_params = KMeansBalancedParams(metric=index.metric)
    labels = np.asarray(kmeans_balanced.predict(index.centers, new_vectors,
                                                km_params, res=res))
    new_np = np.asarray(new_vectors)
    if new_indices is None:
        # auto ids start past the row count and any user-supplied id —
        # including ids that spilled to the overflow block
        base = index.n_rows
        if index.list_indices is not None:
            base = max(base, int(np.asarray(index.list_indices).max()) + 1)
        if index.overflow_indices is not None and \
                index.overflow_indices.shape[0]:
            base = max(base,
                       int(np.asarray(index.overflow_indices).max()) + 1)
        new_ids = np.arange(base, base + len(new_np), dtype=np.int32)
    else:
        new_ids = np.asarray(new_indices, np.int32)

    if index.list_data is None:
        data, idxs, sizes, over_rows, over_ids = _pack_lists(
            new_np, labels, index.n_lists, new_ids,
            index.params.list_pad_expansion)
        data, idxs, sizes = (jnp.asarray(data), jnp.asarray(idxs),
                             jnp.asarray(sizes))
        over_rows, over_ids = jnp.asarray(over_rows), jnp.asarray(over_ids)
    else:
        # device-side append: grow the pad (budget-capped) if needed, then
        # segment-scatter the new batch after each list's tail — existing
        # lists stay packed on device (same path as ivf_pq.extend;
        # reference: build_index_kernel's list fill,
        # detail/ivf_flat_build.cuh:123-160). Rows past a hot list's cap
        # spill to the overflow block (the pad never shrinks below the
        # current storage — no repack on extend).
        old_sizes = np.asarray(index.list_sizes)
        counts = np.bincount(labels, minlength=index.n_lists)
        n_over_old = int(jnp.sum(index.overflow_indices >= 0)) \
            if len(index.overflow_indices) else 0
        cap = max(list_packing.choose_list_pad(
            old_sizes + counts, index.params.list_pad_expansion),
            index.list_data.shape[1])
        keep = list_packing.fit_mask(labels, index.n_lists, cap,
                                     sizes=old_sizes)
        data, idxs = list_packing.grow_pad(
            index.list_data, index.list_indices,
            int((old_sizes + np.bincount(
                labels[keep], minlength=index.n_lists)).max()))
        data, idxs, sizes = list_packing.append_lists(
            data, idxs, index.list_sizes,
            jnp.asarray(new_np[keep]).astype(data.dtype),
            jnp.asarray(new_ids[keep]), jnp.asarray(labels[keep]),
            index.n_lists)
        over_rows, over_ids = _merge_overflow(
            index.overflow_data, index.overflow_indices, n_over_old,
            new_np[~keep].astype(data.dtype), new_ids[~keep])
    centers = index.centers
    if index.params.adaptive_centers:
        dsum = data.astype(jnp.float32).sum(axis=1)
        centers = dsum / jnp.maximum(sizes.astype(jnp.float32), 1.0)[:, None]
    return Index(index.params, centers, data, idxs, sizes,
                 index.n_rows + len(new_np), over_rows, over_ids)


def _merge_overflow(old_rows, old_ids, n_old_valid: int, new_rows_np,
                    new_ids_np):
    """Append spilled rows to the overflow block (8-aligned). Valid rows
    are compacted first (padding slots sit only at the tail)."""
    if len(new_rows_np) == 0:
        return old_rows, old_ids
    merged_rows = np.concatenate(
        [np.asarray(old_rows)[:n_old_valid], new_rows_np], axis=0)
    merged_ids = np.concatenate(
        [np.asarray(old_ids)[:n_old_valid],
         np.asarray(new_ids_np, np.int32)])
    rows, ids = list_packing.pad_overflow_block(merged_rows, merged_ids)
    return jnp.asarray(rows), jnp.asarray(ids)


def _coarse_scores(queries, centers, metric: DistanceType):
    dots = jax.lax.dot_general(
        queries.astype(jnp.float32), centers, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if metric == DistanceType.InnerProduct:
        return dots, False  # maximize
    if metric == DistanceType.CosineExpanded:
        cn = jnp.sqrt(jnp.maximum(row_norms_sq(centers), 1e-20))
        return dots / cn[None, :], False
    qn = row_norms_sq(queries)
    cn = row_norms_sq(centers)
    return qn[:, None] + cn[None, :] - 2.0 * dots, True


def _overflow_scan(qt, qf, o_scan, o_norms, o_ok_base, overflow_indices,
                   filter_words, metric: DistanceType, has_filter: bool,
                   fast_scan: bool, bad_fill):
    """Brute-force distances of one query tile against the overflow block
    (the spilled-rows complement of the probed-list scan): [t, O] distances
    + broadcast ids, ready to concatenate into the final select_k."""
    q_s = qt.astype(jnp.bfloat16) if fast_scan else qf
    dots = jax.lax.dot_general(
        q_s, o_scan, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(None if fast_scan else jax.lax.Precision.HIGHEST),
    )  # [t, O]
    if metric == DistanceType.InnerProduct:
        od = dots
    elif metric == DistanceType.CosineExpanded:
        on = jnp.sqrt(jnp.maximum(o_norms, 1e-20))
        qn = jnp.sqrt(jnp.maximum(row_norms_sq(qf), 1e-20))
        od = 1.0 - dots / (on[None, :] * qn[:, None])
    else:
        od = jnp.maximum(
            row_norms_sq(qf)[:, None] + o_norms[None, :] - 2.0 * dots, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            od = jnp.sqrt(od)
    ok = o_ok_base
    if has_filter:
        ok = ok & bitset_filter_mask(overflow_indices, filter_words)
    od = jnp.where(ok[None, :], od, bad_fill)
    oi = jnp.broadcast_to(overflow_indices[None, :],
                          (qt.shape[0], overflow_indices.shape[0]))
    o_ok = jnp.broadcast_to(ok[None, :], od.shape)
    return od, oi, o_ok


def _search_core(queries, centers, list_data, list_indices, list_sizes,
                 filter_words, metric: DistanceType, k: int, n_probes: int,
                 q_tile: int, has_filter: bool, row_norms=None,
                 fast_scan: bool = False, overflow_data=None,
                 overflow_indices=None, has_overflow: bool = False,
                 select_recall: float = 1.0, refine_mult: int = 4):
    """Traceable search body — jitted below; also shard_mapped by
    raft_tpu.parallel.sharded for multi-device list-sharded search.

    ``has_overflow``: rows spilled past the capped list_pad are scanned
    brute-force for every query and merged into the final select_k — a
    strict candidate superset (exact distances), so recall never drops."""
    nq, dim = queries.shape
    n_lists, list_pad, _ = list_data.shape
    minimize = metric != DistanceType.InnerProduct

    def _sel(vals, kk, sel_min):
        return select_k_maybe_approx(vals, kk, sel_min, select_recall)

    n_q_tiles = cdiv(nq, q_tile)
    pad_q = n_q_tiles * q_tile - nq
    qp = jnp.pad(queries, ((0, pad_q), (0, 0)))

    valid_slot = jnp.arange(list_pad)[None, :] < list_sizes[:, None]  # [L, pad]
    if has_overflow:
        o_f32 = overflow_data.astype(jnp.float32)
        o_norms = row_norms_sq(o_f32)  # [O]
        o_ok_base = overflow_indices >= 0
        o_scan = (overflow_data.astype(jnp.bfloat16) if fast_scan else o_f32)

    def q_body(qt):
        # ---- coarse: top-n_probes clusters per query
        scores, coarse_min = _coarse_scores(qt, centers, metric)
        _, probes = _sel(scores, n_probes, coarse_min)  # [t, P]

        g_idx = list_indices[probes]  # [t, P, pad]
        g_valid = valid_slot[probes]  # [t, P, pad]
        qf = qt.astype(jnp.float32)
        # ---- gather probed lists and scan
        g_data = list_data[probes]  # [t, P, pad, dim]
        if fast_scan:
            # bf16 MXU pass; norms stay exact fp32 (cached per-row)
            q_s, g_s = qt.astype(jnp.bfloat16), g_data.astype(jnp.bfloat16)
        else:
            q_s, g_s = qf, g_data.astype(jnp.float32)
        dots = jnp.einsum(
            "td,tpld->tpl", q_s, g_s,
            # HIGHEST only for true fp32 data on the accurate path;
            # int8/uint8/bf16 values are bf16-exact → single MXU pass
            precision=(jax.lax.Precision.HIGHEST
                       if (not fast_scan
                           and g_data.dtype == jnp.float32) else None),
            preferred_element_type=jnp.float32,
        )
        if metric == DistanceType.InnerProduct:
            d = dots
        else:
            # exact per-row norms: cached [L, pad] gather when available,
            # else recomputed from the gathered tile
            if row_norms is not None:
                vn2 = row_norms[probes]
            else:
                gf32 = g_data.astype(jnp.float32)
                vn2 = jnp.sum(gf32 * gf32, -1)
            if metric == DistanceType.CosineExpanded:
                vn = jnp.sqrt(jnp.maximum(vn2, 1e-20))
                qn = jnp.sqrt(jnp.maximum(row_norms_sq(qf), 1e-20))
                d = 1.0 - dots / (vn * qn[:, None, None])
            else:
                qn2 = row_norms_sq(qf)
                d = qn2[:, None, None] + vn2 - 2.0 * dots
                d = jnp.maximum(d, 0.0)
                if metric == DistanceType.L2SqrtExpanded:
                    d = jnp.sqrt(d)
        bad_fill = jnp.inf if minimize else -jnp.inf
        ok = g_valid
        if has_filter:
            ok = ok & bitset_filter_mask(g_idx, filter_words)
        d = jnp.where(ok, d, bad_fill)

        # ---- final top-k across all probed candidates (k may exceed the
        # candidate pool for tiny indexes; pad the tail with inf/-1)
        n_cand = n_probes * list_pad
        flat_d = d.reshape(qt.shape[0], n_cand)
        flat_i = g_idx.reshape(qt.shape[0], n_cand)
        flat_ok = ok.reshape(qt.shape[0], n_cand)
        if has_overflow:
            od, oi, o_ok = _overflow_scan(qt, qf, o_scan, o_norms, o_ok_base,
                                          overflow_indices, filter_words,
                                          metric, has_filter, fast_scan,
                                          bad_fill)
            flat_d = jnp.concatenate([flat_d, od], axis=1)
            flat_i = jnp.concatenate([flat_i, oi], axis=1)
            flat_ok = jnp.concatenate([flat_ok, o_ok], axis=1)
            n_cand += od.shape[1]
        kk = min(k, n_cand)
        if fast_scan:
            # bf16 expanded-L2 cancels catastrophically when distance gaps
            # are small next to vector norms (measured on v5e: recall
            # 0.9997 -> 0.57 on clustered data; CPU XLA upcasts bf16
            # matmuls, which is why CPU gates never caught it). Same cure
            # as brute_force's fast path: bf16 screen picks ~4k
            # candidates, exact fp32 re-rank orders them.
            k_ref = min(max(refine_mult * k, k + 8), n_cand)
            _, sel = _sel(flat_d, k_ref, minimize)
            cand_i = jnp.take_along_axis(flat_i, sel, axis=1)
            # re-mask from the real validity bits (pad + filter), the way
            # brute_force's re-rank does — screened-distance isfinite would
            # silently flip if a valid distance were ±inf or bad_fill ever
            # became finite
            cand_ok = jnp.take_along_axis(flat_ok, sel, axis=1)
            n_main = n_probes * list_pad
            sel_p = jnp.minimum(sel // list_pad, n_probes - 1)
            sel_s = sel % list_pad
            cand_list = jnp.take_along_axis(probes, sel_p, axis=1)
            main_vecs = list_data[cand_list, sel_s].astype(jnp.float32)
            if has_overflow:
                o_idx = jnp.clip(sel - n_main, 0, o_f32.shape[0] - 1)
                cand_vecs = jnp.where((sel < n_main)[:, :, None],
                                      main_vecs, o_f32[o_idx])
            else:
                cand_vecs = main_vecs
            exact = gathered_distances(qf, cand_vecs, metric)
            exact = jnp.where(cand_ok, exact, bad_fill)
            v, sel2 = select_k(exact, kk, select_min=minimize)
            i_out = jnp.take_along_axis(cand_i, sel2, axis=1)
        else:
            v, sel = _sel(flat_d, kk, minimize)
            i_out = jnp.take_along_axis(flat_i, sel, axis=1)
        if kk < k:
            v = jnp.pad(v, ((0, 0), (0, k - kk)), constant_values=bad_fill)
            i_out = jnp.pad(i_out, ((0, 0), (0, k - kk)), constant_values=-1)
        return v, i_out

    if n_q_tiles == 1:
        vals, idxs = q_body(qp)
    else:
        vals, idxs = jax.lax.map(
            q_body, qp.reshape(n_q_tiles, q_tile, dim)
        )
        vals = vals.reshape(-1, k)
        idxs = idxs.reshape(-1, k)
    return vals[:nq], idxs[:nq]


_search_jit = jax.jit(
    _search_core,
    static_argnames=("metric", "k", "n_probes", "q_tile", "has_filter",
                     "fast_scan",
                     "has_overflow", "select_recall", "refine_mult"),
)

#: public traceable-core name — the cross-package contract for the sharded
#: engine (parallel/sharded.py shard_maps this body) and the graftcheck
#: jaxpr audit; the underscore spelling stays package-private (R004)
search_core = _search_core


def _search_fused_core(queries, centers, list_data, list_indices, list_sizes,
                       row_norms, overflow_data, overflow_indices,
                       metric: DistanceType, k: int, n_probes: int,
                       pad_tile: int, has_overflow: bool,
                       interpret: bool = False):
    """Fused-Pallas search body (``scan_mode="pallas"``, L2 metrics only):
    coarse selection stays XLA, then the probed slabs are DMA'd straight
    to VMEM and merged into an in-kernel top-k carry
    (``ops.pallas_kernels.fused_ivf_topk``) — the [nq, P, pad] candidate
    slab never materializes in HBM and no ``select_k`` k-pad rule
    applies to the fine scan. Overflow rows (spilled past the capped
    list_pad) are scanned by the XLA brute pass in squared space and
    merged with the kernel's survivors through one unpadded ``select_k``."""
    from raft_tpu.ops import pallas_kernels as pk

    nq, dim = queries.shape
    list_pad = list_data.shape[1]
    qf = queries.astype(jnp.float32)

    # ---- coarse: top-n_probes clusters per query (XLA, tiny)
    scores, coarse_min = _coarse_scores(queries, centers, metric)
    _, probes = select_k(scores, n_probes, select_min=coarse_min)

    # unfilled slots must carry the -1 null id the kernel masks on; the
    # class invariant already puts -1 there, this re-derives it from
    # list_sizes so a stale slot can never alias a real row
    valid_slot = jnp.arange(list_pad)[None, :] < list_sizes[:, None]
    safe_ids = jnp.where(valid_slot, list_indices, -1)

    qv = jnp.broadcast_to(qf[:, None, :], (nq, n_probes, dim))
    qn = jnp.broadcast_to(row_norms_sq(qf)[:, None], (nq, n_probes))
    v, i = pk.fused_ivf_topk(probes, qv, qn, list_data, row_norms, safe_ids,
                             k, pad_tile=pad_tile, clamp=True,
                             interpret=interpret)

    if has_overflow:
        o_f32 = overflow_data.astype(jnp.float32)
        od, oi, _ = _overflow_scan(
            queries, qf, o_f32, row_norms_sq(o_f32),
            overflow_indices >= 0, overflow_indices,
            jnp.zeros((0,), jnp.uint32),
            # squared space: the kernel's carry is squared-L2; one sqrt at
            # the end covers both sources
            DistanceType.L2Expanded, False, False, jnp.inf)
        cand_v = jnp.concatenate([v, od], axis=1)
        cand_i = jnp.concatenate([i, oi], axis=1)
        # selection already happened in-kernel — the merge select runs with
        # pad_rules=False so the k-pad rules cannot double-pad it
        v, i = select_k(cand_v, k, select_min=True, indices=cand_i,
                        pad_rules=False)
    if metric == DistanceType.L2SqrtExpanded:
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


_search_fused_jit = jax.jit(
    _search_fused_core,
    static_argnames=("metric", "k", "n_probes", "pad_tile", "has_overflow",
                     "interpret"),
)

#: public traceable-core name for the fused path (R004; audited by
#: graftcheck --jaxpr-audit at the VMEM-budget canonical shape)
search_fused_core = _search_fused_core


def scan_bytes_per_query(n_probes: int, list_pad: int, dim: int) -> int:
    """TRUE peak live-set bytes of the flat scan per query: the gathered
    probe tile [P, pad, dim] fp32, ×2 for the distance/score temporaries
    live with it. The itemized accounting ``plan_scan_tiles`` solves
    against — public so the obs.costs calibration audit can compare the
    planner's prediction to the compiled ``memory_analysis`` truth."""
    return n_probes * list_pad * dim * 4 * 2


def plan_scan_tiles(n_probes: int, list_pad: int, dim: int,
                    workspace_limit_bytes: int) -> int:
    """q_tile from the workspace budget: the gathered probe tile is
    [q_tile, n_probes, list_pad, dim] fp32, ×2 for the distance/score
    temporaries that are live with it (shared by ``search`` and the
    graftcheck jaxpr audit, which certifies the solve statically)."""
    per_q = scan_bytes_per_query(n_probes, list_pad, dim)
    q_tile = int(np.clip(workspace_limit_bytes // max(per_q, 1), 1, 1024))
    if q_tile >= 8:
        q_tile -= q_tile % 8
    return q_tile


@tracing.range("ivf_flat.search")
def search(
    index: Index,
    queries,
    k: int,
    params: Optional[SearchParams] = None,
    filter: Optional[Bitset] = None,
    res: Optional[Resources] = None,
    explain: bool = False,
):
    """Search (reference: ivf_flat::search, ivf_flat-inl.cuh:430).

    Returns (distances [nq, k], indices [nq, k]); indices are source row ids,
    -1 where fewer than k valid candidates were probed. With
    ``explain=True`` a third element carries the
    :class:`raft_tpu.obs.explain.ExplainRecord` of the dispatch decision.
    """
    params = params or SearchParams()
    res = ensure_resources(res)
    if index.list_data is None:
        raise ValueError("index has no data; call extend() first")
    queries = as_query_array(queries)  # host inputs stay host-side: the
    if queries.shape[1] != index.dim:  # jit call transfers the padded
        raise ValueError(              # batch in ONE dispatch
            f"query dim {queries.shape[1]} != index dim {index.dim}")
    nq = queries.shape[0]
    queries = pad_rows(queries, query_bucket(nq))  # serving batch bucket
    n_probes = int(min(params.n_probes, index.n_lists))
    list_pad = index.list_data.shape[1]
    q_tile = plan_scan_tiles(n_probes, list_pad, index.dim,
                             res.workspace_limit_bytes)
    from raft_tpu.ops import pallas_kernels as pk

    fast_scan = params.scan_dtype is not None
    scan_mode = getattr(params, "scan_mode", "auto")
    if scan_mode not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"scan_mode={scan_mode!r}: expected 'auto', 'xla' or 'pallas'")
    if fast_scan:
        if jnp.dtype(params.scan_dtype) != jnp.bfloat16:
            raise ValueError(
                f"scan_dtype={params.scan_dtype!r}: only bfloat16 is supported")
        if index.list_data.dtype != jnp.float32:
            raise ValueError("scan_dtype requires fp32 list data")
    has_overflow = index.overflow_data.shape[0] > 0
    # ---- fused Pallas scan+select (the VMEM top-k carry). Fallback
    # matrix (docs/tuning.md): L2 metrics, no filter (no in-carry filter
    # epilogue), no bf16 fast scan, small k.
    use_fused, fused_interp, dreason = pk.fused_dispatch_explained(
        "ivf_flat", scan_mode)
    ineligible = fused_ineligible_reason(
        index.metric, index.list_data.dtype, int(k), filter is not None,
        fast_scan, require_float=False)
    pk.require_compiled_kernel("ivf_flat", scan_mode, ineligible)
    ex_params = {"k": int(k), "nq": nq, "bucket": queries.shape[0],
                 "n_probes": n_probes, "n_lists": index.n_lists,
                 "list_pad": list_pad, "dim": index.dim,
                 "metric": index.metric.name}
    with contextlib.ExitStack() as stack:
        cap = stack.enter_context(obs_explain.capture()) if explain else None
        if use_fused and ineligible is None:
            pad_tile = pk.plan_fused_ivf_tile(
                list_pad, index.dim, int(k),
                jnp.dtype(index.list_data.dtype).itemsize,
                n_probes=n_probes)
            obs_explain.record_dispatch(
                "ivf_flat", scan_mode, "pallas", dreason, params=ex_params,
                plan={"pad_tile": pad_tile, "interpret": fused_interp})
            v, i = _search_fused_jit(
                queries, index.centers, index.list_data, index.list_indices,
                index.list_sizes, index.ensure_row_norms(),
                index.overflow_data, index.overflow_indices,
                index.metric, int(k), n_probes, pad_tile, has_overflow,
                fused_interp,
            )
        else:
            reason = ineligible if (use_fused and ineligible) else dreason
            obs_explain.record_dispatch(
                "ivf_flat", scan_mode, "xla", reason, params=ex_params,
                plan={"q_tile": q_tile,
                      "predicted_workspace_bytes": q_tile *
                      scan_bytes_per_query(n_probes, list_pad, index.dim)})
            # Cached exact norms are required by the bf16 fast scan; the
            # fp32 path keeps computing norms per probed tile instead
            # (materializing [L, pad] fp32 norms for a large narrow-dtype
            # index is a needless device-memory spike there).
            need_norms = (fast_scan
                          and index.metric != DistanceType.InnerProduct)
            v, i = _search_jit(
                queries, index.centers, index.list_data, index.list_indices,
                index.list_sizes,
                filter.words if filter is not None
                else jnp.zeros((0,), jnp.uint32),
                index.metric, int(k), n_probes, q_tile, filter is not None,
                index.ensure_row_norms() if need_norms else None, fast_scan,
                index.overflow_data, index.overflow_indices,
                has_overflow, float(params.select_recall),
                refine_multiplier(params.refine_ratio, fast_scan),
            )
    if explain:
        return v[:nq], i[:nq], cap.last
    return v[:nq], i[:nq]


_SERIAL_VERSION = 2  # v2: + list_pad_expansion, overflow block


def serialize(index: Index, file) -> None:
    """reference: detail/ivf_flat_serialize.cuh. Paths are written
    atomically (tmp + os.replace) with per-record crc framing."""
    if index.list_data is None:
        raise ValueError("index has no data; call extend() before serialize()")
    with ser.writer_for(file) as stream:
        w = ser.IndexWriter(stream, "ivf_flat", _SERIAL_VERSION)
        w.scalar(int(index.metric), "<i4")
        w.scalar(index.params.n_lists, "<i8")
        w.scalar(index.params.kmeans_n_iters, "<i4")
        w.scalar(index.params.kmeans_trainset_fraction, "<f8")
        w.scalar(1 if index.params.adaptive_centers else 0, "<i4")
        w.scalar(index.params.list_pad_expansion, "<f8")
        w.scalar(index.n_rows, "<i8")
        w.array(index.centers)
        w.array(index.list_data)
        w.array(index.list_indices)
        w.array(index.list_sizes)
        w.array(index.overflow_data)
        w.array(index.overflow_indices)
        w.finish()


def deserialize(file, res: Optional[Resources] = None) -> Index:
    ensure_resources(res)
    with ser.reader_for(file) as stream:
        r = ser.IndexReader(stream, "ivf_flat", _SERIAL_VERSION)
        metric = DistanceType(r.scalar())
        params = IndexParams(
            n_lists=r.scalar(), metric=metric, kmeans_n_iters=r.scalar(),
            kmeans_trainset_fraction=r.scalar(),
            adaptive_centers=bool(r.scalar()),
            # v1 files predate the capped pad: max-driven layout, no spill
            list_pad_expansion=r.scalar() if r.version >= 2 else 1e30,
        )
        n_rows = r.scalar()
        centers = jnp.asarray(r.array())
        data = jnp.asarray(r.array())
        idxs = jnp.asarray(r.array())
        sizes = jnp.asarray(r.array())
        over_rows = jnp.asarray(r.array()) if r.version >= 2 else None
        over_ids = jnp.asarray(r.array()) if r.version >= 2 else None
        r.finish()
        return Index(params, centers, data, idxs, sizes, n_rows,
                     over_rows, over_ids)


# ------------------------------------------------------------------ helpers


class helpers:
    """List-data access utilities (reference: ivf_flat_helpers.cuh /
    ivf_flat_codepacker.hpp — ``helpers::codepacker::{pack,unpack}``).
    Our list storage is already a padded dense block, so pack/unpack are
    plain placements rather than interleaved-group bit shuffles."""

    @staticmethod
    def unpack_list_data(index: "Index", label: int) -> np.ndarray:
        """Valid vectors of list ``label`` → [size, dim] host array."""
        size = int(np.asarray(index.list_sizes)[label])
        return np.asarray(index.list_data)[label, :size]

    @staticmethod
    def unpack_list_ids(index: "Index", label: int) -> np.ndarray:
        size = int(np.asarray(index.list_sizes)[label])
        return np.asarray(index.list_indices)[label, :size]

    @staticmethod
    def pack_list_data(index: "Index", label: int, vectors,
                       ids=None) -> "Index":
        """Overwrite list ``label`` with ``vectors`` (and optional ids);
        returns a new Index (functional analog of in-place pack)."""
        vectors = np.asarray(vectors, np.asarray(index.list_data).dtype)
        n_new = len(vectors)
        pad = index.list_data.shape[1]
        if n_new > pad:
            raise ValueError(f"{n_new} vectors exceed list capacity {pad}")
        data = np.asarray(index.list_data).copy()
        idxs = np.asarray(index.list_indices).copy()
        sizes = np.asarray(index.list_sizes).copy()
        data[label, :n_new] = vectors
        data[label, n_new:] = 0
        if ids is not None:
            idxs[label, :n_new] = np.asarray(ids, np.int32)
        idxs[label, n_new:] = -1
        old = int(sizes[label])
        sizes[label] = n_new
        n_rows = index.n_rows - old + n_new
        return Index(index.params, index.centers, jnp.asarray(data),
                     jnp.asarray(idxs), jnp.asarray(sizes), n_rows)
