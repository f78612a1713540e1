"""Brute-force (exact) k-nearest neighbors.

Reference: ``raft::neighbors::brute_force`` (neighbors/brute_force-inl.cuh,
detail/knn_brute_force.cuh) — ``tiled_brute_force_knn`` picks tile sizes from
free memory (:84), precomputes row norms (:97-136), runs a cuBLAS gemm +
epilogue per tile, ``select_k`` per tile, then ``knn_merge_parts``
(detail/knn_merge_parts.cuh). A persistent ``brute_force::index`` caches the
dataset and its norms (brute_force_types.hpp).

TPU-native design: the distance tile is a bf16/fp32 ``dot_general`` on the MXU
with the metric epilogue fused by XLA; per-tile top-k via ``select_k``; tiles
merged pairwise by concatenating the k-candidate lists and re-selecting —
identical math to knn_merge_parts but expressed as one more top-k. Exact
scans rank 128-row group minima carried across the tiles instead
(``_group_topk``); on a TPU one Pallas kernel makes each tile and its minima
(``ops.pallas_kernels.group_scan_tile``). Query batches stream through a
``lax.map`` so HBM holds only [q_tile, db_tile] distances. Doubles as the exact ground-truth oracle for ANN tests (replacing
the reference's internal naive_knn.cuh:82).
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core import serialize as ser
from raft_tpu.core import tracing
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.ops.distance import (
    DistanceType,
    cosine_expanded,
    gathered_distances,
    inner_product,
    is_min_close,
    l2_expanded,
    resolve_metric,
    row_norms_sq,
    pairwise_core,
)
from raft_tpu.obs import explain as obs_explain
from raft_tpu.obs import metrics as obs_metrics
from raft_tpu.ops import pallas_kernels as pk
from raft_tpu.ops.select_k import (refine_multiplier, select_k,
                                   select_k_maybe_approx)
from raft_tpu.utils.shape import (as_query_array, balanced_tile, cdiv, pad_rows,
                                  query_bucket)


class Index:
    """Persistent brute-force index: dataset + cached norms
    (reference: brute_force_types.hpp)."""

    def __init__(self, dataset: jax.Array, metric: DistanceType, metric_arg: float,
                 norms: Optional[jax.Array] = None):
        self.dataset = dataset
        self.metric = metric
        self.metric_arg = metric_arg
        self.norms = norms

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]


#: metrics whose expanded distances use the rows' squared norms
NORM_METRICS = (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                DistanceType.CosineExpanded)


@tracing.range("brute_force.build")
def build(dataset, metric="euclidean", metric_arg: float = 2.0,
          res: Optional[Resources] = None) -> Index:
    """Build = store dataset + precompute norms for expanded metrics
    (reference: brute_force::build, brute_force-inl.cuh)."""
    ensure_resources(res)
    dataset = jnp.asarray(dataset)
    m = resolve_metric(metric)
    norms = row_norms_sq(dataset) if m in NORM_METRICS else None
    return Index(dataset, m, float(metric_arg), norms)


#: rows per group of the exact scan's group minima: one 128-lane row, so
#: a group the scan keeps is gathered whole
GROUP = 128


def _choose_tiles(n_queries: int, n_db: int, dim: int, k: int, budget: int
                  ) -> Tuple[int, int]:
    """Pick (query_tile, db_tile) so the distance tile fits the workspace
    budget (analog of chooseTileSize, detail/knn_brute_force.cuh:84).

    The budget pays for ~5 concurrent fp32 tiles in the expanded-L2 chain
    (dot, norm-add, clamp, mask-select, selection) — the graftcheck jaxpr
    audit certifies the resulting peak statically. The scan makes no
    padded copy of the database. A db tile is whole groups of ``GROUP``
    rows unless it is the whole database, so that exact scans take the
    group minima (``_scan_tiles``)."""
    q_tile = balanced_tile(n_queries, min(n_queries, 1024), 8)
    db_budget = max(budget // (5 * max(q_tile, 1) * 4), 1)
    db_tile = max(db_budget, 4 * k, 1024)
    if db_tile >= n_db:
        return q_tile, max(n_db, 1)
    return q_tile, balanced_tile(n_db, db_tile - db_tile % GROUP, GROUP)


#: public planner name — consumed by the graftcheck jaxpr audit, which
#: certifies the solve statically against the workspace budget (R004)
choose_tiles = _choose_tiles


def planned_peak_bytes(n_queries: int, n_db: int, dim: int, k: int,
                       budget: int) -> int:
    """The peak live set ``choose_tiles`` believes its solve yields: the
    5 concurrent fp32 distance tiles of the expanded-L2 chain at the
    planned (q_tile, db_tile). Public so the obs.costs calibration audit
    can compare this prediction against the compiled ``memory_analysis``
    ground truth at the same shape."""
    q_tile, db_tile = _choose_tiles(n_queries, n_db, dim, k, budget)
    return 5 * q_tile * db_tile * 4


#: metrics eligible for the bf16 fast-scan (their scan is one MXU matmul and
#: their exact distance is recoverable from gathered candidates at refine)
_FAST_SCAN_METRICS = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.CosineExpanded,
    DistanceType.InnerProduct,
)


def _takes_groups(n_db: int, db_tile: int, k: int,
                  select_recall: float) -> bool:
    """Whether ``_scan_tiles`` takes ``_group_topk``: an exact scan whose
    tiles hold at least ``k`` groups of ``GROUP`` rows, the tiles whole
    groups unless there is one, so that only the last tile pads its last
    group, past every row."""
    return (select_recall >= 1.0 and k * GROUP <= db_tile <= n_db
            and (db_tile % GROUP == 0 or db_tile == n_db))


#: metrics whose tiles the group kernel makes (``pk.group_scan_tile``)
_GROUP_KERNEL_METRICS = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.InnerProduct,
)

#: platforms the group kernel runs on: compiled on a TPU, under the Mosaic
#: interpreter on any other listed here (parity tests add "cpu")
_GROUP_KERNEL_PLATFORMS = ("tpu",)

_GROUP_SCAN_PLANS = obs_metrics.REGISTRY.counter(
    "raft_tpu_group_scan_plans_total",
    "Exact group-minima scans planned (at trace time) by tile producer.",
    ("producer",))


class GroupScan(NamedTuple):
    """How an exact group scan makes its tiles (``plan_group_scan``):
    ``producer`` "pallas" (``pk.group_scan_tile``, ``gb`` groups a step,
    reading the collection's [dim, rows] view when ``lanes_rows``, under
    the interpreter when ``interpret``) or "xla" (``_tile_groups``), and
    the ``obs.explain`` reason code."""
    producer: str
    reason: str
    gb: int = 0
    lanes_rows: bool = False
    interpret: bool = False


def _k_scan(k: int, db_tile: int, fast_scan: bool, refine_mult: int) -> int:
    """Candidates the scan keeps: the fast scan over-selects them, and its
    exact fp32 re-rank recovers the k best."""
    return min(refine_mult * k, db_tile) if fast_scan else min(k, db_tile)


def plan_group_scan(metric: DistanceType, dtype, device, q_tile: int,
                    n_db: int, db_tile: int, dim: int, k: int,
                    fast_scan: bool = False, refine_mult: int = 1,
                    select_recall: float = 1.0) -> Optional[GroupScan]:
    """The tile producer of ``_knn_jit``'s scan of ``n_db`` rows on
    ``device`` (None when the scan does not take the group minima): the
    kernel where every clause holds, else XLA's tile with the reason code
    of the first that fails. Callers record it (``record_group_scan``) and
    pass it to ``_knn_jit``, so the record names what runs."""
    if not _takes_groups(n_db, db_tile,
                         _k_scan(k, db_tile, fast_scan, refine_mult),
                         select_recall):
        return None
    if device.platform not in _GROUP_KERNEL_PLATFORMS:
        return GroupScan("xla", "tpu_absent")
    if fast_scan:
        return GroupScan("xla", "fast_scan")
    if metric not in _GROUP_KERNEL_METRICS:
        return GroupScan("xla", "unsupported_metric")
    if jnp.dtype(dtype) != jnp.float32:
        return GroupScan("xla", "not_float32")
    lanes_rows = pk.rows_on_lanes(device, dtype, (n_db, dim))
    gb = pk.plan_group_scan(q_tile, db_tile, dim, lanes_rows,
                            aligned_to=db_tile if db_tile < n_db else 0)
    if not gb:
        return GroupScan("xla", "query_tile_vmem")
    return GroupScan("pallas", "group_kernel", gb, lanes_rows,
                     device.platform != "tpu")


def record_group_scan(plan: Optional[GroupScan], q_tile: int, db_tile: int,
                      params: dict) -> None:
    """Emit an exact group scan's tile producer as an explain record
    (family ``brute_force_group_scan``); nothing for a scan without
    group minima (``plan`` None)."""
    if plan is None:
        return
    obs_explain.record_dispatch(
        "brute_force_group_scan", "auto", plan.producer, plan.reason,
        params=params,
        plan={"q_tile": q_tile, "db_tile": db_tile,
              "rows_per_step": plan.gb * GROUP,
              "rows_on_lanes": plan.lanes_rows,
              "interpret": plan.interpret})


def _scan_tiles(nq: int, n_db: int, db_tile: int, k: int, tile_dist,
                select_min: bool, select_recall: float, groups=None):
    """The database-tile loop of every brute-force scan. ``tile_dist(start,
    width)`` gives the [nq, width] distances of rows ``[start, start +
    width)``; this runs it over whole tiles of ``db_tile`` rows and once
    over the remainder. No padded copy of the database is made, and one
    tile's distances are live at a time (the reference's
    tiled_brute_force_knn, detail/knn_brute_force.cuh). Returns candidates
    ``(values, row ids)`` [nq, m], m ≥ k when n_db ≥ k, holding the k best.

    An exact scan that ``_takes_groups`` runs ``_group_topk``, which never
    ranks a whole tile, over ``groups`` (the group kernel's tiles) or
    else over ``tile_dist``'s. Otherwise each tile keeps its ``min(k,
    width)`` best by ``select_k`` (APPROX below ``select_recall`` 1) and
    the candidates are pooled in row order (the analog of
    knn_merge_parts)."""
    if _takes_groups(n_db, db_tile, k, select_recall):
        return _group_topk(nq, n_db, db_tile, k,
                           groups or _tile_groups(tile_dist, select_min),
                           select_min)

    def tile_topk(start, width):
        v, i = select_k_maybe_approx(tile_dist(start, width), min(k, width),
                                     select_min, select_recall)
        return v, i + start

    n_full, rem = divmod(n_db, db_tile)
    vs, ids = [], []
    if n_full:
        tv, ti = jax.lax.map(lambda t: tile_topk(t * db_tile, db_tile),
                             jnp.arange(n_full))
        vs.append(jnp.moveaxis(tv, 0, 1).reshape(tv.shape[1], -1))
        ids.append(jnp.moveaxis(ti, 0, 1).reshape(ti.shape[1], -1))
    if rem:
        v, i = tile_topk(n_full * db_tile, rem)
        vs.append(v)
        ids.append(i)
    return jnp.concatenate(vs, axis=1), jnp.concatenate(ids, axis=1)


def _tile_groups(tile_dist, select_min: bool):
    """XLA's producer of a tile's groups for ``_group_topk``: a gather of
    the tile's values (distances, negated when selecting the largest:
    negation is exact) by group, the remainder's pad +inf past every row,
    and each group's minimum [nq, n_g]."""
    def groups(start, width):
        d = tile_dist(start, width)
        d = d if select_min else -d
        n_g = cdiv(width, GROUP)
        if n_g * GROUP > width:
            d = jnp.pad(d, ((0, 0), (0, n_g * GROUP - width)),
                        constant_values=jnp.inf)
        d = d.reshape(d.shape[0], n_g, GROUP)
        return (lambda sel: jnp.take_along_axis(d, sel[..., None], axis=1),
                d.min(axis=-1))

    return groups


def _groups_major_take(tile, sel):
    """The values of groups ``sel`` [q, m] of the group kernel's tile [n_g,
    q, GROUP] as [q, m, GROUP], gathered as rows of its [n_g·q, GROUP]
    view (a bitcast): a v5e gathers those faster than from the batched
    [q, n_g, GROUP] view."""
    n_g, q = tile.shape[:2]
    at = sel * q + jax.lax.broadcasted_iota(sel.dtype, sel.shape, 0)
    return jnp.take(tile.reshape(n_g * q, GROUP), at, axis=0)


#: most kept groups a merge (query-tile rows × k) for which
#: ``_group_topk`` takes each tile's best k groups by ``lax.top_k`` before
#: the sort: XLA keeps such a top_k a partial selection (a TopK call)
#: rather than a full sort. One v5e, 1M × 128, best of 7 (PERF.md §6,
#: PR 28): at 64 queries × k 10 (640) the top_k first takes 2.877 ms a
#: search, sorting every minimum 3.330; at 8 × k 100 (800) 2.449 against
#: 2.361, and sorting wins at every larger shape measured.
_TOPK_MERGE_MAX = 640


def _total_order(x):
    """float32 ``x`` as int32 keys that rank as ``lax.top_k`` ranks floats:
    IEEE total order, -0.0 ahead of +0.0 (``lax.sort`` of the floats would
    tie them)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _group_topk(nq: int, n_db: int, db_tile: int, k: int, groups,
                select_min: bool):
    """Exact top-k by group minima, carried across the tiles; the same
    values and ids as one ``lax.top_k`` over the whole row, ties to the
    lower row.

    ``groups(start, width)`` gives ``take``, which gathers the tile's
    values of groups ``sel`` [nq, m] as [nq, m, GROUP] (least is best), and
    each group's minimum [nq, n_g] (``_tile_groups`` or the group kernel,
    ``_groups_major_take``). The k groups of least minimum (ties to the
    lower group) hold the k best rows: a row outside them is no better
    than any of those k minima, which are k distinct rows ranked ahead of
    it. The carry holds the k best groups so far (minimum as its
    ``_total_order`` key, the rows' values, first row), in row order; each
    tile's group minima compete with it in one stable sort over width /
    GROUP + k keys, and one top-k over the k·GROUP kept values ends the
    scan; the values of a scan selecting the largest are negated back."""
    g = GROUP

    def merge(carry, start, width):
        # The tiles go from the last down and the first carry's groups are
        # empty ones past every row, so the tile's rows precede the
        # carried ones: the tile's candidates come first, equal minima
        # among them in row order, and the stable sort sends ties to the
        # lower row. Each group's first row rides through both sorts as a
        # payload, and the sorted keys are the kept minima: element
        # gathers of them cost a TPU more than the sorts.
        c_keys, c_rows, c_first = carry
        take, mins = groups(start, width)
        nq_t, n_t = mins.shape
        if nq_t * k > _TOPK_MERGE_MAX:
            at = jax.lax.broadcasted_iota(jnp.int32, (nq_t, n_t), 1)
            # one iota, which XLA's stable sort takes as its own tie-break
            # rather than adding a fourth operand (10 ms a call at the
            # deep cell's shard)
            pos = jax.lax.broadcasted_iota(jnp.int32, (nq_t, n_t + k), 1)
        else:
            # the tile's best groups first, ties to the lower group
            v, at = jax.lax.top_k(-mins, min(k, n_t))
            mins = -v
            pos = jnp.concatenate([at, n_t + jax.lax.broadcasted_iota(
                jnp.int32, (nq_t, k), 1)], axis=1)
        keys, sel, first = jax.lax.sort(
            (jnp.concatenate([_total_order(mins), c_keys], axis=1), pos,
             jnp.concatenate([start + g * at, c_first], axis=1)),
            dimension=1, is_stable=True, num_keys=1)
        # the k least, back in position order
        sel, keys, first = jax.lax.sort(
            (sel[:, :k], keys[:, :k], first[:, :k]), dimension=1, num_keys=1)
        in_tile = sel < n_t
        ts, cs = jnp.minimum(sel, n_t - 1), jnp.maximum(sel - n_t, 0)
        return (keys,
                jnp.where(in_tile[..., None], take(ts),
                          jnp.take_along_axis(c_rows, cs[..., None], 1)),
                first)

    carry = (jnp.full((nq, k), _total_order(jnp.float32(jnp.inf))),
             jnp.full((nq, k, g), jnp.inf, jnp.float32),
             jnp.full((nq, k), n_db, jnp.int32))
    n_full, rem = divmod(n_db, db_tile)
    if rem:
        carry = merge(carry, n_full * db_tile, rem)
    carry, _ = jax.lax.scan(
        lambda c, t: (merge(c, t * db_tile, db_tile), None), carry,
        jnp.arange(n_full - 1, -1, -1))
    _, rows, first_row = carry
    pos = (first_row[..., None] + jnp.arange(g, dtype=first_row.dtype)
           ).reshape(nq, k * g)
    v, sel = jax.lax.top_k(-rows.reshape(nq, k * g), k)
    return (-v if select_min else v), jnp.take_along_axis(pos, sel, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("metric", "metric_arg", "k", "q_tile", "db_tile",
                     "budget", "has_filter", "fast_scan", "refine_mult",
                     "select_recall", "group_scan"),
)
def _knn_jit(queries, dataset, db_norms, filter_words, metric, metric_arg, k,
             q_tile, db_tile, budget, has_filter: bool = False,
             fast_scan: bool = False, refine_mult: int = 4,
             select_recall: float = 1.0, n_valid=None,
             group_scan: Optional[GroupScan] = None):
    """Exact kNN core, tiled over queries and database rows. ``n_valid``
    (may be traced) masks rows at or past it as padding: the last shard of
    a row-sharded collection (``parallel.sharded.knn``). ``group_scan``
    (``plan_group_scan``) names the producer of a group scan's tiles;
    without it they are XLA's."""
    nq, dim = queries.shape
    minimize = is_min_close(metric)

    def _sel(vals, kk, sel_min):
        return select_k_maybe_approx(vals, kk, sel_min, select_recall)
    use_cached_norms = db_norms is not None and metric in NORM_METRICS

    n_q_tiles = cdiv(nq, q_tile)
    q_pad = n_q_tiles * q_tile - nq

    qp = jnp.pad(queries, ((0, q_pad), (0, 0)))
    n_db = dataset.shape[0]
    k_scan = _k_scan(k, db_tile, fast_scan, refine_mult)
    # Refine pool must still hold >= k candidates when db_tile < k; the
    # pooled tiles hold >= min(k_scan, n_db) >= k entries.
    k_refine = max(k_scan, k)
    kernel = False
    if _takes_groups(n_db, db_tile, k_scan, select_recall):
        kernel = group_scan is not None and group_scan.producer == "pallas"
        _GROUP_SCAN_PLANS.labels("pallas" if kernel else "xla").inc()
    l2_kernel = kernel and metric != DistanceType.InnerProduct
    need_norms = use_cached_norms or l2_kernel or (
        fast_scan and metric != DistanceType.InnerProduct)
    if use_cached_norms:
        dbn = db_norms
    elif need_norms:
        dbn = row_norms_sq(dataset)
    else:
        dbn = None
    bad_fill = jnp.inf if minimize else -jnp.inf

    def _filter_pass(ids):
        """Packed-bitset test for row ids (shared by scan + refine)."""
        words = filter_words[jnp.minimum(ids // 32, filter_words.shape[0] - 1)]
        return ((words >> (ids % 32).astype(jnp.uint32)) & 1).astype(bool)

    def _bad_rows(ids):
        """Rows that never answer: padding past ``n_valid`` and rows the
        bitset filter clears (reference: bitset_filter,
        sample_filter_types.hpp:55-82)."""
        bad = jnp.zeros(ids.shape, bool) if n_valid is None else ids >= n_valid
        return bad | ~_filter_pass(ids) if has_filter else bad

    if kernel:
        # one term a database row: its squared norm for L2, 0 for inner
        # product, +inf where the filter clears it
        row_terms = dbn if l2_kernel else jnp.zeros((n_db,), jnp.float32)
        if has_filter:
            row_terms = jnp.where(_filter_pass(jnp.arange(n_db)), row_terms,
                                  jnp.inf)

    def q_body(qt):
        # Query-tile norms hoisted out of the db-tile loop (analog of the
        # reference's rowNorm precompute, detail/knn_brute_force.cuh:97-136).
        qt_norms = row_norms_sq(qt) if need_norms else None
        qt_bf = qt.astype(jnp.bfloat16) if fast_scan else None

        def kernel_groups(start, width):
            end = start + width
            tile, mins = pk.group_scan_tile(
                qt, qt_norms if l2_kernel else jnp.zeros((q_tile,)), dataset,
                row_terms, start,
                end if n_valid is None else jnp.minimum(n_valid, end),
                width=width, gb=group_scan.gb,
                lanes_rows=group_scan.lanes_rows, l2=l2_kernel,
                sqrt=metric == DistanceType.L2SqrtExpanded,
                negate=not minimize, filtered=has_filter,
                interpret=group_scan.interpret)
            return functools.partial(_groups_major_take, tile), mins

        def tile_dist(start, width):
            db_t = jax.lax.dynamic_slice_in_dim(dataset, start, width, 0)
            if fast_scan:
                # Single-pass bf16 MXU matmul (the TPU analog of the
                # reference's TF32/CUTLASS fast path, dispatch_sm80.cuh):
                # bf16 inputs take _dot's fast-precision path while the
                # precomputed norms stay fp32, so only the cross term is
                # approximate. Ranking-only score: sqrt skipped for
                # L2SqrtExpanded (monotone); exact distances come from the
                # refine stage.
                db_bf = db_t.astype(jnp.bfloat16)
                if metric == DistanceType.InnerProduct:
                    d = inner_product(qt_bf, db_bf)
                elif metric == DistanceType.CosineExpanded:
                    dbn_t = jax.lax.dynamic_slice_in_dim(
                        dbn, start, width, 0)
                    d = cosine_expanded(qt_bf, db_bf, x_norms=qt_norms,
                                        y_norms=dbn_t)
                else:
                    dbn_t = jax.lax.dynamic_slice_in_dim(
                        dbn, start, width, 0)
                    d = l2_expanded(qt_bf, db_bf, sqrt=False,
                                    x_norms=qt_norms, y_norms=dbn_t)
            elif use_cached_norms:
                dbn_t = jax.lax.dynamic_slice_in_dim(dbn, start, width, 0)
                if metric == DistanceType.CosineExpanded:
                    d = cosine_expanded(qt, db_t, x_norms=qt_norms, y_norms=dbn_t)
                else:
                    d = l2_expanded(
                        qt, db_t, sqrt=(metric == DistanceType.L2SqrtExpanded),
                        x_norms=qt_norms, y_norms=dbn_t,
                    )
            else:
                d = pairwise_core(qt, db_t, metric, metric_arg, budget)
            if n_valid is not None or has_filter:
                bad = _bad_rows(start + jnp.arange(width))
                d = jnp.where(bad[None, :], bad_fill, d)
            return d

        all_v, all_i = _scan_tiles(q_tile, n_db, db_tile, k_scan, tile_dist,
                                   minimize, select_recall,
                                   kernel_groups if kernel else None)
        if fast_scan:
            # Exact fp32 re-rank of the scanned candidates (reference analog:
            # neighbors::refine over a coarse candidate list).
            _, sel = _sel(all_v, min(k_refine, all_v.shape[-1]), minimize)
            cand_i = jnp.take_along_axis(all_i, sel, axis=1)
            cand_vecs = jnp.take(dataset, cand_i, axis=0)  # [q_tile, k_ref, dim]
            exact = gathered_distances(qt, cand_vecs, metric)
            # Re-mask padded/filtered rows (their gathered distance is real).
            if n_valid is not None or has_filter:
                exact = jnp.where(_bad_rows(cand_i), bad_fill, exact)
            v, sel2 = select_k(exact, k, select_min=minimize)
            return v, jnp.take_along_axis(cand_i, sel2, axis=1)
        v, sel = select_k(all_v, k, select_min=minimize)
        return v, jnp.take_along_axis(all_i, sel, axis=1)

    if n_q_tiles == 1:
        vals, idxs = q_body(qp)
    else:
        vq = jax.lax.map(q_body, qp.reshape(n_q_tiles, q_tile, dim))
        vals = vq[0].reshape(-1, k)
        idxs = vq[1].reshape(-1, k)
    return vals[:nq], idxs[:nq]


#: public traceable-core name — consumed by the graftcheck jaxpr audit
#: (R004: the underscore spelling stays package-private)
knn_core = _knn_jit


@functools.partial(
    jax.jit, static_argnames=("k", "tm", "tn", "sqrt", "interpret"))
def _knn_fused_jit(queries, dataset, db_norms, k: int, tm: int, tn: int,
                   sqrt: bool, interpret: bool):
    """Fused-Pallas brute-force core: the [nq, ndb] distance slab never
    touches HBM — each [tm, tn] tile feeds the VMEM-resident top-k carry
    (``ops.pallas_kernels.fused_l2_topk``). Selection happens in-kernel,
    so no ``select_k`` call and no k-pad rule applies here."""
    qn = row_norms_sq(queries)
    dbn = row_norms_sq(dataset) if db_norms is None else db_norms
    v, i = pk.fused_l2_topk(queries, dataset, k, x_norms=qn, y_norms=dbn,
                            tm=tm, tn=tn, interpret=interpret)
    if sqrt:
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


#: public traceable-core name for the fused path (R004; audited by
#: graftcheck --jaxpr-audit at the VMEM-budget canonical shape)
knn_fused_core = _knn_fused_jit


#: metrics the fused scan+select kernel serves exactly (the minimize-only
#: VMEM carry is not rank-safe for IP/cosine without negation plumbing)
_FUSED_SCAN_METRICS = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
)


def _fused_eligible(index: Index, k: int, has_filter: bool,
                    fast_scan: bool) -> bool:
    """The fallback matrix for ``scan_mode="pallas"`` (docs/tuning.md):
    L2 metrics, float data, small k, no bitset filter (the kernel has no
    in-carry filter epilogue), not combined with the bf16 fast scan."""
    return fused_ineligible_reason(index.metric, index.dataset.dtype, k,
                                   has_filter, fast_scan) is None


def fused_ineligible_reason(metric, dtype, k: int, has_filter: bool,
                            fast_scan: bool,
                            require_float: bool = True) -> Optional[str]:
    """First failing clause of the fused fallback matrix as an
    ``obs.explain`` reason code, or None when fully eligible — shared by
    brute_force and ivf_flat (same conjunction, except ivf_flat's fused
    scan accepts narrow list dtypes → ``require_float=False``) so the
    explain record names the same cause docs/tuning.md documents."""
    if metric not in _FUSED_SCAN_METRICS:
        return "non_l2"
    if has_filter:
        return "filtered"
    if fast_scan:
        return "fast_scan"
    if k > 1024:
        return "k_gt_1024"
    if require_float and not jnp.issubdtype(dtype, jnp.floating):
        return "non_float_dtype"
    return None


@tracing.range("brute_force.search")
def search(index: Index, queries, k: int, filter=None,
           res: Optional[Resources] = None, scan_dtype=None,
           refine_ratio: float = 4.0,
           select_recall: float = 1.0,
           scan_mode: str = "auto",
           explain: bool = False):
    """Exact kNN search → (distances [nq, k], indices [nq, k]).

    ``filter`` is an optional :class:`raft_tpu.core.bitset.Bitset` over
    database row ids; cleared bits are excluded (reference: the
    bitset_filter overloads of brute_force::search).

    ``scan_dtype="bfloat16"`` (fp32 data, expanded-L2/cosine/inner-product
    metrics only) runs the distance matmul as a single bf16 MXU pass and
    exactly re-ranks the top ``refine_ratio·k`` candidates in fp32 — the TPU
    analog of the reference's TF32/CUTLASS Ampere path (detail/
    pairwise_matrix/dispatch_sm80.cuh). Returned distances are exact fp32;
    ranking is exact except for candidates the bf16 screen misses
    (recall ≥ 0.999 at refine_ratio=4 in practice).

    ``scan_mode`` selects the scan/select engine: ``"xla"`` forces the
    tiled XLA two-step, ``"pallas"`` requests the fused Pallas
    scan+select kernel (VMEM-resident top-k carry, docs/tuning.md), and
    ``"auto"`` picks pallas on TPU only where the committed probe artifact
    shows it winning. Unsupported combinations (non-L2 metric, filter,
    fast scan, k > 1024, CPU without the interpret hook) fall back to XLA
    silently — the mode is a performance hint, never a correctness
    switch. Every resolution is attributed: a reason-coded dispatch
    counter increments per call, and ``explain=True`` additionally
    returns ``(distances, indices, ExplainRecord)``."""
    res = ensure_resources(res)
    if scan_mode not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"scan_mode={scan_mode!r}: expected 'auto', 'xla' or 'pallas'")
    # host inputs stay host-side: the jit call transfers the padded
    # batch in ONE dispatch
    queries = as_query_array(queries, dtype=index.dataset.dtype)
    if queries.shape[1] != index.dim:
        raise ValueError(f"query dim {queries.shape[1]} != index dim {index.dim}")
    k = int(min(k, index.size))
    fast_scan = scan_dtype is not None
    if fast_scan:
        if jnp.dtype(scan_dtype) != jnp.bfloat16:
            raise ValueError(
                f"scan_dtype={scan_dtype!r}: only bfloat16 is supported")
        if index.dataset.dtype != jnp.float32:
            raise ValueError(
                "scan_dtype requires an fp32 dataset (narrow dtypes already "
                "take the fast MXU path)")
        if index.metric not in _FAST_SCAN_METRICS:
            raise ValueError(
                f"scan_dtype unsupported for metric {index.metric.name}; "
                "eligible: L2Expanded/L2SqrtExpanded/CosineExpanded/"
                "InnerProduct")
    refine_mult = refine_multiplier(refine_ratio, fast_scan)
    nq = queries.shape[0]
    queries = pad_rows(queries, query_bucket(nq))  # serving batch bucket
    use_fused, fused_interp, dreason = pk.fused_dispatch_explained(
        "brute_force", scan_mode)
    ineligible = fused_ineligible_reason(
        index.metric, index.dataset.dtype, k, filter is not None, fast_scan)
    pk.require_compiled_kernel("brute_force", scan_mode, ineligible)
    ex_params = {"k": k, "nq": nq, "bucket": queries.shape[0],
                 "n_db": index.size, "dim": index.dim,
                 "metric": index.metric.name}
    with contextlib.ExitStack() as stack:
        cap = stack.enter_context(obs_explain.capture()) if explain else None
        if use_fused and ineligible is None:
            tm, tn = pk.plan_fused_topk_tiles(
                queries.shape[0], index.size, index.dim, k)
            obs_explain.record_dispatch(
                "brute_force", scan_mode, "pallas", dreason,
                params=ex_params, plan={"tm": tm, "tn": tn,
                                        "interpret": fused_interp})
            v, i = _knn_fused_jit(
                queries, index.dataset, index.norms, k, tm, tn,
                index.metric == DistanceType.L2SqrtExpanded, fused_interp)
        else:
            q_tile, db_tile = _choose_tiles(
                queries.shape[0], index.size, index.dim, k,
                res.workspace_limit_bytes)
            if fast_scan:
                # Budget the refine gather too: [q_tile, k_refine, dim] fp32
                # candidates must fit the workspace like the scan tile does.
                k_refine = max(min(refine_mult * k, db_tile), k)
                per_row = k_refine * index.dim * 4
                q_cap = max(
                    8, res.workspace_limit_bytes // (4 * max(per_row, 1)))
                q_tile = min(q_tile, q_cap - q_cap % 8 or 8)
            group_scan = plan_group_scan(
                index.metric, index.dataset.dtype, res.device, q_tile,
                index.size, db_tile, index.dim, k, fast_scan, refine_mult,
                select_recall)
            record_group_scan(group_scan, q_tile, db_tile, ex_params)
            # fused was dispatchable but this request's shape wasn't
            # eligible -> the matrix clause outranks the dispatch verdict
            reason = ineligible if (use_fused and ineligible) else dreason
            obs_explain.record_dispatch(
                "brute_force", scan_mode, "xla", reason, params=ex_params,
                plan={"q_tile": q_tile, "db_tile": db_tile,
                      "predicted_peak_bytes": planned_peak_bytes(
                          queries.shape[0], index.size, index.dim, k,
                          res.workspace_limit_bytes)})
            v, i = _knn_jit(
                queries, index.dataset, index.norms,
                filter.words if filter is not None
                else jnp.zeros((0,), jnp.uint32),
                index.metric, index.metric_arg,
                k, q_tile, db_tile, res.workspace_limit_bytes,
                filter is not None, fast_scan, refine_mult,
                select_recall=float(select_recall), group_scan=group_scan,
            )
    if explain:
        return v[:nq], i[:nq], cap.last
    return v[:nq], i[:nq]


@tracing.range("brute_force.knn")
def knn(queries, dataset, k: int, metric="euclidean", metric_arg: float = 2.0,
        res: Optional[Resources] = None, scan_dtype=None,
        refine_ratio: float = 4.0,
        select_recall: float = 1.0,
        scan_mode: str = "auto", explain: bool = False):
    """One-shot exact kNN (reference: brute_force::knn)."""
    return search(build(dataset, metric, metric_arg, res), queries, k,
                  res=res, scan_dtype=scan_dtype, refine_ratio=refine_ratio,
                  select_recall=select_recall, scan_mode=scan_mode,
                  explain=explain)


_SERIAL_VERSION = 1


def serialize(index: Index, file) -> None:
    """Write index (reference: brute_force_serialize.cuh). Paths are
    written atomically (tmp + os.replace) with per-record crc framing."""
    with ser.writer_for(file) as stream:
        w = ser.IndexWriter(stream, "brute_force", _SERIAL_VERSION)
        w.scalar(int(index.metric), "<i4").scalar(index.metric_arg, "<f8")
        w.array(index.dataset)
        w.scalar(1 if index.norms is not None else 0, "<i4")
        if index.norms is not None:
            w.array(index.norms)
        w.finish()


def deserialize(file, res: Optional[Resources] = None) -> Index:
    ensure_resources(res)
    with ser.reader_for(file) as stream:
        r = ser.IndexReader(stream, "brute_force", _SERIAL_VERSION)
        metric = DistanceType(r.scalar())
        metric_arg = r.scalar()
        dataset = jnp.asarray(r.array())
        norms = jnp.asarray(r.array()) if r.scalar() else None
        r.finish()
        return Index(dataset, metric, metric_arg, norms)


def make_batch_k_query(index: Index, queries, batch_size: int,
                       res: Optional[Resources] = None):
    """Iterate over each query's neighbor list in batches of ``batch_size``:
    the first yield holds the nearest ``batch_size`` neighbors, the next the
    following ``batch_size``, … (reference: brute_force::make_batch_k_query,
    detail/knn_brute_force_batch_k_query.cuh).

    The searched k grows geometrically and several batches are sliced from
    each result, so draining n neighbors costs O(log(n/batch_size)) searches
    (and compilations) rather than one per batch."""
    res = ensure_resources(res)
    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")

    def _iter():
        offset = 0
        k = 0
        d = i = None
        while offset < index.size:
            if offset + batch_size > k:  # widen: double, at least 4 batches
                k = min(max(4 * batch_size, 2 * k), index.size)
                d, i = search(index, queries, k, res=res)
            end = min(offset + batch_size, index.size)
            yield d[:, offset:end], i[:, offset:end]
            offset = end

    return _iter()
