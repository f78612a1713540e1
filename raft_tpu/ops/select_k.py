"""Batched top-k selection — THE key primitive for all ANN search.

Reference: ``raft::matrix::select_k`` (matrix/select_k.cuh) with two kernel
families — radix "AIR top-k" (detail/select_radix.cuh:54-67) and warpsort
per-warp priority queues (detail/select_warpsort.cuh:40-75) — picked by
``choose_select_k_algorithm`` (detail/select_k-inl.cuh:48).

TPU-native design: ``jax.lax.top_k`` (an XLA-native O(len·log len / lane)
sort-based selection that TPUs lower well) is the baseline algorithm; a
two-phase tiled variant (per-tile top-k then merge) bounds the working set for
very wide rows, mirroring how warpsort splits into per-warp queues + a final
merge. Min-selection is negation (distances are finite); NaN/Inf payloads are
pushed to the end like the reference's null-padding convention.

``SelectAlgo`` mirrors matrix/select_k_types.hpp:36-78 in spirit: AUTO picks
between the direct and two-phase paths by row width.
"""

from __future__ import annotations

import enum
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core.bitset import filter_mask
from raft_tpu.obs import explain as obs_explain
from raft_tpu.utils.shape import cdiv


class SelectAlgo(enum.Enum):
    AUTO = "auto"
    DIRECT = "direct"  # single lax.top_k over the full row
    TWO_PHASE = "two_phase"  # per-tile top-k, then merge (wide rows)
    APPROX = "approx"  # TPU PartialReduce (lax.approx_min_k), recall<1


_TILE = 16384

# ---------------------------------------------------------------- AUTO table
#
# AUTO picks DIRECT vs TWO_PHASE from a per-platform crossover table (the
# reference's choose_select_k_algorithm, detail/select_k-inl.cuh:48): for
# each k-band, the row width above which the tiled path wins, written here
# by the PR that measured it (``tools/select_k_bench.py`` prints one).
# Platforms without a table take the "default" entry.
_NEVER = 1 << 62
_BUILTIN_TABLES = {
    # k_max → min row width at which TWO_PHASE beats DIRECT
    # XLA:CPU's top_k is already partial: tiling only adds a merge pass
    "cpu": {"inf": _NEVER},
    # v5e, batch 2048, widths 4096-131072, k 10-256: DIRECT won everywhere
    # but k=256 at width >= 131072 (TWO_PHASE's flat ~175 ms against
    # DIRECT's 208 ms). Exact scans over wider rows never rank a whole
    # tile (brute_force's group minima).
    "tpu": {"128": _NEVER, "256": 131072, "inf": 131072},
    "default": {"32": 65536, "256": 65536, "inf": 131072},
}
_auto_table_cache: Optional[dict] = None


def _load_auto_table() -> dict:
    global _auto_table_cache
    if _auto_table_cache is None:
        _auto_table_cache = dict(_BUILTIN_TABLES)
    return _auto_table_cache


def set_auto_table(platform: str, crossovers: Optional[dict]) -> None:
    """Install (or with None, drop) a measured crossover table for a
    platform: ``{"<k_max>"|"inf": min_two_phase_width}``."""
    global _auto_table_cache
    tables = _load_auto_table()
    if crossovers is None:
        tables.pop(platform, None)
    else:
        tables[platform] = dict(crossovers)
    _auto_table_cache = tables


def _platform_key() -> str:
    """Key for the measured tables: the backend name ("tpu", "cpu")."""
    return jax.default_backend()


def _band(table: dict, k: int):
    """Width threshold of the smallest k-band covering ``k`` (None: never)."""
    for k_max, width in sorted(
            ((float(km) if km != "inf" else float("inf"), w)
             for km, w in table.items())):
        if k <= k_max:
            return width
    return None


# ------------------------------------------------------------- k-pad rules
#
# XLA:TPU's top_k lowering has pointwise-pathological (n, k) cells: the r3
# and r4 hardware sweeps (batch 2048) measured (n=4096, k=10) at
# 112-120 ms while k=32 at the SAME width runs in 1.7-2.3 ms and k=10 on
# wider rows in 1-3 ms. top_k(x, k')[..., :k] is exact for any k' >= k
# (the output is descending-sorted, ties broken by lower index, and the
# prefix of a larger selection is the smaller selection), so the fix is a
# trace-time rewrite of the REQUESTED k. Rules are matched by exact k and
# nearby width (x1.25 — pointwise pathologies don't extrapolate, cf. the
# reference picking select algorithms per shape,
# detail/select_k-inl.cuh:48). The (4096, 10) "tpu" row is the cell the
# r3 and r4 sweeps (batch 2048) both measured pathological; the other 19
# come from one later sweep at batch 2048 (2026-08-02), each padded k at
# least 2x faster than the requested one, a sweep its round's review
# found noisy from host-core contention. None was measured on the chip
# setup the benchmark now runs; a PR that measures a cell changes its
# row here.
_BUILTIN_PAD_RULES = {
    "tpu": [
        {"n": 1024, "k": 4, "k_pad": 64},
        {"n": 1024, "k": 8, "k_pad": 64},
        {"n": 1024, "k": 32, "k_pad": 64},
        {"n": 2048, "k": 4, "k_pad": 32},
        {"n": 2048, "k": 10, "k_pad": 32},
        {"n": 2048, "k": 12, "k_pad": 32},
        {"n": 2048, "k": 16, "k_pad": 32},
        {"n": 2048, "k": 24, "k_pad": 32},
        {"n": 2048, "k": 40, "k_pad": 48},
        {"n": 6144, "k": 4, "k_pad": 24},
        {"n": 6144, "k": 8, "k_pad": 24},
        {"n": 6144, "k": 12, "k_pad": 24},
        {"n": 8192, "k": 8, "k_pad": 16},
        {"n": 8192, "k": 10, "k_pad": 16},
        {"n": 16384, "k": 8, "k_pad": 40},
        {"n": 16384, "k": 12, "k_pad": 40},
        {"n": 16384, "k": 32, "k_pad": 40},
        {"n": 32768, "k": 4, "k_pad": 16},
        {"n": 32768, "k": 8, "k_pad": 16},
        {"n": 4096, "k": 10, "k_pad": 32},
    ],
}
_pad_rules_cache: Optional[dict] = None


def _load_pad_rules() -> dict:
    global _pad_rules_cache
    if _pad_rules_cache is None:
        _pad_rules_cache = {k: [dict(r) for r in v]
                            for k, v in _BUILTIN_PAD_RULES.items()}
    return _pad_rules_cache


def set_pad_rules(platform: str, rules: Optional[list]) -> None:
    """Install (or with None, drop) measured k-pad rules for a platform:
    ``[{"n": width, "k": requested_k, "k_pad": padded_k}, ...]``."""
    tables = _load_pad_rules()
    if rules is None:
        tables.pop(platform, None)
    else:
        tables[platform] = [dict(r) for r in rules]


def _pad_k(n: int, k: int) -> int:
    """The k top_k should actually be asked for at row width ``n``: the
    measured pad rule with matching k and width within x1.25 (nearest by
    width ratio), else k unchanged. The top_k pathologies are pointwise
    in (n, k) and don't extrapolate, so the window is deliberately tight
    — just wide enough to cover tile widths adjacent to a measured power
    of two (e.g. a 5000-wide balanced tile under the 4096 rule)."""
    rules = _load_pad_rules().get(_platform_key(), [])
    best = None
    for r in rules:
        if r["k"] != k:
            continue
        ratio = max(n, r["n"]) / max(1, min(n, r["n"]))
        if ratio <= 1.25 and (best is None or ratio < best[0]):
            best = (ratio, r["k_pad"])
    return min(n, best[1]) if best else k


def _resolve_auto(n: int, k: int) -> "SelectAlgo":
    tables = _load_auto_table()
    table = tables.get(_platform_key(), tables["default"])
    if k * 4 > n:
        return SelectAlgo.DIRECT
    band = _band(table, k)
    if band is None or n < band:
        return SelectAlgo.DIRECT
    return SelectAlgo.TWO_PHASE


def _direct(values: jax.Array, k: int, select_min: bool, k_pad: int = 0):
    # k_pad is resolved OUTSIDE the jit boundary (select_k()) so it is
    # part of the compile key — installing/dropping pad rules retraces
    # instead of silently reusing a stale cached decision (the same
    # pre-jit-resolution rule AUTO follows).
    k_eff = min(values.shape[-1], max(k, k_pad))
    v = -values if select_min else values
    top_v, top_i = jax.lax.top_k(v, k_eff)
    if k_eff != k:  # exact: the prefix of a larger selection
        top_v, top_i = top_v[..., :k], top_i[..., :k]
    return (-top_v if select_min else top_v), top_i


def _approx(values: jax.Array, k: int, select_min: bool,
            recall_target: float):
    """TPU-native approximate selection via the PartialReduce custom call
    (``lax.approx_min_k``) — measured 10-40x faster than ``lax.top_k`` at
    the IVF-critical shapes (batch 2048, width 16k-131k, k<=256) on v5e,
    at a per-element recall target. This is the TPU analog of the recall/
    speed dial the reference exposes through search params (its select_k
    itself is exact, but lut_dtype/internal_distance_dtype make the same
    trade upstream of selection, ivf_pq_types.hpp:110-146). Results come
    back sorted like DIRECT's."""
    fn = jax.lax.approx_min_k if select_min else jax.lax.approx_max_k
    return fn(values, k, recall_target=recall_target)


def _two_phase(values: jax.Array, k: int, select_min: bool):
    batch, n = values.shape
    tile = max(_TILE, k)
    n_tiles = cdiv(n, tile)
    pad = n_tiles * tile - n
    v = -values if select_min else values
    if pad:
        v = jnp.pad(v, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    vt = v.reshape(batch, n_tiles, tile)  # graftcheck: R005 — O(input) view
    # Phase 1: top-k within each tile (vmapped over tiles).
    tv, ti = jax.lax.top_k(vt, min(k, tile))
    ti = ti + (jnp.arange(n_tiles, dtype=ti.dtype) * tile)[None, :, None]
    # Phase 2: merge the n_tiles*k survivors.
    tv = tv.reshape(batch, -1)
    ti = ti.reshape(batch, -1)
    mv, mi = jax.lax.top_k(tv, k)
    out_i = jnp.take_along_axis(ti, mi, axis=1)
    return (-mv if select_min else mv), out_i


@functools.partial(jax.jit, static_argnames=(
    "k", "select_min", "algo", "recall", "k_pad"))
def _select_k_jit(values, k, select_min, algo, recall=0.95, k_pad=0):
    assert algo != SelectAlgo.AUTO  # resolved in select_k(), pre-cache
    if algo == SelectAlgo.APPROX:
        return _approx(values, k, select_min, recall)
    if algo == SelectAlgo.DIRECT:
        return _direct(values, k, select_min, k_pad)
    return _two_phase(values, k, select_min)


def select_k(
    values,
    k: int,
    select_min: bool = True,
    indices: Optional[jax.Array] = None,
    algo: SelectAlgo = SelectAlgo.AUTO,
    recall_target: float = 0.95,
    pad_rules: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Select k smallest (or largest) per row of ``values`` [batch, len].

    Returns (selected_values [batch, k], selected_indices [batch, k]).
    When ``indices`` is given, returned indices are gathered from it —
    the source-index relabeling the reference supports via its in_idx arg.

    ``algo=APPROX`` opts into the TPU PartialReduce engine at the given
    per-element ``recall_target`` — AUTO never picks it (the public
    primitive stays exact, matching matrix::select_k); ANN searches opt
    in through their search params where the recall trade is theirs to
    make.

    ``pad_rules=False`` skips the k-pad rules (``_pad_k``). The measured
    rules model an HBM-resident select over a raw scan slab; callers whose
    selection already happened inside a fused Pallas kernel (the input is
    a short merged candidate list, not a slab) must not be re-padded on
    top of the in-kernel carry width.
    """
    values = jnp.asarray(values)
    algo = SelectAlgo(algo)  # a name outside the enum raises ValueError
    if values.ndim == 1:
        v, i = select_k(values[None], k, select_min, None, algo,
                        recall_target, pad_rules)
        v, i = v[0], i[0]
        if indices is not None:
            i = jnp.asarray(indices)[i]
        return v, i
    if k > values.shape[-1]:
        raise ValueError(f"k={k} > row length {values.shape[-1]}")
    if algo == SelectAlgo.AUTO:
        # Resolve BEFORE the jit boundary: the concrete algo is the compile
        # key, so later set_auto_table() changes apply to fresh calls
        # instead of being baked into a cached AUTO trace.
        algo = _resolve_auto(values.shape[-1], int(k))
    # pad rules resolve pre-jit too: the padded k is part of the compile
    # key, so set_pad_rules() changes retrace fresh calls
    k_pad = _pad_k(values.shape[-1], int(k)) if (
        pad_rules and algo == SelectAlgo.DIRECT) else 0
    # capture-only explain note: this body runs at TRACE time inside the
    # jitted search cores (once per compiled shape, not per call), so it
    # attaches the resolved algo/pad to the active explain capture but
    # never touches the per-call dispatch counter (obs/explain.py)
    obs_explain.note_select_k(values.shape[-1], int(k), algo.name, k_pad)
    out_v, out_i = _select_k_jit(values, int(k), bool(select_min), algo,
                                 float(recall_target), k_pad)
    if indices is not None:
        out_i = jnp.take_along_axis(jnp.asarray(indices), out_i, axis=1)
    return out_v, out_i


def select_k_filtered(
    values,
    k: int,
    ids,
    filter_words,
    select_min: bool = True,
    algo: SelectAlgo = SelectAlgo.AUTO,
    recall_target: float = 0.95,
    pad_rules: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``select_k`` with a standing bitset filter folded into selection.

    ``values`` [batch, len] are candidate distances labeled by ``ids``
    [batch, len] (or [len], broadcast across the batch; -1 marks padding
    per the null convention). ``filter_words`` is a ``core.bitset`` word
    array where a SET bit means the id is eligible — candidates whose bit
    is clear are pushed to the sentinel before the top-k, so a filtered
    id can never surface (ROADMAP item 4's sample-filter semantics,
    sample_filter_types.hpp:27-82, applied post-scan).

    Returns ``(selected_values, selected_ids, n_filtered)`` where
    ``n_filtered`` is a scalar i32: the count of otherwise-live
    candidates (valid id, finite distance) removed specifically by the
    bitset — the observable behind the ``filtered_rows`` metric.
    """
    values = jnp.asarray(values)
    ids = jnp.asarray(ids)
    if ids.ndim == values.ndim - 1:
        ids = jnp.broadcast_to(ids[None, :], values.shape)
    valid = ids >= 0
    if jnp.issubdtype(values.dtype, jnp.floating):
        valid = valid & jnp.isfinite(values)
    allowed = filter_mask(ids, jnp.asarray(filter_words))
    n_filtered = jnp.sum(valid & ~allowed, dtype=jnp.int32)
    keep = valid & allowed
    sentinel = jnp.inf if select_min else -jnp.inf
    masked_v = jnp.where(keep, values, jnp.asarray(sentinel, values.dtype))
    masked_i = jnp.where(keep, ids, -1)
    v, i = select_k(masked_v, k, select_min, indices=masked_i, algo=algo,
                    recall_target=recall_target, pad_rules=pad_rules)
    return v, i, n_filtered


def select_k_plan(n: int, k: int, pad_rules: bool = True) -> dict:
    """The resolution ``select_k`` would make for a [*, n] row at this k,
    WITHOUT running it: ``{"algo", "k_pad"}`` from the measured
    AUTO table and k-pad rules. The dry-run surface ``tools/explain.py``
    prints so an operator can see the selection plan of a query shape
    before paying a compile."""
    algo = _resolve_auto(int(n), int(k))
    k_pad = _pad_k(int(n), int(k)) if (
        pad_rules and algo == SelectAlgo.DIRECT) else 0
    return {"algo": algo.name, "k_pad": int(k_pad)}


def select_k_maybe_approx(values, k: int, select_min: bool,
                          select_recall: float):
    """Traceable select used inside search bodies: exact AUTO at
    ``select_recall >= 1.0``, the APPROX (PartialReduce) engine at the
    given per-element recall target below it. One definition so every
    search (single-chip and sharded) makes the same dispatch."""
    if select_recall < 1.0:
        return select_k(values, k, select_min=select_min,
                        algo=SelectAlgo.APPROX,
                        recall_target=select_recall)
    return select_k(values, k, select_min=select_min)


def refine_multiplier(refine_ratio, fast_scan: bool) -> int:
    """Round a ``refine_ratio`` search param to the static screen multiple
    shared by every fast-scan path (brute_force, ivf_flat, sharded) — 1
    when the fast scan is off, so it never varies the jit cache key."""
    return max(1, int(round(float(refine_ratio)))) if fast_scan else 1


def merge_topk_dedup(ids, dists, k: int, exclude_ids=None):
    """Top-``k`` smallest ``dists`` per row with duplicate-id suppression
    (traceable; the shared merge step of graph algorithms — nn-descent's
    heap-insert analog and CAGRA's itopk merge).

    ``ids`` [b, m] int32 candidate ids (-1 = invalid), ``dists`` [b, m];
    ``exclude_ids`` [b] optionally bans one id per row (self-suppression).
    Returns (ids [b, k], dists [b, k]) sorted ascending by distance; losers
    padded with (-1, +inf). Ties between duplicate copies keep the first in
    id-sorted order.
    """
    b, m = ids.shape
    if exclude_ids is not None:
        ids = jnp.where(ids == exclude_ids[:, None], -1, ids)
    ds = jnp.where(ids < 0, jnp.inf, dists)
    order = jnp.argsort(ids, axis=1)
    ids_s = jnp.take_along_axis(ids, order, axis=1)
    ds_s = jnp.take_along_axis(ds, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((b, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1)
    ds_s = jnp.where(dup, jnp.inf, ds_s)
    top, sel = jax.lax.top_k(-ds_s, k)
    out_ids = jnp.take_along_axis(ids_s, sel, axis=1)
    return jnp.where(jnp.isfinite(-top), out_ids, -1), -top


def merge_topk_dedup_flagged(ids, dists, flags, k: int):
    """``merge_topk_dedup`` carrying a per-entry boolean flag: duplicate ids
    collapse to one entry whose flag is the OR of the copies' flags (CAGRA's
    itopk merge, where the flag means "already expanded as a parent" —
    the buffer-resident analog of the reference's visited hashmap).

    Returns (ids [b, k], dists [b, k], flags [b, k]) ascending by distance.
    """
    b, m = ids.shape
    ds = jnp.where(ids < 0, jnp.inf, dists)
    # sort by (id, flag-first) so each dup group is adjacent with a flagged
    # copy leading when present; ids < 2^30 assumed (int32 key headroom)
    key = ids * 2 + jnp.where(flags, 0, 1)
    order = jnp.argsort(jnp.where(ids < 0, jnp.iinfo(jnp.int32).max, key),
                        axis=1)
    ids_s = jnp.take_along_axis(ids, order, axis=1)
    ds_s = jnp.take_along_axis(ds, order, axis=1)
    fl_s = jnp.take_along_axis(flags, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((b, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1)
    # the group leader absorbs any copy's flag (same node, same distance)
    grp_flag = fl_s  # leader is flagged-first by the sort key
    ds_s = jnp.where(dup, jnp.inf, ds_s)
    top, sel = jax.lax.top_k(-ds_s, k)
    out_ids = jnp.take_along_axis(ids_s, sel, axis=1)
    out_fl = jnp.take_along_axis(grp_flag, sel, axis=1)
    valid = jnp.isfinite(-top)
    return (jnp.where(valid, out_ids, -1), -top, out_fl & valid)
