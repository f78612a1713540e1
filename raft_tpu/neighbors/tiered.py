"""Tiered IVF-PQ serving: host-resident lists behind a device LRU arena.

Everything else in the repo assumes the index fits HBM. This module is
the serve-time tier that breaks that assumption (ROADMAP item 6): the
PQ code/id lists live in host RAM (:class:`HostTier`, plain numpy —
loadable straight from the streamed-build files via
``native.iter_bin_batches_prefetch``), while the coarse quantizer,
rotation, codebooks and the tiny overflow block stay HBM-resident.
Probed lists resolve through a fixed-size device slab arena
(:class:`SlabArena`) managed as an LRU keyed by ``(namespace, coarse
cluster id)`` — the SPANN memory/disk split (hot coarse structures,
paged posting lists) recast onto the host/HBM boundary.

Bit-identity with the all-HBM cache engine is a hard invariant, pinned
by test: :func:`tiered_scan_core` mirrors
``ivf_pq._search_cache_core``'s per-tile body op for op (same q_tile
padding, same ``[t, P, pad, rot]`` gather shapes, same einsum/select
calls), with only the gather *source* swapped from ``list_decoded`` to
the arena slabs — a pure copy, so every f32 reduction sees identical
shapes and operand values. The arena's decoded slabs come from the
same ``_decode_lists_jit`` decode the resident cache uses, and the
host-precomputed slab norms are produced by chunking that decode at
exactly the ``list_tile`` ``ensure_scan_cache`` would pick, so chunk
boundaries coincide with the reference's internal tiles.

Concurrency model: arena device state is updated *functionally*
(``.at[slots].set`` returns new arrays), so an in-flight scan holds an
immutable snapshot and an eviction can never tear it. The only mutable
state is the LRU map + counters, all under one lock; nothing blocks
under that lock (host reads are numpy slices; fetch dispatch is async;
``block_until_ready`` stall accounting happens after release).

A :class:`TierPrefetcher` thread peeks the serving batcher's
already-formed next batch (``Batcher.peek()``, non-consuming) and
resolves its probes through the prefetch path, so the host→device copy
overlaps the previous batch's device time. Because the arena is keyed
by namespace, one arena multiplexes N indexes per chip: cold tenants
cost only host RAM, and a fleet ``rolling_swap`` onto a tiered searcher
is a cache-promotion event — the new generation's lists warm on first
probe while the old generation's slabs age out of the same LRU.
"""

from __future__ import annotations

import functools
import itertools
import json
import logging
import os
import threading
import time
import zlib
from collections import OrderedDict
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import native
from raft_tpu.core.resources import (Resources, ensure_resources,
                                     solve_host_tier)
from raft_tpu.neighbors import ivf_pq
from raft_tpu.neighbors.ivf_pq import CodebookGen, SearchParams
from raft_tpu.obs import explain as obs_explain
from raft_tpu.obs import metrics as obs_metrics
from raft_tpu.obs import spans as obs_spans
from raft_tpu.ops.distance import DistanceType
from raft_tpu.ops.select_k import select_k_maybe_approx
from raft_tpu.utils.shape import (as_query_array, balanced_tile, cdiv,
                                  pad_rows, query_bucket)

__all__ = [
    "HostTier",
    "SlabArena",
    "TierPrefetcher",
    "TierReadError",
    "TierStats",
    "TieredArenaError",
    "TieredIvfPq",
    "attach_prefetcher",
    "coarse_probes_core",
    "host_tier_from_index",
    "load_manifest",
    "load_tiered",
    "save_tiered",
    "tiered_scan_core",
    "validate_manifest",
    "MANIFEST_PREFIX",
    "MANIFEST_SCHEMA",
]

logger = logging.getLogger("raft_tpu.neighbors.tiered")

MANIFEST_PREFIX = "TIERED_MANIFEST_"
MANIFEST_SCHEMA = "raft_tpu.tiered_manifest/v1"

_arena_seq = itertools.count()


class TierReadError(RuntimeError):
    """A host-tier list read failed. Always raised *before* the arena map
    mutates, and always chained (``__cause__``) to the underlying error —
    the serving engine's containment turns it into a typed
    ``BatchFailed``, never a hang."""


class TieredArenaError(RuntimeError):
    """One batch probes more distinct lists than the arena has slots —
    a sizing error (``solve_host_tier`` reports the per-batch worst
    case), not a runtime condition to retry."""


# ------------------------------------------------------------- host tier


class HostTier:
    """Host-RAM residence for one index's packed lists.

    ``norms`` are the decoded-residual squared norms the resident cache
    engine would hold in ``decoded_norms`` — precomputed once here (see
    :func:`host_tier_from_index`) so a fetch uploads them instead of
    re-reducing on device, keeping the scan's ``g_n`` operand bit-equal
    to the reference's.
    """

    def __init__(self, codes: np.ndarray, ids: np.ndarray,
                 sizes: np.ndarray, norms: np.ndarray) -> None:
        if codes.ndim != 3 or ids.shape != codes.shape[:2]:
            raise ValueError(f"codes {codes.shape} / ids {ids.shape} "
                             f"disagree")
        if norms.shape != ids.shape or sizes.shape != (codes.shape[0],):
            raise ValueError(f"norms {norms.shape} / sizes {sizes.shape} "
                             f"disagree with lists {ids.shape}")
        self.codes = np.ascontiguousarray(codes, np.uint8)
        self.ids = np.ascontiguousarray(ids, np.int32)
        self.sizes = np.ascontiguousarray(sizes, np.int32)
        self.norms = np.ascontiguousarray(norms, np.float32)

    @property
    def n_lists(self) -> int:
        return self.codes.shape[0]

    @property
    def list_pad(self) -> int:
        return self.codes.shape[1]

    @property
    def n_code_bytes(self) -> int:
        return self.codes.shape[2]

    @property
    def nbytes(self) -> int:
        return (self.codes.nbytes + self.ids.nbytes + self.sizes.nbytes
                + self.norms.nbytes)

    def read_lists(self, clusters: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray]:
        """Gather the named lists' host rows. Any failure surfaces as a
        chained :class:`TierReadError` (the typed degraded path)."""
        try:
            cl = np.asarray(clusters, np.int64)
            if cl.size and (cl.min() < 0 or cl.max() >= self.n_lists):
                raise IndexError(f"cluster ids {cl.min()}..{cl.max()} "
                                 f"outside [0, {self.n_lists})")
            return (self.codes[cl], self.ids[cl], self.sizes[cl],
                    self.norms[cl])
        except Exception as e:
            raise TierReadError(
                f"host tier read failed for {np.size(clusters)} "
                f"list(s)") from e


def _host_norms(index: "ivf_pq.Index", cache_dtype=jnp.bfloat16
                ) -> np.ndarray:
    """Decoded-residual norms for every list, chunked at exactly the
    ``list_tile`` ``ensure_scan_cache`` uses so each chunk reproduces one
    of the reference decode's internal tiles (last chunk zero-pads the
    same way) — the norms are bit-equal to ``index.decoded_norms``."""
    per_cluster = index.params.codebook_kind == CodebookGen.PER_CLUSTER
    n_lists = index.n_lists
    list_pad = index.list_codes.shape[1]
    list_tile = balanced_tile(n_lists, min(n_lists, 128), 8)
    out = np.empty((n_lists, list_pad), np.float32)
    for a in range(0, n_lists, list_tile):
        b = min(a + list_tile, n_lists)
        cb = index.codebooks[a:b] if per_cluster else index.codebooks
        _, nrm = ivf_pq._decode_lists_jit(
            cb, index.list_codes[a:b], index.pq_dim, index.pq_bits,
            per_cluster, list_tile, jnp.dtype(cache_dtype).name)
        out[a:b] = np.asarray(nrm)[:b - a]
    return out


def host_tier_from_index(index: "ivf_pq.Index",
                         cache_dtype=jnp.bfloat16) -> HostTier:
    """Demote an in-memory index's lists to a :class:`HostTier`."""
    if index.list_codes is None:
        raise ValueError("index has no packed lists to demote")
    return HostTier(np.asarray(index.list_codes),
                    np.asarray(index.list_indices),
                    np.asarray(index.list_sizes),
                    _host_norms(index, cache_dtype))


# ------------------------------------------------------------ telemetry

#: prefetch accounting vocabulary (``raft_tpu_tier_prefetch_total``'s
#: ``event`` label) — fetch: lists pulled by the prefetch path;
#: already_resident: peeked lists that were already in the arena;
#: useful: a demand hit landed on a slab the prefetcher staged;
#: error: a prefetch pass failed (never takes serving down)
_PREFETCH_EVENTS = ("fetch", "already_resident", "useful", "error")

_STALL_PATHS = ("demand", "prefetch")


class TierStats:
    """Registry-backed tier telemetry for one arena (the
    ``ServingStats`` idiom: labeled children pre-touched so a scrape
    shows the full vocabulary at 0)."""

    def __init__(self, registry: Optional[obs_metrics.Registry] = None,
                 arena_label: str = "arena") -> None:
        r = registry if registry is not None else obs_metrics.REGISTRY
        self.registry = r
        self.arena_label = arena_label
        a = arena_label
        self._hits = r.counter(
            "raft_tpu_tier_cache_hits_total",
            "Demand-path probed lists found resident in the arena.",
            ("arena",)).labels(a)
        self._misses = r.counter(
            "raft_tpu_tier_cache_misses_total",
            "Demand-path probed lists fetched from the host tier.",
            ("arena",)).labels(a)
        self._evictions = r.counter(
            "raft_tpu_tier_cache_evictions_total",
            "LRU slab evictions (any path).", ("arena",)).labels(a)
        pf = r.counter(
            "raft_tpu_tier_prefetch_total",
            "Prefetcher accounting by event.", ("arena", "event"))
        self._pf = {ev: pf.labels(a, ev) for ev in _PREFETCH_EVENTS}
        stall = r.histogram(
            "raft_tpu_tier_fetch_stall_seconds",
            "Wall time a resolve blocked on host->device slab fetches.",
            ("arena", "path"),
            buckets=obs_metrics.exponential_buckets(1e-5, 2.0, 20))
        self._stall = {p: stall.labels(a, p) for p in _STALL_PATHS}
        self._occ = r.gauge(
            "raft_tpu_tier_arena_occupancy",
            "Occupied arena slot fraction.", ("arena",)).labels(a)
        self._occ.set(0.0)

    def record_resolve(self, path: str, hits: int, misses: int,
                       evictions: int, useful: int,
                       occupancy_frac: float) -> None:
        if path == "demand":
            if hits:
                self._hits.inc(hits)
            if misses:
                self._misses.inc(misses)
            if useful:
                self._pf["useful"].inc(useful)
        else:
            if hits:
                self._pf["already_resident"].inc(hits)
            if misses:
                self._pf["fetch"].inc(misses)
        if evictions:
            self._evictions.inc(evictions)
        self._occ.set(occupancy_frac)

    def record_stall(self, path: str, seconds: float) -> None:
        self._stall[path].observe(seconds)

    def prefetch_event(self, event: str, n: int = 1) -> None:
        self._pf[event].inc(n)


# ------------------------------------------------------------ slab arena


class _ArenaSnapshot(NamedTuple):
    """Immutable view of the arena's device state at resolve time —
    in-flight scans keep scanning it unperturbed by later fetches."""

    dec: jax.Array    # [slots, list_pad, rot_dim] cache dtype
    norms: jax.Array  # [slots, list_pad] f32
    ids: jax.Array    # [slots, list_pad] i32 (-1 padding)
    sizes: jax.Array  # [slots] i32


@functools.partial(jax.jit, static_argnames=("pq_dim", "pq_bits",
                                             "per_cluster", "cache_dtype"))
def _fetch_insert_jit(arena_dec, arena_norms, arena_ids, arena_sizes,
                      codebooks, clusters, codes, norms, ids, sizes, slots,
                      pq_dim: int, pq_bits: int, per_cluster: bool,
                      cache_dtype: str):
    """Decode one fixed-shape group of host lists and scatter them into
    the arena (functional: returns the replacement arrays). The decode
    is ``ivf_pq._decode_lists_jit`` itself (inlined by the nested jit)
    at ``list_tile == group size``, so slab values are the exact bytes
    ``ensure_scan_cache`` would have produced; the norms ride along
    host-precomputed (see :func:`_host_norms`) and the decode's own
    norm output is dead code."""
    cb = codebooks[clusters] if per_cluster else codebooks
    dec, _ = ivf_pq._decode_lists_jit(cb, codes, pq_dim, pq_bits,
                                      per_cluster, codes.shape[0],
                                      cache_dtype)
    return (arena_dec.at[slots].set(dec),
            arena_norms.at[slots].set(norms),
            arena_ids.at[slots].set(ids),
            arena_sizes.at[slots].set(sizes))


class SlabArena:
    """Fixed-size device-resident LRU of decoded list slabs.

    Keyed by ``(namespace, cluster)`` so one arena multiplexes every
    tiered index on the chip: a tenant with no traffic holds zero slots
    (host RAM only); a hot tenant's probed lists stay resident. All
    mutable bookkeeping lives under one lock; device arrays are only
    *replaced* (functional scatter), never mutated, so readers hold
    consistent snapshots without taking the lock during the scan.
    """

    def __init__(self, slots: int, list_pad: int, rot_dim: int,
                 cache_dtype=jnp.bfloat16, fetch_tile: int = 8,
                 registry: Optional[obs_metrics.Registry] = None,
                 label: Optional[str] = None, span_sink=None,
                 clock=time.perf_counter) -> None:
        if slots < 1:
            raise ValueError(f"arena needs >= 1 slot, got {slots}")
        self.slots = int(slots)
        self.list_pad = int(list_pad)
        self.rot_dim = int(rot_dim)
        self.cache_dtype = jnp.dtype(cache_dtype)
        self.fetch_tile = max(1, min(int(fetch_tile), self.slots))
        self.label = label or f"arena{next(_arena_seq)}"
        self.span_sink = span_sink
        self.clock = clock
        self.stats = TierStats(registry, self.label)
        d3, d2 = (slots, list_pad, rot_dim), (slots, list_pad)
        self._dec = jnp.zeros(d3, self.cache_dtype)    # guarded_by: _lock
        self._norms = jnp.zeros(d2, jnp.float32)       # guarded_by: _lock
        self._ids = jnp.full(d2, -1, jnp.int32)        # guarded_by: _lock
        self._sizes = jnp.zeros((slots,), jnp.int32)   # guarded_by: _lock
        self._lock = threading.Lock()
        # (namespace, cluster) -> slot, in LRU order (front = coldest)
        self._map = OrderedDict()                      # guarded_by: _lock
        self._prefetched = [False] * slots             # guarded_by: _lock
        self._free = list(range(slots - 1, -1, -1))    # guarded_by: _lock
        self.counts = {                                # guarded_by: _lock
            "hits": 0, "misses": 0, "evictions": 0, "inserts": 0,
            "resolved": 0, "prefetch_fetches": 0, "prefetch_hits": 0,
            "useful_prefetch": 0,
        }

    @property
    def nbytes(self) -> int:
        """Measured device footprint (the number ``solve_host_tier``'s
        ``arena_bytes`` predicts; the C001 smoke pins the ratio)."""
        return int(self._dec.nbytes + self._norms.nbytes + self._ids.nbytes
                   + self._sizes.nbytes)

    def occupancy(self) -> int:
        with self._lock:
            return len(self._map)

    def snapshot_counts(self) -> Dict[str, int]:
        """Consistent counter snapshot plus occupancy — the interleave
        tests reconcile these exactly per seed (hits + misses +
        prefetch_hits + prefetch_fetches == resolved; inserts == misses
        + prefetch_fetches; evictions == inserts - occupancy)."""
        with self._lock:
            out = dict(self.counts)
            out["occupancy"] = len(self._map)
            return out

    def resolve_probes(self, owner: "TieredIvfPq",
                       cluster_probes: np.ndarray,
                       trace_id: Optional[str] = None
                       ) -> Tuple[_ArenaSnapshot, np.ndarray]:
        """Demand path: make every probed cluster resident and return
        ``(snapshot, slot_probes)`` with ``slot_probes`` shaped like
        ``cluster_probes`` — ready to gather the snapshot's slabs."""
        cp = np.asarray(cluster_probes)
        uniq = np.unique(cp)
        snap, resolved = self._resolve(owner, uniq, "demand", trace_id)
        lut = np.zeros(int(uniq.max()) + 1 if uniq.size else 1, np.int32)
        for c, s in resolved.items():
            lut[c] = s
        return snap, lut[cp].astype(np.int32)

    def prefetch(self, owner: "TieredIvfPq", clusters: np.ndarray,
                 trace_id: Optional[str] = None) -> int:
        """Prefetch path: stage ``clusters`` without demand accounting.
        Returns the number of lists actually fetched."""
        uniq = np.unique(np.asarray(clusters))
        _, resolved = self._resolve(owner, uniq, "prefetch", trace_id)
        return len(resolved)

    # the single mutation point — everything else is a view
    def _resolve(self, owner: "TieredIvfPq", uniq: np.ndarray, path: str,
                 trace_id: Optional[str]
                 ) -> Tuple[_ArenaSnapshot, Dict[int, int]]:
        ns = owner.namespace
        t0 = self.clock()
        groups: List[Tuple[List[int], List[int]]] = []
        with self._lock:
            if len(uniq) > self.slots:
                raise TieredArenaError(
                    f"batch probes {len(uniq)} distinct lists but the "
                    f"arena has {self.slots} slots — size the arena with "
                    f"solve_host_tier (worst case max_batch * n_probes)")
            resolved: Dict[int, int] = {}
            missing: List[int] = []
            n_hits = n_useful = 0
            for c in uniq:
                key = (ns, int(c))
                slot = self._map.get(key)
                if slot is None:
                    missing.append(int(c))
                    continue
                self._map.move_to_end(key)
                resolved[int(c)] = slot
                n_hits += 1
                if path == "demand" and self._prefetched[slot]:
                    self._prefetched[slot] = False
                    n_useful += 1
            if missing:
                # host reads before any map mutation: a TierReadError
                # leaves the arena exactly as it was
                codes, ids, sizes, norms = owner.tier.read_lists(
                    np.asarray(missing, np.int64))
                n_evict = 0
                for c in missing:
                    if self._free:
                        slot = self._free.pop()
                    else:
                        _, slot = self._map.popitem(last=False)
                        n_evict += 1
                    self._map[(ns, c)] = slot
                    self._prefetched[slot] = path == "prefetch"
                    resolved[c] = slot
                ft = self.fetch_tile
                for a in range(0, len(missing), ft):
                    pos = list(range(a, min(a + ft, len(missing))))
                    pos += [pos[0]] * (ft - len(pos))  # repeat-pad: the
                    # duplicate scatter carries an identical payload
                    grp = [missing[p] for p in pos]
                    slots_g = [resolved[c] for c in grp]
                    self._dec, self._norms, self._ids, self._sizes = \
                        _fetch_insert_jit(
                            self._dec, self._norms, self._ids, self._sizes,
                            owner.codebooks,
                            jnp.asarray(grp, jnp.int32),
                            jnp.asarray(codes[pos]),
                            jnp.asarray(norms[pos]),
                            jnp.asarray(ids[pos]),
                            jnp.asarray(sizes[pos]),
                            jnp.asarray(slots_g, jnp.int32),
                            owner.pq_dim, owner.pq_bits,
                            owner.per_cluster, self.cache_dtype.name)
                    groups.append((grp, slots_g))
                cnt = self.counts
                cnt["inserts"] += len(missing)
                cnt["evictions"] += n_evict
            else:
                n_evict = 0
            cnt = self.counts
            cnt["resolved"] += len(uniq)
            if path == "demand":
                cnt["hits"] += n_hits
                cnt["misses"] += len(missing)
                cnt["useful_prefetch"] += n_useful
            else:
                cnt["prefetch_hits"] += n_hits
                cnt["prefetch_fetches"] += len(missing)
            snap = _ArenaSnapshot(self._dec, self._norms, self._ids,
                                  self._sizes)
            occ = len(self._map)
        # emission + the stall wait happen OUTSIDE the lock: telemetry
        # never extends the critical section, and the lock graph stays
        # zero-edge (this lock is never held across another acquire)
        self.stats.record_resolve(path, n_hits, len(missing), n_evict,
                                  n_useful, occ / self.slots)
        if groups:
            jax.block_until_ready(snap.dec)
            stall = self.clock() - t0
            self.stats.record_stall(path, stall)
            if self.span_sink is not None:
                obs_spans.safe_emit(self.span_sink, {
                    "kind": "tier_fetch",
                    "trace": trace_id or obs_spans.new_trace_id(),
                    "arena": self.label,
                    "namespace": ns,
                    "path": path,
                    "n_lists": len(missing),
                    "clusters": [c for g, _ in groups for c in g],
                    "slots": [s for _, g in groups for s in g],
                    "stall_s": stall,
                })
        return snap, resolved


# ----------------------------------------------------------- scan cores


def coarse_probes_core(queries, centers, rotation, metric: DistanceType,
                       n_probes: int, q_tile: int,
                       select_recall: float = 1.0):
    """Coarse top-``n_probes`` clusters per query — the exact probe ids
    ``_search_cache_core`` computes internally, lifted out so the host
    can resolve them against the arena. Same q_tile padding, same
    HIGHEST-precision matmuls, same ``select_k_maybe_approx`` call: the
    returned probes are bit-equal to the resident engine's."""
    nq, dim = queries.shape
    n_q_tiles = cdiv(nq, q_tile)
    pad_q = n_q_tiles * q_tile - nq
    qp = jnp.pad(queries.astype(jnp.float32), ((0, pad_q), (0, 0)))
    centers_rot = jax.lax.dot_general(
        centers, rotation, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    def q_body(qt):
        q_rot = jax.lax.dot_general(
            qt, rotation, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        dots_c = jax.lax.dot_general(
            q_rot, centers_rot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        if metric == DistanceType.InnerProduct:
            _, probes = select_k_maybe_approx(dots_c, n_probes, False,
                                              select_recall)
        else:
            cn = jnp.sum(centers_rot * centers_rot, -1)
            _, probes = select_k_maybe_approx(cn[None, :] - 2.0 * dots_c,
                                              n_probes, True, select_recall)
        return probes

    if n_q_tiles == 1:
        probes = q_body(qp)
    else:
        probes = jax.lax.map(q_body, qp.reshape(n_q_tiles, q_tile, dim))
        probes = probes.reshape(-1, n_probes)
    return probes[:nq]


_coarse_probes_jit = jax.jit(
    coarse_probes_core,
    static_argnames=("metric", "n_probes", "q_tile", "select_recall"),
)


def tiered_scan_core(queries, centers, rotation, arena_dec, arena_norms,
                     arena_ids, arena_sizes, cluster_probes, slot_probes,
                     metric: DistanceType, k: int, n_probes: int,
                     q_tile: int, overflow_decoded=None,
                     overflow_norms=None, overflow_indices=None,
                     has_overflow: bool = False,
                     select_recall: float = 1.0):
    """ADC scan over arena-resident slabs — ``_search_cache_core``'s
    non-pallas tile body with the probes injected (``cluster_probes``
    for the ``centers_rot`` terms, ``slot_probes`` for the slab
    gathers). Every arithmetic op, operand shape and reduction matches
    the reference, so restricted to the same probed lists the outputs
    are bit-identical (pinned by tests/test_tiered.py)."""
    nq, dim = queries.shape
    slots, list_pad, rot_dim = arena_dec.shape
    minimize = metric != DistanceType.InnerProduct
    prec = ivf_pq.contraction_precision(arena_dec.dtype)

    def _sel(vals, kk, sel_min):
        return select_k_maybe_approx(vals, kk, sel_min, select_recall)

    n_q_tiles = cdiv(nq, q_tile)
    pad_q = n_q_tiles * q_tile - nq
    qp = jnp.pad(queries.astype(jnp.float32), ((0, pad_q), (0, 0)))
    cp = jnp.pad(cluster_probes, ((0, pad_q), (0, 0)))
    sp = jnp.pad(slot_probes, ((0, pad_q), (0, 0)))

    centers_rot = jax.lax.dot_general(
        centers, rotation, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    valid_slot = jnp.arange(list_pad)[None, :] < arena_sizes[:, None]

    def q_body(args):
        qt, probes, slotp = args
        q_rot = jax.lax.dot_general(
            qt, rotation, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        g_idx = arena_ids[slotp]
        g_valid = valid_slot[slotp]
        if metric == DistanceType.InnerProduct:
            dots_c = jax.lax.dot_general(
                q_rot, centers_rot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            g_dec = arena_dec[slotp]  # [t, P, pad, rot] bf16
            dots = jnp.einsum("td,tpld->tpl", q_rot,
                              g_dec.astype(jnp.float32),
                              preferred_element_type=jnp.float32,
                              precision=prec)
            base = jnp.take_along_axis(dots_c, probes, axis=1)
            d = base[:, :, None] + dots
        else:
            g_dec = arena_dec[slotp]  # [t, P, pad, rot] bf16
            g_n = arena_norms[slotp]  # [t, P, pad]
            qr_res = q_rot[:, None, :] - centers_rot[probes]  # [t, P, rot]
            dots = jnp.einsum("tpd,tpld->tpl", qr_res,
                              g_dec.astype(jnp.float32),
                              preferred_element_type=jnp.float32,
                              precision=prec)
            qn = jnp.sum(qr_res * qr_res, -1)  # [t, P]
            d = qn[:, :, None] - 2.0 * dots + g_n

        bad_fill = jnp.inf if minimize else -jnp.inf
        d = jnp.where(g_valid, d, bad_fill)

        n_cand = n_probes * list_pad
        flat_d = d.reshape(qt.shape[0], n_cand)
        flat_i = g_idx.reshape(qt.shape[0], n_cand)
        if has_overflow:
            od, oi = ivf_pq._pq_overflow_scan(
                q_rot, overflow_decoded, overflow_norms, overflow_indices,
                jnp.zeros((0,), jnp.uint32), metric, False, bad_fill, prec)
            flat_d = jnp.concatenate([flat_d, od], axis=1)
            flat_i = jnp.concatenate([flat_i, oi], axis=1)
            n_cand += od.shape[1]
        kk = min(k, n_cand)
        v, sel = _sel(flat_d, kk, minimize)
        i_out = jnp.take_along_axis(flat_i, sel, axis=1)
        if kk < k:
            v = jnp.pad(v, ((0, 0), (0, k - kk)), constant_values=bad_fill)
            i_out = jnp.pad(i_out, ((0, 0), (0, k - kk)),
                            constant_values=-1)
        if metric == DistanceType.L2SqrtExpanded:
            v = jnp.sqrt(jnp.maximum(v, 0.0))
        return v, i_out

    if n_q_tiles == 1:
        vals, idxs = q_body((qp, cp, sp))
    else:
        vals, idxs = jax.lax.map(
            q_body, (qp.reshape(n_q_tiles, q_tile, dim),
                     cp.reshape(n_q_tiles, q_tile, n_probes),
                     sp.reshape(n_q_tiles, q_tile, n_probes)))
        vals = vals.reshape(-1, k)
        idxs = idxs.reshape(-1, k)
    return vals[:nq], idxs[:nq]


_tiered_scan_jit = jax.jit(
    tiered_scan_core,
    static_argnames=("metric", "k", "n_probes", "q_tile", "has_overflow",
                     "select_recall"),
)


# -------------------------------------------------------- tiered index


class TieredIvfPq:
    """IVF-PQ searcher with HBM-resident coarse structures and
    host-resident lists resolved through a :class:`SlabArena`.

    ``namespace`` keys this index's slabs in the (possibly shared)
    arena; distinct tiered indexes sharing one arena multiplex the same
    device budget, which is the multi-tenant story: promotion is just
    LRU traffic, demotion is just silence.
    """

    def __init__(self, params: "ivf_pq.IndexParams", pq_dim: int,
                 centers, rotation, codebooks, tier: HostTier,
                 arena: SlabArena, n_rows: int,
                 overflow_decoded=None, overflow_norms=None,
                 overflow_indices=None, namespace: Optional[str] = None,
                 res: Optional[Resources] = None) -> None:
        if arena.list_pad != tier.list_pad:
            raise ValueError(f"arena list_pad {arena.list_pad} != tier "
                             f"list_pad {tier.list_pad}")
        if arena.rot_dim != rotation.shape[0]:
            raise ValueError(f"arena rot_dim {arena.rot_dim} != index "
                             f"rot_dim {rotation.shape[0]}")
        self.params = params
        self.pq_dim = int(pq_dim)
        self.centers = centers
        self.rotation = rotation
        self.codebooks = codebooks
        self.tier = tier
        self.arena = arena
        self.n_rows = int(n_rows)
        self.overflow_decoded = overflow_decoded
        self.overflow_norms = overflow_norms
        self.overflow_indices = overflow_indices
        self.namespace = namespace or f"tiered{id(self):x}"
        self.res = res

    # -- geometry -----------------------------------------------------
    @property
    def metric(self) -> DistanceType:
        return self.params.metric

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def n_lists(self) -> int:
        return self.tier.n_lists

    @property
    def list_pad(self) -> int:
        return self.tier.list_pad

    @property
    def pq_bits(self) -> int:
        return self.params.pq_bits

    @property
    def per_cluster(self) -> bool:
        return self.params.codebook_kind == CodebookGen.PER_CLUSTER

    @property
    def has_overflow(self) -> bool:
        return (self.overflow_indices is not None
                and self.overflow_indices.shape[0] > 0)

    # -- construction -------------------------------------------------
    @classmethod
    def from_index(cls, index: "ivf_pq.Index",
                   res: Optional[Resources] = None,
                   arena: Optional[SlabArena] = None,
                   arena_slots: Optional[int] = None,
                   namespace: Optional[str] = None,
                   cache_dtype=jnp.bfloat16,
                   registry: Optional[obs_metrics.Registry] = None,
                   span_sink=None) -> "TieredIvfPq":
        """Demote an in-memory index: lists → host tier, coarse
        structures stay device-resident, arena sized by
        :func:`solve_host_tier` unless given."""
        res = ensure_resources(res)
        tier = host_tier_from_index(index, cache_dtype)
        od = on = oi = None
        if index.overflow_codes.shape[0] > 0:
            ivf_pq.ensure_overflow_decoded(index, cache_dtype)
            od, on = index.overflow_decoded, index.overflow_norms
            oi = index.overflow_indices
        if arena is None:
            plan = solve_host_tier(
                tier.n_lists, tier.list_pad, index.rot_dim,
                tier.n_code_bytes, res.workspace_limit_bytes,
                cache_itemsize=jnp.dtype(cache_dtype).itemsize)
            slots = arena_slots if arena_slots is not None \
                else plan["arena_slots"]
            arena = SlabArena(slots, tier.list_pad, index.rot_dim,
                              cache_dtype=cache_dtype, registry=registry,
                              span_sink=span_sink)
        return cls(index.params, index.pq_dim, index.centers,
                   index.rotation, index.codebooks, tier, arena,
                   index.n_rows, od, on, oi, namespace=namespace, res=res)

    @classmethod
    def from_file(cls, path: str, params=None,
                  res: Optional[Resources] = None,
                  batch_rows: int = 1 << 18, dtype=None,
                  max_train_rows: Optional[int] = None,
                  **kwargs) -> "TieredIvfPq":
        """Streamed build straight into the tier: ``ooc``'s
        ``iter_bin_batches_prefetch``-backed file build produces the
        index, whose lists are immediately demoted to host RAM."""
        from raft_tpu.neighbors import ooc
        res = ensure_resources(res)
        index = ooc.build_ivf_pq_from_file(
            path, params=params, res=res, batch_rows=batch_rows,
            dtype=dtype, max_train_rows=max_train_rows)
        return cls.from_index(index, res=res, **kwargs)

    # -- search -------------------------------------------------------
    def search(self, queries, k: int,
               params: Optional[SearchParams] = None,
               res: Optional[Resources] = None):
        """Top-``k`` search, bit-identical to ``ivf_pq.search`` with
        ``scan_mode="cache"`` over the same probed lists. Steady-state
        hits re-dispatch three cached executables (coarse, fetchless
        resolve, scan) — zero compiles after warmup."""
        params = params or SearchParams()
        if params.scan_mode not in ("auto", "cache"):
            raise ValueError(
                f"tiered serving has only the cache engine; scan_mode="
                f"{params.scan_mode!r} is not tierable")
        res = ensure_resources(res if res is not None else self.res)
        queries = as_query_array(queries)
        nq = queries.shape[0]
        if queries.shape[1] != self.dim:
            raise ValueError(f"queries dim {queries.shape[1]} != index "
                             f"dim {self.dim}")
        queries = pad_rows(queries, query_bucket(nq))
        n_probes = min(params.n_probes, self.n_lists)
        q_tile = ivf_pq.plan_cache_tiles(n_probes, self.list_pad,
                                         self.rot_dim,
                                         res.workspace_limit_bytes)
        probes_dev = _coarse_probes_jit(
            queries, self.centers, self.rotation, self.metric, n_probes,
            q_tile, float(params.select_recall))
        cluster_probes = np.asarray(probes_dev)
        snap, slot_probes = self.arena.resolve_probes(
            self, cluster_probes, trace_id=obs_spans.current_trace())
        obs_explain.record_dispatch(
            "tiered_ivf_pq", params.scan_mode, "cache", "only_engine",
            params={"n_probes": n_probes, "k": int(k)},
            plan={"q_tile": q_tile, "arena_slots": self.arena.slots,
                  "namespace": self.namespace})
        v, i = _tiered_scan_jit(
            queries, self.centers, self.rotation,
            snap.dec, snap.norms, snap.ids, snap.sizes,
            probes_dev, jnp.asarray(slot_probes),
            self.metric, int(k), n_probes, q_tile,
            self.overflow_decoded, self.overflow_norms,
            self.overflow_indices, self.has_overflow,
            float(params.select_recall))
        return v[:nq], i[:nq]

    def prefetch_queries(self, queries, params: Optional[SearchParams] = None,
                         depth: Optional[int] = None,
                         trace_id: Optional[str] = None) -> int:
        """Stage the lists a future ``search(queries)`` would probe.
        Shares the demand path's compiled coarse program (same bucket
        shapes → no extra compiles). ``depth`` caps the number of lists
        staged; a cap is LOGGED, never silent."""
        params = params or SearchParams()
        res = ensure_resources(self.res)
        queries = as_query_array(queries)
        queries = pad_rows(queries, query_bucket(queries.shape[0]))
        n_probes = min(params.n_probes, self.n_lists)
        q_tile = ivf_pq.plan_cache_tiles(n_probes, self.list_pad,
                                         self.rot_dim,
                                         res.workspace_limit_bytes)
        probes = np.asarray(_coarse_probes_jit(
            queries, self.centers, self.rotation, self.metric, n_probes,
            q_tile, float(params.select_recall)))
        uniq = np.unique(probes)
        if depth is not None and len(uniq) > depth:
            logger.warning(
                "tier prefetch capped at depth=%d (batch probes %d "
                "distinct lists) — coverage is partial, raise depth to "
                "stage the full peeked batch", depth, len(uniq))
            uniq = uniq[:depth]
        return self.arena.prefetch(self, uniq, trace_id=trace_id)


# ------------------------------------------------------------ prefetcher


class TierPrefetcher:
    """Batcher-driven prefetch thread: peeks the engine batcher's
    already-formed next batch (non-consuming ``Batcher.peek()``) and
    stages its probed lists, so the host→device slab copies overlap the
    previous batch's device time instead of stalling dispatch.

    Thread discipline (graftcheck T-series): the loop's only wait is the
    budgeted ``Event.wait(poll_s)``; all cross-thread state it touches
    is owned elsewhere under those owners' locks (batcher, arena), and
    its own fields are single-writer (this thread) — progress counters
    are read racily by tests/benches, which is fine for monotonic ints.
    """

    def __init__(self, engine, tiered: TieredIvfPq,
                 params: Optional[SearchParams] = None,
                 depth: Optional[int] = None,
                 poll_s: float = 0.0005) -> None:
        self.engine = engine
        self.tiered = tiered
        self.params = params or SearchParams()
        self.depth = depth
        self.poll_s = float(poll_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._seen = None      # single-writer: the prefetch thread
        self.n_passes = 0      # single-writer: the prefetch thread
        self.n_capped = 0      # single-writer: the prefetch thread
        self.n_errors = 0      # single-writer: the prefetch thread

    def start(self) -> "TierPrefetcher":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(  # guarded_by: atomic
            target=self._loop, name=f"tier-prefetch-{self.tiered.namespace}",
            daemon=True)
        self._thread.start()
        return self

    def close(self, timeout: float = 2.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None  # guarded_by: atomic

    def __enter__(self) -> "TierPrefetcher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_s):  # budgeted wait
            batch = self.engine.batcher.peek()
            if not batch:
                continue
            head = batch[0]
            key = (id(head), head.trace_id, len(batch))
            if key == self._seen:
                continue
            self._seen = key
            try:
                t = self.tiered
                bucket = query_bucket(len(batch))
                qs = np.zeros((bucket, t.dim), np.float32)
                for j, r in enumerate(batch):
                    qs[j] = np.asarray(r.query, np.float32).reshape(-1)
                t.prefetch_queries(qs, params=self.params,
                                   depth=self.depth,
                                   trace_id=head.trace_id)
                self.n_passes += 1
            except Exception as e:  # prefetch never takes serving down
                self.n_errors += 1
                self.tiered.arena.stats.prefetch_event("error")
                logger.warning("tier prefetch pass failed: %s: %s",
                               type(e).__name__, e)


def attach_prefetcher(engine, tiered: TieredIvfPq,
                      params: Optional[SearchParams] = None,
                      depth: Optional[int] = None,
                      poll_s: float = 0.0005) -> TierPrefetcher:
    """Start a :class:`TierPrefetcher` against a running engine. The
    caller owns shutdown (``close()`` or use as a context manager)."""
    return TierPrefetcher(engine, tiered, params=params, depth=depth,
                          poll_s=poll_s).start()


# -------------------------------------------------------------- manifest


def _file_crc32(path: str, chunk: int = 1 << 20) -> int:
    crc = 0
    with open(path, "rb") as fh:
        while True:
            b = fh.read(chunk)
            if not b:
                return crc & 0xFFFFFFFF
            crc = zlib.crc32(b, crc)


def save_tiered(tiered: TieredIvfPq, dir_path: str,
                name: str = "default") -> str:
    """Persist a tiered index: host lists as ``.bin`` files (the
    streamed-IO format ``iter_bin_batches_prefetch`` reads), coarse
    structures as one ``.npz``, and a ``TIERED_MANIFEST_*.json`` tying
    them together with per-list spans and crc32s (the artifact
    graftcheck ``--artifacts`` validates under :func:`load_manifest`)."""
    os.makedirs(dir_path, exist_ok=True)
    t = tiered.tier
    L, P, B = t.n_lists, t.list_pad, t.n_code_bytes
    rels = {
        "codes": f"tier_{name}_codes.bin",
        "ids": f"tier_{name}_ids.bin",
        "norms": f"tier_{name}_norms.bin",
        "sizes": f"tier_{name}_sizes.bin",
        "coarse": f"tier_{name}_coarse.npz",
    }
    native.write_bin(os.path.join(dir_path, rels["codes"]),
                     t.codes.reshape(L * P, B))
    native.write_bin(os.path.join(dir_path, rels["ids"]), t.ids)
    native.write_bin(os.path.join(dir_path, rels["norms"]), t.norms)
    native.write_bin(os.path.join(dir_path, rels["sizes"]),
                     t.sizes.reshape(L, 1))
    coarse = {
        "centers": np.asarray(tiered.centers, np.float32),
        "rotation": np.asarray(tiered.rotation, np.float32),
        "codebooks": np.asarray(tiered.codebooks, np.float32),
    }
    if tiered.has_overflow:
        coarse["overflow_decoded"] = np.asarray(tiered.overflow_decoded,
                                                np.float32)
        coarse["overflow_norms"] = np.asarray(tiered.overflow_norms,
                                              np.float32)
        coarse["overflow_indices"] = np.asarray(tiered.overflow_indices,
                                                np.int32)
    np.savez(os.path.join(dir_path, rels["coarse"]), **coarse)
    dtypes = {"codes": "uint8", "ids": "int32", "norms": "float32",
              "sizes": "int32"}
    dims = {"codes": B, "ids": P, "norms": P, "sizes": 1}
    n_rows_of = {"codes": L * P, "ids": L, "norms": L, "sizes": L}
    files = {}
    for key, rel in rels.items():
        full = os.path.join(dir_path, rel)
        entry = {"path": rel, "crc32": _file_crc32(full)}
        if key != "coarse":
            entry.update(rows=n_rows_of[key], dim=dims[key],
                         dtype=dtypes[key])
        files[key] = entry
    sizes = t.sizes
    manifest = {
        "schema": MANIFEST_SCHEMA,
        "namespace": tiered.namespace,
        "n_lists": L, "list_pad": P, "n_code_bytes": B,
        "pq_dim": tiered.pq_dim, "pq_bits": tiered.pq_bits,
        "metric": int(tiered.metric),
        "codebook_kind": int(tiered.params.codebook_kind),
        "n_rows": tiered.n_rows, "dim": tiered.dim,
        "rot_dim": tiered.rot_dim,
        "files": files,
        "lists": [{"list": i, "row_start": i * P, "rows": P,
                   "size": int(sizes[i])} for i in range(L)],
    }
    mpath = os.path.join(dir_path, f"{MANIFEST_PREFIX}{name}.json")
    with open(mpath, "w") as fh:
        json.dump(manifest, fh, indent=1)
    return mpath


def validate_manifest(art: dict, base_dir: Optional[str] = None,
                      check_files: bool = False) -> None:
    """Schema + span validation; with ``check_files`` also header/crc32
    verification of every referenced host file. This is the exact
    front half of :func:`load_tiered` — graftcheck's A001 checker calls
    it so the gate can never drift from the consuming loader."""
    if art.get("schema") != MANIFEST_SCHEMA:
        raise ValueError(f"schema {art.get('schema')!r} != "
                         f"{MANIFEST_SCHEMA!r}")
    for key in ("n_lists", "list_pad", "n_code_bytes", "pq_dim",
                "pq_bits", "n_rows", "dim", "rot_dim"):
        if not isinstance(art.get(key), int) or art[key] < 0:
            raise ValueError(f"manifest key {key!r} must be a "
                             f"non-negative int, got {art.get(key)!r}")
    L, P = art["n_lists"], art["list_pad"]
    files = art.get("files")
    if not isinstance(files, dict):
        raise ValueError("manifest has no 'files' dict")
    for key in ("codes", "ids", "norms", "sizes", "coarse"):
        entry = files.get(key)
        if not isinstance(entry, dict) or "path" not in entry \
                or "crc32" not in entry:
            raise ValueError(f"files[{key!r}] needs 'path' and 'crc32'")
    lists = art.get("lists")
    if not isinstance(lists, list) or len(lists) != L:
        raise ValueError(f"'lists' must enumerate all {L} lists")
    for row in lists:
        if not all(k in row for k in ("list", "row_start", "rows", "size")):
            raise ValueError(f"list span {row} lacks a "
                             f"list/row_start/rows/size key")
        if row["row_start"] + row["rows"] > L * P:
            raise ValueError(f"list span {row} overruns the codes file "
                             f"({L * P} rows)")
        if row["size"] > P:
            raise ValueError(f"list {row['list']} size {row['size']} "
                             f"exceeds list_pad {P}")
    if not check_files:
        return
    base = base_dir or "."
    for key, entry in files.items():
        full = os.path.join(base, entry["path"])
        if not os.path.exists(full):
            raise FileNotFoundError(f"manifest references missing host "
                                    f"file {entry['path']!r}")
        crc = _file_crc32(full)
        if crc != entry["crc32"]:
            raise ValueError(f"{entry['path']}: crc32 {crc:#010x} != "
                             f"manifest {entry['crc32']:#010x}")
        if key != "coarse":
            rows, dim = native.read_bin_header(full)
            if (rows, dim) != (entry["rows"], entry["dim"]):
                raise ValueError(
                    f"{entry['path']}: header [{rows}, {dim}] != "
                    f"manifest [{entry['rows']}, {entry['dim']}]")


def load_manifest(path: str) -> dict:
    with open(path) as fh:
        art = json.load(fh)
    validate_manifest(art, base_dir=os.path.dirname(path) or ".",
                      check_files=True)
    return art


def load_tiered(manifest_path: str, res: Optional[Resources] = None,
                arena: Optional[SlabArena] = None,
                arena_slots: Optional[int] = None,
                batch_rows: int = 1 << 16,
                registry: Optional[obs_metrics.Registry] = None,
                span_sink=None) -> TieredIvfPq:
    """Rebuild a :class:`TieredIvfPq` from its manifest: the packed
    codes stream in through ``native.iter_bin_batches_prefetch`` (IO
    overlapped with the copy into the pinned host block), everything
    else loads whole (small)."""
    art = load_manifest(manifest_path)
    base = os.path.dirname(manifest_path) or "."
    L, P, B = art["n_lists"], art["list_pad"], art["n_code_bytes"]
    files = art["files"]
    codes = np.empty((L * P, B), np.uint8)
    for off, batch in native.iter_bin_batches_prefetch(
            os.path.join(base, files["codes"]["path"]), batch_rows,
            dtype=np.uint8):
        codes[off:off + len(batch)] = batch
    ids = native.read_bin(os.path.join(base, files["ids"]["path"]),
                          dtype=np.int32)
    norms = native.read_bin(os.path.join(base, files["norms"]["path"]),
                            dtype=np.float32)
    sizes = native.read_bin(os.path.join(base, files["sizes"]["path"]),
                            dtype=np.int32).reshape(-1)
    tier = HostTier(codes.reshape(L, P, B), ids, sizes, norms)
    with np.load(os.path.join(base, files["coarse"]["path"])) as z:
        centers = jnp.asarray(z["centers"])
        rotation = jnp.asarray(z["rotation"])
        codebooks = jnp.asarray(z["codebooks"])
        od = on = oi = None
        if "overflow_indices" in z:
            od = jnp.asarray(z["overflow_decoded"])
            on = jnp.asarray(z["overflow_norms"])
            oi = jnp.asarray(z["overflow_indices"])
    res = ensure_resources(res)
    params = ivf_pq.IndexParams(
        n_lists=L, metric=DistanceType(art["metric"]),
        pq_bits=art["pq_bits"],
        codebook_kind=CodebookGen(art["codebook_kind"]))
    if arena is None:
        plan = solve_host_tier(L, P, art["rot_dim"], B,
                               res.workspace_limit_bytes)
        slots = arena_slots if arena_slots is not None \
            else plan["arena_slots"]
        arena = SlabArena(slots, P, art["rot_dim"], registry=registry,
                          span_sink=span_sink)
    return TieredIvfPq(params, art["pq_dim"], centers, rotation,
                       codebooks, tier, arena, art["n_rows"], od, on, oi,
                       namespace=art.get("namespace"), res=res)
