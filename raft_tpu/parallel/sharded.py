"""Sharded (multi-device / multi-host) index build & search.

Reference: the MNMG pattern raft-dask + cuML implement over ``raft::comms``
(SURVEY.md §2.8, §5): each worker holds a data partition with its own local
index; queries are broadcast; each worker searches locally, and the
per-worker top-k lists are merged (the
``knn_merge_parts`` pattern, detail/knn_merge_parts.cuh, applied across
ranks instead of tiles).

TPU-native design: partitions are mesh shards, not worker processes. The
whole search (local scan + cross-device merge) is ONE jitted SPMD program:
``shard_map`` runs the local search per device shard, ``all_gather`` moves
only the [nq, k] candidate lists over ICI (tiny vs the dataset), and the
merge is a final top-k — XLA overlaps the collective with compute. Dataset
shards never move. Build shards rows round-robin; ids stay global.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor, as_completed
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.core import tracing
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.obs import explain as obs_explain
from raft_tpu.obs import metrics as obs_metrics
from raft_tpu.obs import spans as obs_spans
from raft_tpu.ops.distance import (DistanceType, pairwise_core,
                                   resolve_metric, row_norms_sq)
from raft_tpu.ops.select_k import refine_multiplier, select_k
from raft_tpu.parallel.comms import Comms
from raft_tpu.utils.shape import cdiv

# MNMG observability (docs/observability.md): entry-point call counters
# plus checkpoint verify/restore outcomes — the numbers the runbook's
# pre-flight reads off /metrics after a restore drill
_SHARDED_SEARCHES = obs_metrics.REGISTRY.counter(
    "raft_tpu_sharded_search_total",
    "Sharded search/knn entry-point calls by family.", ("family",))
_CKPT_VERIFY = obs_metrics.REGISTRY.counter(
    "raft_tpu_checkpoint_verify_total",
    "verify_checkpoint runs by overall result.", ("result",))
_CKPT_FILES = obs_metrics.REGISTRY.counter(
    "raft_tpu_checkpoint_file_status_total",
    "Rank-file statuses observed by verify_checkpoint.", ("status",))
_CKPT_RESTORES = obs_metrics.REGISTRY.counter(
    "raft_tpu_checkpoint_restore_total",
    "Sharded checkpoint restores by kind and coverage mode.",
    ("kind", "mode"))

# ---- per-shard trace spans (docs/observability.md "Sharded search
# spans"): a module-level sink, installed by set_span_sink. With no sink
# (the default) every search entrypoint runs its usual single fused SPMD
# program — zero overhead, zero behavior change. With a sink installed,
# the same local cores run in a two-phase dispatch: phase A is the
# shard_map local scan WITHOUT the in-program merge (per-shard [nq, kk]
# candidates stay sharded), each shard is fenced in rank order to emit a
# per-shard child span (rank, device, readback-order completion ms),
# and phase B merges host-gathered candidates via ``_elastic_merge`` —
# bit-identical math to the in-program allgather merge (rank-order
# concat along the candidate axis feeding the same deterministic
# select_k), pinned by tests/test_parallel.py.
_SPAN_SINK_LOCK = threading.Lock()
_SPAN_SINK: Optional[object] = None


def set_span_sink(sink: Optional[object]) -> Optional[object]:
    """Install (or clear, with None) the sharded-search span sink.
    Anything with ``emit(dict)`` works (:class:`raft_tpu.obs.RingSink`,
    :class:`~raft_tpu.obs.JsonlSink`, ...). Returns the previous sink
    so callers can restore it."""
    global _SPAN_SINK
    with _SPAN_SINK_LOCK:
        prev, _SPAN_SINK = _SPAN_SINK, sink
    return prev


def _span_sink() -> Optional[object]:
    with _SPAN_SINK_LOCK:
        return _SPAN_SINK


def _instrumented_search(comms: Comms, local_scan, in_specs, args,
                         family: str, nq: int, k_eff: int,
                         minimize: bool, sink) -> Tuple[jax.Array,
                                                        jax.Array]:
    """Two-phase sharded search with per-shard child spans.

    ``local_scan`` is the entrypoint's per-device scan (returns the
    [nq, kk] local candidates WITHOUT the merge). Phase A runs it under
    shard_map with the candidates left sharded [S, nq, kk]; each shard
    is then fenced in rank order (``shard_search`` child spans — since
    the dispatch is one SPMD program, all shards compute concurrently
    and ``device_ms`` is each shard's completion lag in readback order,
    the per-rank skew signal). Phase B merges on the default device via
    :func:`_elastic_merge` and emits the parent ``sharded_search`` span
    carrying launch/merge/total wall time under the minted trace id."""
    ax = comms.axis
    trace_id = obs_spans.new_trace_id()
    t0 = time.perf_counter()

    def expanded(*a):
        v, i = local_scan(*a)
        return v[None], i[None]

    fn = comms.run(expanded, in_specs,
                   (P(ax, None, None), P(ax, None, None)))
    v, i = jax.jit(fn)(*args)
    t_launch = time.perf_counter()
    by_rank_i = {s.index[0].start or 0: s for s in i.addressable_shards}
    v_parts, i_parts = [], []
    for sh in sorted(v.addressable_shards,
                     key=lambda s: s.index[0].start or 0):
        rank = int(sh.index[0].start or 0)
        ts = time.perf_counter()
        v_np = np.asarray(sh.data)  # graftcheck: R001 — the fence
        i_np = np.asarray(by_rank_i[rank].data)  # graftcheck: R001
        obs_spans.safe_emit(sink, {
            "kind": "shard_search", "trace_id": trace_id,
            "family": family, "rank": rank, "device": str(sh.device),
            "device_ms": round((time.perf_counter() - ts) * 1e3, 3)})
        v_parts.append(v_np)
        i_parts.append(i_np)
    t_merge = time.perf_counter()
    vm, im = _elastic_merge(
        jnp.asarray(np.concatenate(v_parts, axis=0)),
        jnp.asarray(np.concatenate(i_parts, axis=0)),
        nq, k_eff, minimize)
    jax.block_until_ready((vm, im))
    t_end = time.perf_counter()
    obs_spans.safe_emit(sink, {
        "kind": "sharded_search", "trace_id": trace_id, "family": family,
        "n_shards": len(v_parts),
        "launch_ms": round((t_launch - t0) * 1e3, 3),
        "merge_ms": round((t_end - t_merge) * 1e3, 3),
        "total_ms": round((t_end - t0) * 1e3, 3)})
    return vm, im


# ------------------------------------------------- shard build orchestration


def _shard_device(comms: Comms, r: int) -> jax.Device:
    """First device of shard ``r``'s slice along the comms axis."""
    ax_pos = comms.mesh.axis_names.index(comms.axis)
    return np.asarray(np.take(comms.mesh.devices, r, axis=ax_pos)).flat[0]


def _map_shards(comms: Comms, fn, res: Resources, spans=None) -> dict:
    """Run ``fn(r, shard_res)`` for every shard whose device belongs to this
    process — on accelerator platforms one thread per local shard, each
    pinned to its shard's device via ``jax.default_device`` so per-shard
    builds dispatch to distinct chips instead of queueing on one (VERDICT
    r1 #5: the serial host loop serialized an 8× build); on the cpu
    platform serially (XLA:CPU compile-thread-safety, see below;
    RAFT_TPU_PARALLEL_BUILD=0/1 overrides either default). In a
    multi-controller deployment each process builds only its addressable
    shards (the raft-dask per-worker build role,
    raft_dask/common/comms.py:138-173).

    PRNG keys are pre-derived per shard (deterministic regardless of thread
    completion order). ``spans`` (rows per shard, when the caller knows
    them) lets the warm-up cover every distinct shard shape exactly."""
    size = comms.size
    keys = [res.next_key() for _ in range(size)]
    devs = {r: _shard_device(comms, r) for r in range(size)}
    pid = jax.process_index()
    local = [r for r in range(size) if devs[r].process_index == pid]
    results: dict = {}

    def run(r):
        shard_res = Resources(device=devs[r])
        shard_res._key = keys[r]
        with jax.default_device(devs[r]):
            results[r] = fn(r, shard_res)

    # XLA:CPU's compiler (LLVM JIT) is not safe under concurrent
    # compilation from multiple threads — and op-by-op dispatch compiles
    # per *device*, so even identical per-shard programs compile once per
    # pinned device (observed segfaults in backend_compile_and_load on
    # the 8-device virtual mesh, 128 GB free). Builds therefore run
    # serially on the cpu platform; accelerator platforms keep the
    # one-thread-per-shard dispatch. RAFT_TPU_PARALLEL_BUILD=1/0
    # overrides either way.
    force = os.environ.get("RAFT_TPU_PARALLEL_BUILD")
    if force is not None and force.lower() not in ("0", "1", "true",
                                                   "false", "on", "off"):
        raise ValueError(
            f"RAFT_TPU_PARALLEL_BUILD={force!r}: use 0/1/true/false/on/off")
    parallel = (devs[local[0]].platform != "cpu"
                if force is None
                else force.lower() in ("1", "true", "on")) if local else False
    if not parallel:
        for r in local:
            run(r)
        return results

    # Serial warm-up of one shard per distinct shard shape (from ``spans``
    # when provided; endpoint shards otherwise — linspace puts the odd
    # span sizes at the ends in the single-host case). The warm-up
    # populates the jit cache so the parallel workers mostly *execute*
    # concurrently instead of compiling.
    if spans is not None:
        seen: set = set()
        warm = []
        for r in local:
            s = int(spans[r])
            if s not in seen:
                seen.add(s)
                warm.append(r)
    else:
        warm = [local[0], *([local[-1]] if len(local) > 1 else [])]
    for r in warm:
        run(r)
    rest = [r for r in local if r not in warm]
    if len(rest) == 1:
        run(rest[0])
    elif rest:
        _run_parallel_cancelling(run, rest)
    return results


def _run_parallel_cancelling(run, ranks) -> None:
    """One thread per shard with first-failure cancellation: when any
    shard build raises, unstarted siblings never run and running siblings
    get a ``core.interruptible`` cancellation token — their next
    ``yield_now()``/``synchronize()`` raises instead of burning device
    hours completing builds whose results will be discarded. The FIRST
    failure propagates; sibling-cancellation fallout is suppressed."""
    from raft_tpu.core import interruptible

    failure: list = []
    tids: dict = {}
    lock = threading.Lock()

    def worker(r):
        with lock:
            if failure:
                return
            tids[r] = threading.get_ident()
        try:
            interruptible.yield_now()
            run(r)
        except interruptible.InterruptedException:
            with lock:
                if failure:
                    return  # cancelled because a sibling failed first
            raise
        except BaseException as e:
            with lock:
                failure.append(e)
                for rr, tid in tids.items():
                    if rr != r:
                        interruptible.cancel(tid)
            raise
        finally:
            with lock:
                tids.pop(r, None)
            # never leak an unconsumed token to a reused thread ident
            interruptible.release_token()

    with ThreadPoolExecutor(max_workers=len(ranks)) as ex:
        futs = [ex.submit(worker, r) for r in ranks]
        for f in as_completed(futs):
            if not f.cancelled() and f.exception() is not None:
                for other in futs:
                    other.cancel()
    if failure:
        raise failure[0]


def _global_max_shape(comms: Comms, local_max: np.ndarray) -> np.ndarray:
    """Elementwise max of a small int vector across processes (multi-host
    shard-shape agreement; single-process sees every shard already)."""
    if jax.process_count() == 1:
        return local_max
    x = jax.make_array_from_callback(
        (comms.size, len(local_max)),
        NamedSharding(comms.mesh, P(comms.axis, None)),
        lambda idx: np.asarray(local_max, np.int32)[None])
    fn = comms.run(lambda v: jax.lax.pmax(v[0], comms.axis),
                   P(comms.axis, None), P(None))
    return np.asarray(jax.jit(fn)(x))


def _global_any(comms: Comms, flag: bool) -> bool:
    """OR of a per-process bool (pmax of 0/1). Decisions that gate
    COLLECTIVES (e.g. whether overflow blocks get stacked) must be agreed
    globally — a process-local flag would deadlock the processes that
    disagree and compile divergent SPMD programs."""
    return bool(_global_max_shape(
        comms, np.asarray([1 if flag else 0], np.int64))[0])


def _stack_sharded(comms: Comms, parts: dict, fill=0):
    """Assemble ``{r: np.ndarray}`` per-shard blocks (ragged dims allowed —
    padded with ``fill``) into a global ``[S, ...]`` array sharded
    ``P(axis, None, ...)``. Each block is materialized only for its own
    device via ``make_array_from_callback`` — no host-side ``np.stack`` of
    all shards, and in multi-controller runs each process touches only its
    addressable shards (VERDICT r1 #5: assembly staged all state through
    one host's RAM)."""
    sample = next(iter(parts.values()))
    nd = sample.ndim
    local_max = np.zeros((nd,), np.int64)
    for p in parts.values():
        local_max = np.maximum(local_max, p.shape)
    inner = tuple(int(v) for v in _global_max_shape(comms, local_max))
    global_shape = (comms.size,) + inner
    sharding = NamedSharding(comms.mesh, P(comms.axis, *([None] * nd)))

    def cb(index):
        r = index[0].start or 0
        p = parts[r]
        if p.shape == inner:
            return p[None]
        block = np.full(inner, fill, dtype=sample.dtype)
        block[tuple(slice(0, s) for s in p.shape)] = p
        return block[None]

    return jax.make_array_from_callback(global_shape, sharding, cb)


# ------------------------------------------------------ placement planning
#
# Every sharded entrypoint used to re-derive the same facts inline — row
# bounds, per-shard candidate width, workspace tiles, and (implicitly) the
# one hardcoded all_gather merge. A PlacementPlan solves them once per
# (index, shape) and carries the resolved cross-chip merge engine, so the
# search bodies just execute the plan and ROADMAP item 2's router has one
# object to consume.

MERGE_MODES = ("auto", "allgather", "tree", "ring")


def shard_bounds(size: int, n: int) -> np.ndarray:
    """[S+1] balanced row offsets — THE row partition every sharded build
    uses (np.linspace keeps shard sizes within one row of each other and
    the last shard ragged when S ∤ n)."""
    return np.linspace(0, n, size + 1).astype(np.int64)


def _check_n_lists(bounds: np.ndarray, n_lists: int, n: int,
                   size: int) -> None:
    min_shard = int(np.diff(bounds).min())
    if n_lists > min_shard:
        raise ValueError(
            f"n_lists={n_lists} exceeds the smallest shard's "
            f"{min_shard} rows ({n} rows over {size} devices); every shard "
            f"builds its own index, so n_lists must be ≤ rows-per-shard")


@dataclasses.dataclass(frozen=True)
class PlacementPlan:
    """One sharded search, solved: where the rows live (mesh axis, size,
    bounds), what scans them (family + engine + tiles), and how the
    per-shard candidates merge across chips (mode + reason + predicted
    bytes). Frozen and cached per (index, shape) in ``_PLAN_CACHE`` —
    entrypoints execute plans, they don't re-derive them."""

    axis: str
    size: int
    n_rows: int
    bounds: Tuple[int, ...]   # [S+1] global row offsets ((∅) if unknown)
    family: str               # "brute_force" | "cagra" | "ivf_flat" | "ivf_pq"
    engine: str               # local scan engine ("xla", "cache", "lut", ...)
    nq: int
    k: int
    kk: int                   # per-shard candidate width entering the merge
    k_out: int                # merged output width = min(k, size*kk)
    merge_mode: str           # resolved: "allgather" | "tree" | "ring"
    merge_reason: str         # obs.explain REASONS member
    ring_shift: str           # "pallas" | "pallas_interpret" | "xla" | ""
    mask_invalid: bool        # mask id<0 candidates to ±inf before merging
    tiles: Tuple[Tuple[str, int], ...] = ()   # planner tile choices
    merge_bytes: Tuple[Tuple[str, int], ...] = ()  # predicted bytes by mode

    def explain_plan(self) -> dict:
        """The flat JSON-safe dict an ExplainRecord carries."""
        out = {"size": self.size, "kk": self.kk, "k_out": self.k_out,
               "merge_mode": self.merge_mode, "ring_shift": self.ring_shift}
        out.update({f"tile_{k}": v for k, v in self.tiles})
        out.update({f"merge_bytes_{k}": v for k, v in self.merge_bytes})
        return out


_PLAN_CACHE: dict = {}
_PLAN_CACHE_CAP = 256
_PLAN_LOCK = threading.Lock()
_PLAN_SOLVES = obs_metrics.REGISTRY.counter(
    "raft_tpu_placement_plan_solves_total",
    "PlacementPlan cache misses (fresh solves) by family.", ("family",))


def plan_cache_clear() -> None:
    """Test hook: drop every cached PlacementPlan (and the programs built
    from them)."""
    with _PLAN_LOCK:
        _PLAN_CACHE.clear()
    _knn_program.cache_clear()


def merge_dispatch_explained(merge_mode: str, size: int):
    """Resolve the cross-chip merge engine: ``(engine, reason,
    ring_shift)`` with reason from ``obs.explain.REASONS`` — the merge
    analog of ``ops.pallas_kernels.fused_dispatch_explained``. ``auto``
    takes the pure-XLA tree merge on any power-of-two mesh, on and off the
    chip: the RDMA ring kernel has no chip measurement, so it runs only
    when asked for (``ring``). Non-power-of-two meshes fall back to
    all_gather (the tree pairs ranks by XOR)."""
    on_tpu = jax.default_backend() == "tpu"
    interp = os.environ.get("RAFT_TPU_PALLAS_INTERPRET") == "1"
    pow2 = size >= 2 and (size & (size - 1)) == 0
    if merge_mode == "allgather":
        return "allgather", "forced", ""
    if merge_mode == "tree":
        if not pow2:
            raise ValueError(
                f"merge_mode='tree' needs a power-of-two mesh axis "
                f"(size={size}); use 'allgather' or 'auto'")
        return "tree", "forced", ""
    if merge_mode == "ring":
        if size < 2:
            raise ValueError("merge_mode='ring' needs a mesh axis of at "
                             "least 2 devices")
        # explicit request is the opt-in (cf. scan_mode="pallas"):
        # hardware RDMA on TPU, Mosaic interpreter under the parity hook,
        # the same ring schedule over XLA ppermute elsewhere
        shift = ("pallas" if on_tpu
                 else "pallas_interpret" if interp else "xla")
        return "ring", "forced", shift
    if merge_mode != "auto":
        raise ValueError(f"unknown merge_mode: {merge_mode!r} "
                         f"(one of {MERGE_MODES})")
    if not pow2:
        return "allgather", "merge_allgather", ""
    return "tree", "merge_tree", ""


def plan_sharded_search(comms: Comms, family: str, n_rows: int, bounds,
                        nq: int, k: int, kk: int, engine: str,
                        merge_mode: str = "auto", mask_invalid: bool = False,
                        tiles: Optional[dict] = None) -> PlacementPlan:
    """Solve (or fetch) the PlacementPlan for one sharded search shape.

    Cached on the full solving key — including backend and merge_mode, so
    a probe artifact landing mid-process or an env flip retraces rather
    than reusing a stale resolution (the select_k AUTO-table rule)."""
    from raft_tpu.core.resources import solve_merge_bytes

    bounds_t = tuple(int(b) for b in bounds) if bounds is not None else ()
    tiles_t = tuple(sorted((tiles or {}).items()))
    key = (family, comms.axis, comms.size, int(n_rows), bounds_t, int(nq),
           int(k), int(kk), engine, merge_mode, bool(mask_invalid), tiles_t,
           jax.default_backend(),
           os.environ.get("RAFT_TPU_PALLAS_INTERPRET"))
    with _PLAN_LOCK:
        plan = _PLAN_CACHE.get(key)
    if plan is not None:
        return plan
    mode, reason, ring_shift = merge_dispatch_explained(merge_mode,
                                                        comms.size)
    k_out = min(int(k), comms.size * int(kk))
    mb = solve_merge_bytes(comms.size, int(nq), int(kk), k_out)
    plan = PlacementPlan(
        axis=comms.axis, size=comms.size, n_rows=int(n_rows),
        bounds=bounds_t, family=family, engine=engine, nq=int(nq),
        k=int(k), kk=int(kk), k_out=k_out, merge_mode=mode,
        merge_reason=reason, ring_shift=ring_shift,
        mask_invalid=bool(mask_invalid), tiles=tiles_t,
        merge_bytes=tuple(sorted(mb.items())))
    _PLAN_SOLVES.labels(family).inc()
    with _PLAN_LOCK:
        if len(_PLAN_CACHE) >= _PLAN_CACHE_CAP:
            _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
        _PLAN_CACHE[key] = plan
    return plan


def _plan_merge(comms: Comms, plan: PlacementPlan, v, i, minimize: bool):
    """Execute the plan's cross-chip merge (traceable, inside shard_map).
    All three engines are bit-identical by construction: allgather is the
    reference rank-order concat + stable select_k; tree and ring select
    by explicit (value, concat-pos) lexicographic order, which equals the
    stable selection for any merge schedule (comms.py)."""
    if plan.mask_invalid:
        v = jnp.where(i < 0, jnp.inf if minimize else -jnp.inf, v)
    if plan.merge_mode == "allgather":
        v_all = comms.allgather(v, axis=1)
        i_all = comms.allgather(i, axis=1)
        vm, sel = select_k(v_all, plan.k_out, select_min=minimize)
        return vm, jnp.take_along_axis(i_all, sel, axis=1)
    if plan.merge_mode == "tree":
        return comms.tree_topk_merge(v, i, plan.k_out, select_min=minimize)
    shift = None
    if plan.ring_shift.startswith("pallas"):
        from raft_tpu.ops.pallas_kernels import pallas_ring_shift

        interp = plan.ring_shift == "pallas_interpret"
        shift = functools.partial(pallas_ring_shift, axis=comms.axis,
                                  size=comms.size, interpret=interp)
    return comms.ring_topk_merge(v, i, plan.k_out, select_min=minimize,
                                 shift=shift)


def _record_plan(plan: PlacementPlan, requested: str,
                 params: Optional[dict] = None) -> None:
    """Emit the merge-dispatch ExplainRecord for one sharded search call
    (the parallel/ analog of the single-chip families' attribution —
    graftcheck R007 covers these sites)."""
    p = {"nq": plan.nq, "k": plan.k, "engine": plan.engine}
    p.update(params or {})
    obs_explain.record_dispatch(
        f"sharded_{plan.family}", requested, plan.merge_mode,
        plan.merge_reason, params=p, plan=plan.explain_plan())


# ----------------------------------------------------------- sharded knn


@tracing.range("sharded.knn")
def knn(
    comms: Comms,
    queries,
    dataset,
    k: int,
    metric="sqeuclidean",
    res: Optional[Resources] = None,
    merge_mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN over a row-sharded dataset: local brute force per shard +
    ICI merge (the SPMD analog of MNMG brute_force over raft::comms).

    Each chip scans its shard with ``brute_force``'s tiled core: only one
    [queries, tile] distance block is live at a time, and each tile's
    top-k is ``select_k``'s exact AUTO choice for the tile's width.

    ``dataset`` may already be sharded over ``comms.axis``; otherwise it is
    placed with row sharding here. ``merge_mode`` picks the cross-chip
    top-k merge (docs/sharding.md): "auto" routes the streaming tree/ring
    ladder, "allgather" the legacy full-slab merge — all bit-identical.
    Returns replicated (distances, indices) with global row ids.
    """
    from raft_tpu.neighbors import brute_force

    _SHARDED_SEARCHES.labels("brute_force").inc()
    res = ensure_resources(res)
    m = resolve_metric(metric)
    minimize = m != DistanceType.InnerProduct
    queries = jnp.asarray(queries)
    dataset = jnp.asarray(dataset)
    n, dim = dataset.shape
    nq = queries.shape[0]
    size = comms.size
    shard = cdiv(n, size)
    n_pad = shard * size
    if n_pad != n:
        dataset = jnp.pad(dataset, ((0, n_pad - n), (0, 0)))
    x = comms.shard(dataset, P(comms.axis, None))
    q = comms.shard(queries, P(None, None))

    kk = min(k, shard)
    budget = res.workspace_limit_bytes
    q_tile, db_tile = brute_force.choose_tiles(nq, shard, dim, kk, budget)
    group_scan = brute_force.plan_group_scan(
        m, jnp.promote_types(queries.dtype, dataset.dtype),
        comms.mesh.devices.flat[0], q_tile, shard, db_tile, dim, kk)
    brute_force.record_group_scan(
        group_scan, q_tile, db_tile,
        {"nq": nq, "k": k, "metric": m.name, "sharded": size})
    sink = _span_sink()
    if sink is not None:
        return _instrumented_search(
            comms, _knn_local_scan(comms, m, n, shard, kk, q_tile, db_tile,
                                   budget, group_scan),
            (P(None, None), P(comms.axis, None)), (q, x), "brute_force",
            nq, min(k, size * kk), minimize, sink)

    plan = plan_sharded_search(
        comms, "brute_force", n, tuple(range(0, n_pad + 1, shard)),
        nq, k, kk, "xla", merge_mode=merge_mode,
        tiles={"q_tile": q_tile, "db_tile": db_tile})
    _record_plan(plan, merge_mode, {"metric": m.name})
    return _knn_program(comms, plan, m, budget, group_scan)(q, x)


def _knn_local_scan(comms: Comms, metric: DistanceType, n: int, shard: int,
                    kk: int, q_tile: int, db_tile: int, budget: int,
                    group_scan):
    """Each chip's part of ``knn``: ``brute_force``'s tiled exact core over
    its shard (tiles by ``group_scan``, ``brute_force.plan_group_scan``),
    rows past ``n`` (the last shard's padding) masked, ids made global →
    the shard's ``kk`` best (values, ids)."""
    from raft_tpu.neighbors import brute_force

    def local_scan(q_rep, x_loc):
        base = comms.rank() * shard
        # the shard's norms once, as brute_force.build keeps them: a norm
        # fused into each tile's distances may round by the tile's shape
        norms = (row_norms_sq(x_loc) if metric in brute_force.NORM_METRICS
                 else None)
        v, i = brute_force.knn_core(
            q_rep, x_loc, norms, jnp.zeros((0,), jnp.uint32), metric, 2.0,
            kk, q_tile, db_tile, budget, n_valid=n - base,
            group_scan=group_scan)
        return v, (i + base).astype(jnp.int32)

    return local_scan


@functools.lru_cache(maxsize=64)
def _knn_program(comms: Comms, plan: PlacementPlan, metric: DistanceType,
                 budget: int, group_scan):
    """The jitted SPMD program of one ``knn`` plan (local scan + merge):
    calls with the same comms and plan reuse it, so a repeated shape
    compiles once."""
    tiles = dict(plan.tiles)
    scan = _knn_local_scan(comms, metric, plan.n_rows, plan.bounds[1],
                           plan.kk, tiles["q_tile"], tiles["db_tile"], budget,
                           group_scan)
    minimize = metric != DistanceType.InnerProduct

    def local(q_rep, x_loc):
        return _plan_merge(comms, plan, *scan(q_rep, x_loc), minimize)

    return jax.jit(comms.run(local, (P(None, None), P(comms.axis, None)),
                             (P(None, None), P(None, None))))


# ---------------------------------------------- sharded pairwise distance


@tracing.range("sharded.pairwise_distance")
def pairwise_distance(
    comms: Comms,
    x,
    y,
    metric="sqeuclidean",
    metric_arg: float = 2.0,
    res: Optional[Resources] = None,
) -> jax.Array:
    """Full [n, m] pairwise distances with BOTH operands row-sharded — the
    MNMG pairwise primitive consumers run over raft::comms (cuML's
    distributed pairwise role).

    Ring schedule (the ring-attention pattern applied to distance tiles):
    x shards stay put; y shards rotate over ICI via ``ppermute``, each
    device computing one [n/S, m/S] MXU tile per step and writing it into
    its output row-block. Peak per-device memory is O(nm/S²) per step +
    the [n/S, m] output block; only y's shards ever move, overlapping with
    compute (XLA schedules the collective ahead of the matmul).

    Returns the distance matrix sharded over rows of ``x``.
    """
    ensure_resources(res)
    m_ = resolve_metric(metric)
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    n, dim = x.shape
    m, _ = y.shape
    size = comms.size
    xs_rows = cdiv(n, size)
    ys_rows = cdiv(m, size)
    xp = jnp.pad(x, ((0, xs_rows * size - n), (0, 0)))
    yp = jnp.pad(y, ((0, ys_rows * size - m), (0, 0)))
    xsh = comms.shard(xp, P(comms.axis, None))
    ysh = comms.shard(yp, P(comms.axis, None))

    def local(x_loc, y_loc):
        rank = comms.rank()

        def tile(i, y_cur, out):
            # after i ring shifts, this device holds shard (rank - i)
            src = (rank - i) % size
            d = pairwise_core(x_loc, y_cur, m_, metric_arg, 1 << 30)
            return jax.lax.dynamic_update_slice(
                out, d.astype(out.dtype), (0, src * ys_rows))

        def step(i, carry):
            y_cur, out = carry
            return comms.shift(y_cur, 1), tile(i, y_cur, out)

        out0 = jnp.zeros((x_loc.shape[0], ys_rows * size), jnp.float32)
        # size-1 compute+shift steps, then a final compute — the last
        # rotation's payload would never be read, so it is never sent
        y_last, out = jax.lax.fori_loop(0, size - 1, step, (y_loc, out0))
        return tile(size - 1, y_last, out)

    fn = comms.run(local, (P(comms.axis, None), P(comms.axis, None)),
                   P(comms.axis, None))
    out = jax.jit(fn)(xsh, ysh)
    return out[:n, :m]


# ------------------------------------------------------- sharded k-means


@tracing.range("sharded.kmeans_fit")
def kmeans_fit(
    comms: Comms,
    x,
    n_clusters: int,
    n_iters: int = 20,
    key=None,
    res: Optional[Resources] = None,
    balance_threshold: Optional[float] = None,
    donor_pool: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """Data-parallel Lloyd k-means over a row-sharded dataset (the MNMG
    k-means pattern: local assignment, psum of per-cluster sums/counts —
    what cuML does over raft::comms allreduce). Returns (centers, labels).

    ``balance_threshold`` turns on the multi-host analog of
    ``cluster.kmeans_balanced``'s adjust_centers: each iteration, clusters
    whose GLOBAL (psum'd) size falls at or below ``threshold · n/K`` are
    re-seeded toward a donor row from a big (size ≥ average) cluster —
    new_center = (wc·center[donor's cluster] + donor)/(wc+1), wc =
    min(size, 7), exactly the reference rescue but fed by the mesh-wide
    counts. The donor pool is sampled once host-side and replicated, so
    the rescue is pure replicated math and every device stays consistent
    (the rotation of pool slots per iteration stands in for the
    single-chip trainer's per-iteration resampling)."""
    res = ensure_resources(res)
    if key is None:
        key = res.next_key()
    x = jnp.asarray(x).astype(jnp.float32)
    n, dim = x.shape
    size = comms.size
    shard = cdiv(n, size)
    n_pad = shard * size
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    xs = comms.shard(x, P(comms.axis, None))
    # init must consume `key` exactly as the pre-balanced trainer did so a
    # fixed seed reproduces the same clustering when balancing is off
    init = jax.random.choice(key, n, (n_clusters,), replace=False)
    centers0 = comms.shard(jnp.asarray(x)[jnp.sort(init)], P(None, None))
    balanced = balance_threshold is not None
    if balanced:
        dkey = jax.random.fold_in(key, 1)
        pick = jax.random.randint(dkey, (int(donor_pool),), 0, n)
        donors0 = comms.shard(jnp.asarray(x)[pick], P(None, None))

    def _rescue(it, new_c, counts, donors):
        avg = jnp.float32(n) / n_clusters
        starving = counts <= avg * jnp.float32(balance_threshold)
        big = counts >= avg
        # donor labels vs the freshly updated centers (tiny pool matmul)
        cn = jnp.sum(new_c * new_c, -1)
        dd = cn[None, :] - 2.0 * donors @ new_c.T
        dlab = jnp.argmin(dd, axis=1)
        pool_ok = big[dlab]
        order = jnp.argsort(~pool_ok)  # good donors first (stable)
        drows, dlab = donors[order], dlab[order]
        n_good = jnp.sum(pool_ok.astype(jnp.int32))
        slot = (jnp.arange(n_clusters) + it * 131) % jnp.maximum(n_good, 1)
        have = (n_good > 0) & starving
        wc = jnp.minimum(counts, 7.0)[:, None]
        resc = (wc * new_c[dlab[slot]] + drows[slot]) / (wc + 1.0)
        return jnp.where(have[:, None], resc, new_c)

    def local(x_loc, c0, donors):
        rank = comms.rank()
        base = rank * shard
        valid = (jnp.arange(shard) + base) < n

        def step(c, it):
            cn = jnp.sum(c * c, -1)
            d = cn[None, :] - 2.0 * jax.lax.dot_general(
                x_loc, c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            labels = jnp.argmin(d, axis=1)
            w = valid.astype(jnp.float32)
            sums = jnp.zeros((n_clusters, dim), jnp.float32).at[labels].add(
                x_loc * w[:, None])
            counts = jnp.zeros((n_clusters,), jnp.float32).at[labels].add(w)
            sums = comms.allreduce(sums)  # psum over ICI
            counts = comms.allreduce(counts)
            new_c = jnp.where(counts[:, None] > 0,
                              sums / jnp.maximum(counts, 1.0)[:, None], c)
            if balanced:
                new_c = _rescue(it, new_c, counts, donors)
            return new_c, None

        c_final, _ = jax.lax.scan(step, c0, jnp.arange(n_iters))
        cn = jnp.sum(c_final * c_final, -1)
        d = cn[None, :] - 2.0 * x_loc @ c_final.T
        labels = jnp.argmin(d, axis=1).astype(jnp.int32)
        return c_final, labels

    out_specs = (P(None, None), P(comms.axis))
    if balanced:
        fn = comms.run(local, (P(comms.axis, None), P(None, None),
                               P(None, None)), out_specs)
        centers, labels = jax.jit(fn)(xs, centers0, donors0)
    else:
        fn = comms.run(lambda xl, c0: local(xl, c0, None),
                       (P(comms.axis, None), P(None, None)), out_specs)
        centers, labels = jax.jit(fn)(xs, centers0)
    return centers, labels[:n]


# ----------------------------------------------------- sharded cagra


class ShardedCagra:
    """A CAGRA index partitioned over a mesh axis: each device owns the
    graph + dataset of its row shard; queries replicate; per-shard beam
    searches merge over ICI (raft-dask-style MNMG deployment of a
    graph index)."""

    def __init__(self, comms: Comms, datasets, graphs, metric: DistanceType,
                 n_rows: int, bounds):
        self.comms = comms
        self.datasets = datasets  # [S, shard_pad, dim]
        self.graphs = graphs  # [S, shard_pad, degree] local ids
        self.metric = metric
        self.n_rows = n_rows
        self.bounds = bounds  # [S + 1] row offsets per shard
        self._datasets_bf16 = None  # lazy bf16 copies for scan_dtype

    def ensure_scan_datasets(self):
        if self._datasets_bf16 is None:
            self._datasets_bf16 = self.datasets.astype(jnp.bfloat16)
        return self._datasets_bf16


@tracing.range("sharded.build_cagra")
def build_cagra(
    comms: Comms,
    dataset,
    params=None,
    res: Optional[Resources] = None,
) -> ShardedCagra:
    """Per-shard CAGRA builds over row partitions, dispatched concurrently
    one shard per device (see _map_shards).

    Multi-controller contract: every process must pass the IDENTICAL full
    ``dataset`` and an identically-seeded ``res`` (see build_ivf_pq)."""
    from raft_tpu.neighbors import cagra

    res = ensure_resources(res)
    params = params or cagra.IndexParams()
    dataset = np.asarray(dataset)
    n, dim = dataset.shape
    bounds = shard_bounds(comms.size, n)

    def one(r, shard_res):
        lo, hi = bounds[r], bounds[r + 1]
        idx = cagra.build(dataset[lo:hi], params, res=shard_res)
        return np.asarray(idx.dataset), np.asarray(idx.graph)

    subs = _map_shards(comms, one, res, spans=np.diff(bounds))
    # padding rows point at node 0 and are never seeded (their distances
    # are real but they are unreachable unless linked)
    return ShardedCagra(
        comms,
        _stack_sharded(comms, {r: s[0] for r, s in subs.items()}),
        _stack_sharded(comms, {r: s[1] for r, s in subs.items()}),
        params.metric, n, bounds)


@tracing.range("sharded.search_cagra")
def search_cagra(
    index: ShardedCagra,
    queries,
    k: int,
    params=None,
    res: Optional[Resources] = None,
    merge_mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """SPMD CAGRA search: per-device beam search over its shard's graph,
    local ids mapped to global row ids, then the planned cross-chip top-k
    merge over ICI (``merge_mode``, docs/sharding.md)."""
    from raft_tpu.neighbors import cagra

    _SHARDED_SEARCHES.labels("cagra").inc()
    ensure_resources(res)
    params = params or cagra.SearchParams()
    comms = index.comms
    queries = jnp.asarray(queries)
    nq = queries.shape[0]
    minimize = index.metric != DistanceType.InnerProduct
    size = comms.size
    shard_rows = jnp.asarray(
        np.diff(index.bounds).astype(np.int32))  # valid rows per shard
    base = jnp.asarray(index.bounds[:-1].astype(np.int32))
    # same resolved beam plan as the single-host engine (seeds scale with
    # num_random_samplings and may exceed the buffer — they enter through
    # the merge), sized to the per-shard row count
    itopk, width, max_iter, n_seeds = cagra.resolve_search_plan(
        params, k, int(index.datasets.shape[1]))
    degree = index.graphs.shape[2]
    key = jax.random.fold_in(
        jax.random.key(params.rand_xor_mask & 0x7FFFFFFF), nq)
    empty = jnp.zeros((0,), jnp.uint32)
    fast_scan = getattr(params, "scan_dtype", None) is not None
    if fast_scan:
        if jnp.dtype(params.scan_dtype) != jnp.bfloat16:
            raise ValueError(
                f"scan_dtype={params.scan_dtype!r}: only bfloat16 is "
                "supported")
        if index.datasets.dtype != jnp.float32:
            raise ValueError("scan_dtype requires an fp32 dataset")

    def local_scan(q_rep, ds, sds, gr, n_valid, b):
        # per-shard seeds within the shard's valid rows
        rank = comms.rank()
        seeds = jax.random.randint(
            jax.random.fold_in(key, rank), (q_rep.shape[0], n_seeds), 0,
            jnp.maximum(n_valid[0], 1), jnp.int32)
        v, i = cagra.search_core(
            q_rep, ds[0], sds[0], gr[0], seeds, empty, index.metric, int(k),
            itopk, width, max_iter, False, fast_scan)
        # local → global ids; mask out padding rows
        pad_hit = (i < 0) | (i >= n_valid[0])
        gid = jnp.where(pad_hit, -1, i + b[0])
        v = jnp.where(pad_hit, jnp.inf if minimize else -jnp.inf, v)
        return v, gid

    ax = comms.axis
    in_specs = (P(None, None), P(ax, None, None), P(ax, None, None),
                P(ax, None, None), P(ax), P(ax))
    q = comms.shard(queries, P(None, None))
    # bf16 scan copies are cached on the index (one cast, reused per search)
    scan_ds = index.ensure_scan_datasets() if fast_scan else index.datasets
    args = (q, index.datasets, scan_ds, index.graphs,
            comms.shard(shard_rows, P(ax)), comms.shard(base, P(ax)))
    sink = _span_sink()
    if sink is not None:
        return _instrumented_search(comms, local_scan, in_specs, args,
                                    "cagra", nq, int(k), minimize, sink)

    plan = plan_sharded_search(
        comms, "cagra", index.n_rows, index.bounds, nq, int(k), int(k),
        "xla", merge_mode=merge_mode)
    _record_plan(plan, merge_mode,
                 {"itopk": itopk, "search_width": width})

    def local(q_rep, ds, sds, gr, n_valid, b):
        v, gid = local_scan(q_rep, ds, sds, gr, n_valid, b)
        return _plan_merge(comms, plan, v, gid, minimize)

    fn = comms.run(local, in_specs, (P(None, None), P(None, None)))
    return jax.jit(fn)(*args)


# --------------------------------------------------- sharded ivf_flat search


class ShardedIvfFlat:
    """An IVF-Flat index partitioned over a mesh axis: each device owns a
    full local index over its row shard (the raft-dask deployment shape);
    search is one SPMD program with an ICI candidate merge."""

    def __init__(self, comms: Comms, centers, list_data, list_indices,
                 list_sizes, metric: DistanceType, n_rows: int,
                 overflow_data=None, overflow_indices=None):
        self.comms = comms
        # all leading-axis [size, ...] stacked per-shard arrays
        self.centers = centers  # [S, L, dim]
        self.list_data = list_data  # [S, L, pad, dim]
        self.list_indices = list_indices  # [S, L, pad] global ids
        self.list_sizes = list_sizes  # [S, L]
        self.metric = metric
        self.n_rows = n_rows
        # per-shard budget-capped spill blocks (global ids; [S, O, dim] /
        # [S, O], O = max over shards, -1-padded) — each device scans its
        # own block alongside its probed lists
        self.overflow_data = overflow_data
        self.overflow_indices = overflow_indices
        # full-mesh restore always serves every row (degraded restores go
        # through the elastic classes, which compute a real fraction)
        self.coverage = 1.0


@tracing.range("sharded.build_ivf_flat")
def build_ivf_flat(
    comms: Comms,
    dataset,
    params=None,
    res: Optional[Resources] = None,
) -> ShardedIvfFlat:
    """Build per-shard IVF-Flat indexes over row partitions with global ids
    (host-orchestrated like raft-dask's per-worker build; the per-shard
    build itself is the single-chip path).

    Multi-controller contract: every process must pass the IDENTICAL full
    ``dataset`` and an identically-seeded ``res`` (see build_ivf_pq)."""
    from raft_tpu.neighbors import ivf_flat

    res = ensure_resources(res)
    params = params or ivf_flat.IndexParams()
    dataset = np.asarray(dataset)
    n = len(dataset)
    size = comms.size
    bounds = shard_bounds(size, n)
    _check_n_lists(bounds, params.n_lists, n, size)

    def one(r, shard_res):
        lo, hi = bounds[r], bounds[r + 1]
        idx = ivf_flat.build(dataset[lo:hi], params, res=shard_res)
        # rewrite ids to global row ids (spilled rows included)
        gl_idx = np.asarray(idx.list_indices)
        gl_idx = np.where(gl_idx >= 0, gl_idx + lo, -1).astype(np.int32)
        return idx, gl_idx, _globalize_overflow_ids(idx, lo)

    subs = _map_shards(comms, one, res, spans=np.diff(bounds))
    out = _assemble_sharded_ivf_flat(comms, subs, params, n)
    out.bounds = bounds
    return out


def _globalize_overflow_ids(idx, lo: int) -> np.ndarray:
    over = np.asarray(idx.overflow_indices)
    return np.where(over >= 0, over + lo, -1).astype(np.int32)


@tracing.range("sharded.build_ivf_flat_from_file")
def build_ivf_flat_from_file(
    comms: Comms,
    path: str,
    params=None,
    res: Optional[Resources] = None,
    batch_rows: int = 1 << 18,
    dtype=None,
    max_train_rows: Optional[int] = None,
) -> ShardedIvfFlat:
    """Streamed MNMG IVF-Flat build: each shard builds out-of-core from its
    row span of the fbin file (ids file-absolute), then shard state is
    placed across the mesh for SPMD search."""
    from raft_tpu.neighbors import ivf_flat, ooc

    params = params or ivf_flat.IndexParams()
    return _build_sharded_from_file(
        comms, path, params, ooc.build_ivf_flat_from_file,
        _assemble_sharded_ivf_flat, res, batch_rows, dtype, max_train_rows)


def _build_sharded_from_file(comms, path, params, ooc_builder, assembler,
                             res, batch_rows, dtype, max_train_rows):
    """Shared streamed-MNMG skeleton: row-span bounds, per-shard ooc build
    (file-absolute ids), mesh placement via ``assembler``."""
    from raft_tpu import native

    res = ensure_resources(res)
    n, _ = native.read_bin_header(path)
    size = comms.size
    bounds = shard_bounds(size, n)
    _check_n_lists(bounds, params.n_lists, n, size)

    def one(r, shard_res):
        lo, hi = int(bounds[r]), int(bounds[r + 1])
        idx = ooc_builder(
            path, params, res=shard_res, batch_rows=batch_rows, dtype=dtype,
            max_train_rows=max_train_rows, row_range=(lo, hi))
        # ids are file-absolute already, overflow ids included
        return idx, np.asarray(idx.list_indices), np.asarray(
            idx.overflow_indices)

    subs = _map_shards(comms, one, res, spans=np.diff(bounds))
    out = assembler(comms, subs, params, n)
    out.bounds = bounds
    return out


def _assemble_sharded_ivf_flat(comms: Comms, subs, params, n: int
                               ) -> ShardedIvfFlat:
    """Place per-shard ``{r: (Index, global_ids, global_overflow_ids)}``
    as mesh-sharded [S, ...] state (ragged list pads equalized per field;
    no one-host staging)."""
    any_overflow = _global_any(
        comms, any(len(go) for _, _, go in subs.values()))
    return ShardedIvfFlat(
        comms,
        _stack_sharded(comms, {r: np.asarray(i.centers)
                               for r, (i, _, _) in subs.items()}),
        _stack_sharded(comms, {r: np.asarray(i.list_data)
                               for r, (i, _, _) in subs.items()}),
        _stack_sharded(comms, {r: g for r, (_, g, _) in subs.items()},
                       fill=-1),
        _stack_sharded(comms, {r: np.asarray(i.list_sizes)
                               for r, (i, _, _) in subs.items()}),
        params.metric, n,
        overflow_data=_stack_sharded(
            comms, {r: np.asarray(i.overflow_data)
                    for r, (i, _, _) in subs.items()})
        if any_overflow else None,
        overflow_indices=_stack_sharded(
            comms, {r: go for r, (_, _, go) in subs.items()}, fill=-1)
        if any_overflow else None)


# ----------------------------------------------------- sharded ivf_pq


class ShardedIvfPq:
    """An IVF-PQ index partitioned over a mesh axis (BASELINE target #4:
    DEEP-100M pq_dim=64 sharded over ICI): each device owns a full local
    IVF-PQ index over its row shard; search is one SPMD program with an ICI
    top-k merge. Two storage engines (the single-chip scan_mode pair):
    ``cache`` keeps the decoded-residual scan cache resident
    ([S, L, pad, rot] bf16 — fastest MXU scan), ``lut`` keeps only the
    packed codes + codebooks ([S, L, pad, B] u8 — ~2× more rows per chip
    at pq_bits=8, the DEEP-100M/8 memory-lean shape)."""

    def __init__(self, comms: Comms, centers, rotation, list_indices,
                 list_sizes, metric: DistanceType, n_rows: int,
                 list_decoded=None, decoded_norms=None, codebooks=None,
                 list_codes=None, per_cluster: bool = False,
                 pq_dim: int = 0, pq_bits: int = 8,
                 overflow_decoded=None, overflow_norms=None,
                 overflow_indices=None):
        self.comms = comms
        # all leading-axis [S, ...] stacked per-shard arrays
        self.centers = centers  # [S, L, dim]
        self.rotation = rotation  # [S, rot, dim]
        self.list_indices = list_indices  # [S, L, pad] global ids
        self.list_sizes = list_sizes  # [S, L]
        self.metric = metric
        self.n_rows = n_rows
        # cache engine state (None when built with scan_mode="lut")
        self.list_decoded = list_decoded  # [S, L, pad, rot] bf16
        self.decoded_norms = decoded_norms  # [S, L, pad] f32
        # lut engine state (None when built with scan_mode="cache")
        self.codebooks = codebooks  # [S, G, book, pq_len]
        self.list_codes = list_codes  # [S, L, pad, n_bytes] u8
        self.per_cluster = per_cluster
        self.pq_dim = pq_dim
        self.pq_bits = pq_bits
        # per-shard budget-capped spill blocks, decoded to full rotated
        # vectors (see ivf_pq.ensure_overflow_decoded); global ids,
        # [S, O, rot] / [S, O] — shared by both engines
        self.overflow_decoded = overflow_decoded
        self.overflow_norms = overflow_norms
        self.overflow_indices = overflow_indices
        # full-mesh restore always serves every row (degraded restores go
        # through the elastic classes, which compute a real fraction)
        self.coverage = 1.0


@tracing.range("sharded.build_ivf_pq")
def build_ivf_pq(
    comms: Comms,
    dataset,
    params=None,
    res: Optional[Resources] = None,
    scan_mode: str = "cache",
    scan_cache_dtype=jnp.bfloat16,
) -> ShardedIvfPq:
    """Build per-shard IVF-PQ indexes over row partitions with global ids,
    dispatched concurrently one shard per device. ``scan_mode="cache"``
    materializes the decoded scan cache per shard (fastest search);
    ``"lut"`` keeps only packed codes + codebooks resident (memory-lean,
    VERDICT r1 #7 — roughly doubles the max shard at pq_bits=8).
    ``scan_cache_dtype`` also sets the overflow-block decode dtype for
    *lut* builds — pin it to fp32 when comparing engines bit-for-bit.

    Multi-controller contract: every process must pass the IDENTICAL full
    ``dataset`` and an identically-seeded ``res`` — each process slices its
    own shards from it, and divergent inputs silently produce inconsistent
    shard state. For datasets too big to replicate, use
    :func:`build_ivf_pq_from_file` (per-process row spans from a shared
    file)."""
    from raft_tpu.neighbors import ivf_pq

    res = ensure_resources(res)
    params = params or ivf_pq.IndexParams()
    dataset = np.asarray(dataset)
    n = len(dataset)
    size = comms.size
    bounds = shard_bounds(size, n)
    _check_n_lists(bounds, params.n_lists, n, size)

    def one(r, shard_res):
        lo, hi = bounds[r], bounds[r + 1]
        idx = ivf_pq.build(dataset[lo:hi], params, res=shard_res)
        gl_idx = np.asarray(idx.list_indices)
        gl_idx = np.where(gl_idx >= 0, gl_idx + lo, -1).astype(np.int32)
        return idx, gl_idx, _globalize_overflow_ids(idx, lo)

    subs = _map_shards(comms, one, res, spans=np.diff(bounds))
    out = _assemble_sharded_ivf_pq(comms, subs, params, n,
                                   scan_mode=scan_mode,
                                   scan_cache_dtype=scan_cache_dtype)
    out.bounds = bounds
    return out


@tracing.range("sharded.build_ivf_pq_from_file")
def build_ivf_pq_from_file(
    comms: Comms,
    path: str,
    params=None,
    res: Optional[Resources] = None,
    batch_rows: int = 1 << 18,
    dtype=None,
    max_train_rows: Optional[int] = None,
    scan_mode: str = "cache",
    scan_cache_dtype=jnp.bfloat16,
) -> ShardedIvfPq:
    """Streamed MNMG IVF-PQ build (BASELINE target #4 at DEEP-100M scale):
    each shard's index is built out-of-core from its row span of the fbin
    file (neighbors.ooc two-pass pipeline, ids file-absolute; the file must
    be reachable from every process in multi-controller runs), then shard
    state is placed across the mesh for SPMD search. ``scan_mode="lut"``
    keeps only packed codes resident — the DEEP-100M/8 shape."""
    from raft_tpu.neighbors import ivf_pq, ooc

    params = params or ivf_pq.IndexParams()
    return _build_sharded_from_file(
        comms, path, params, ooc.build_ivf_pq_from_file,
        functools.partial(_assemble_sharded_ivf_pq, scan_mode=scan_mode,
                          scan_cache_dtype=scan_cache_dtype),
        res, batch_rows, dtype, max_train_rows)


@tracing.range("sharded.build_ivf_pq_from_file_pod")
def build_ivf_pq_from_file_pod(
    comms: Comms,
    path: str,
    params=None,
    res: Optional[Resources] = None,
    batch_rows: int = 1 << 18,
    dtype=None,
    max_train_rows: Optional[int] = None,
    scan_mode: str = "lut",
    scan_cache_dtype=jnp.bfloat16,
    balance_threshold: Optional[float] = 0.25,
) -> ShardedIvfPq:
    """Pod-scale streamed IVF-PQ build (the DEEP-100M path): ONE mesh-wide
    balanced k-means trains the shared coarse centers (``kmeans_fit``'s
    psum pattern scaled past one chip), PQ rotation + codebooks train once
    on the pooled sample, then every shard streams its row span through
    the shared quantizer — the sharded PQ encode.

    Unlike :func:`build_ivf_pq_from_file` (each shard trains its OWN
    quantizer over its span), all shards agree on the coarse partition, so
    ``n_lists`` is bounded by the trainset size, not rows-per-shard, and
    probe routing is consistent across the mesh — the shape the chunked
    ground-truth oracle in tools/deep100m_dryrun.py verifies recall
    against. Training memory is one pooled sample (≤ ``max_train_rows``
    rows); encode memory is one shard's packed codes + a batch."""
    from raft_tpu import native
    from raft_tpu.neighbors import ivf_pq, ooc

    res = ensure_resources(res)
    params = params or ivf_pq.IndexParams()
    n, _ = native.read_bin_header(path)
    size = comms.size
    bounds = shard_bounds(size, n)
    n_train = max(int(n * params.kmeans_trainset_fraction), params.n_lists)
    if max_train_rows is not None:
        n_train = min(n_train, int(max_train_rows))
    if params.n_lists > n_train:
        raise ValueError(f"n_lists={params.n_lists} > trainset rows "
                         f"{n_train}; raise max_train_rows or "
                         f"kmeans_trainset_fraction")
    # per-shard strided samples pooled into one mesh-wide trainset
    per = cdiv(n_train, size)
    trainset = np.concatenate([
        ooc.sample_rows_from_file(
            path, per, seed=r, dtype=dtype, batch_rows=batch_rows,
            row_range=(int(bounds[r]), int(bounds[r + 1])))
        for r in range(size)], axis=0).astype(np.float32)
    centers, _ = kmeans_fit(comms, trainset, params.n_lists,
                            n_iters=params.kmeans_n_iters, res=res,
                            balance_threshold=balance_threshold)
    train_params = dataclasses.replace(params, kmeans_trainset_fraction=1.0,
                                       add_data_on_build=False)
    trained = ivf_pq.build(trainset, train_params, res=res,
                           coarse_centers=np.asarray(centers))
    del trainset

    def one(r, shard_res):
        lo, hi = int(bounds[r]), int(bounds[r + 1])
        idx = ooc.build_ivf_pq_from_file(
            path, params, res=shard_res, batch_rows=batch_rows, dtype=dtype,
            row_range=(lo, hi), trained_index=trained)
        # ids are file-absolute already, overflow ids included
        return idx, np.asarray(idx.list_indices), np.asarray(
            idx.overflow_indices)

    subs = _map_shards(comms, one, res, spans=np.diff(bounds))
    out = _assemble_sharded_ivf_pq(comms, subs, params, n,
                                   scan_mode=scan_mode,
                                   scan_cache_dtype=scan_cache_dtype)
    out.bounds = bounds
    return out


def _assemble_sharded_ivf_pq(comms: Comms, subs, params, n: int,
                             scan_mode: str = "cache",
                             scan_cache_dtype=jnp.bfloat16) -> ShardedIvfPq:
    """Place per-shard ``{r: (Index, global_ids)}`` as mesh-sharded [S, ...]
    state (ragged list pads equalized per field; no one-host staging).
    ``scan_mode`` picks the resident engine: decoded cache or packed
    codes + codebooks."""
    from raft_tpu.neighbors import ivf_pq

    if scan_mode not in ("cache", "lut"):
        raise ValueError(f"unknown scan_mode: {scan_mode!r}")
    first = next(iter(subs.values()))[0]
    common = dict(
        centers=_stack_sharded(comms, {r: np.asarray(i.centers)
                                       for r, (i, _, _) in subs.items()}),
        rotation=_stack_sharded(comms, {r: np.asarray(i.rotation)
                                        for r, (i, _, _) in subs.items()}),
        list_indices=_stack_sharded(comms, {r: g for r, (_, g, _)
                                            in subs.items()}, fill=-1),
        list_sizes=_stack_sharded(comms, {r: np.asarray(i.list_sizes)
                                          for r, (i, _, _) in subs.items()}),
    )
    if _global_any(comms, any(len(go) for _, _, go in subs.values())):
        for idx, _, _ in subs.values():
            ivf_pq.ensure_overflow_decoded(idx, scan_cache_dtype)
        # all-shard equalized decode dtype; a shard with no spill holds a
        # [0, rot] block and pads to the global max with zeros/-1
        common.update(
            overflow_decoded=_stack_sharded(
                comms, {r: np.asarray(
                    i.overflow_decoded if i.overflow_decoded is not None
                    else np.zeros((0, i.rot_dim),
                                  dtype=jnp.dtype(scan_cache_dtype)))
                    for r, (i, _, _) in subs.items()}),
            overflow_norms=_stack_sharded(
                comms, {r: np.asarray(
                    i.overflow_norms if i.overflow_norms is not None
                    else np.zeros((0,), np.float32))
                    for r, (i, _, _) in subs.items()}),
            overflow_indices=_stack_sharded(
                comms, {r: go for r, (_, _, go) in subs.items()},
                fill=-1))
    if scan_mode == "cache":
        for idx, _, _ in subs.values():
            ivf_pq.ensure_scan_cache(idx, scan_cache_dtype)
        return ShardedIvfPq(
            comms, **common, metric=params.metric, n_rows=n,
            list_decoded=_stack_sharded(
                comms, {r: np.asarray(i.list_decoded)
                        for r, (i, _, _) in subs.items()}),
            decoded_norms=_stack_sharded(
                comms, {r: np.asarray(i.decoded_norms)
                        for r, (i, _, _) in subs.items()}))
    return ShardedIvfPq(
        comms, **common, metric=params.metric, n_rows=n,
        codebooks=_stack_sharded(comms, {r: np.asarray(i.codebooks)
                                         for r, (i, _, _) in subs.items()}),
        list_codes=_stack_sharded(comms, {r: np.asarray(i.list_codes)
                                          for r, (i, _, _) in subs.items()}),
        per_cluster=(first.params.codebook_kind
                     == ivf_pq.CodebookGen.PER_CLUSTER),
        pq_dim=first.pq_dim, pq_bits=first.pq_bits)


def _resolve_pq_scan_mode(params, list_decoded, list_codes) -> str:
    """Scan-engine resolution shared by the mesh and elastic searches —
    "auto" follows the engine the index was built with."""
    if params.scan_mode not in ("auto", "cache", "lut"):
        raise ValueError(f"unknown scan_mode: {params.scan_mode!r}")
    mode = params.scan_mode
    if mode == "auto":
        mode = "cache" if list_decoded is not None else "lut"
    if mode == "cache" and list_decoded is None:
        raise ValueError(
            'index holds no decoded cache (built scan_mode="lut"); '
            'search with scan_mode="lut"/"auto" or rebuild')
    if mode == "lut" and list_codes is None:
        raise ValueError(
            'index holds no packed codes (built scan_mode="cache"); '
            'search with scan_mode="cache"/"auto" or rebuild')
    return mode


def _pq_tiles(mode: str, n_probes: int, res: Resources, list_decoded,
              list_codes, pq_dim: int, pq_bits: int,
              lut_itemsize: int = 4, dist_itemsize: int = 4
              ) -> Tuple[int, int]:
    """Workspace-bounded (q_tile, probe_tile), shared by the mesh and
    elastic searches so single-chip serving tiles can't desync from mesh
    tiles. Shapes are [..., pad, last] with any number of leading axes.
    The cache engine scans all probes in one pass (probe_tile =
    n_probes); the LUT engine's tiles come from the true-peak accounting
    (ivf_pq.plan_lut_tiles), engaging its probe loop when the budget
    demands it."""
    from raft_tpu.neighbors import ivf_pq

    if mode == "cache":
        list_pad = list_decoded.shape[-2]
        rot = list_decoded.shape[-1]
        per_q = n_probes * list_pad * (rot * 2 + 12)
        q_tile = int(np.clip(res.workspace_limit_bytes // max(per_q, 1),
                             1, 1024))
        if q_tile >= 8:
            q_tile -= q_tile % 8
        return q_tile, n_probes
    return ivf_pq.plan_lut_tiles(
        n_probes, list_codes.shape[-2], pq_dim, pq_bits,
        res.workspace_limit_bytes, lut_itemsize, dist_itemsize)


@tracing.range("sharded.search_ivf_pq")
def search_ivf_pq(
    index: ShardedIvfPq,
    queries,
    k: int,
    params=None,
    res: Optional[Resources] = None,
    merge_mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """SPMD IVF-PQ search: per-device ADC scan of its shard's probed lists
    (cache or LUT engine, per ``params.scan_mode`` — "auto" follows the
    engine the index was built with), then the planned cross-chip top-k
    merge over ICI (``merge_mode``, docs/sharding.md)."""
    from raft_tpu.neighbors import ivf_pq

    _SHARDED_SEARCHES.labels("ivf_pq").inc()
    res = ensure_resources(res)
    params = params or ivf_pq.SearchParams()
    comms = index.comms
    queries = jnp.asarray(queries)
    minimize = index.metric != DistanceType.InnerProduct
    n_lists = index.centers.shape[1]
    n_probes = int(min(params.n_probes, n_lists))
    select_recall = float(getattr(params, "select_recall", 1.0))
    mode = _resolve_pq_scan_mode(params, index.list_decoded,
                                 index.list_codes)
    empty_filter = jnp.zeros((0,), jnp.uint32)
    ax = comms.axis

    has_overflow = index.overflow_decoded is not None
    over_ops = ((index.overflow_decoded, index.overflow_norms,
                 index.overflow_indices) if has_overflow else ())
    over_specs = ((P(ax, None, None), P(ax, None), P(ax, None))
                  if has_overflow else ())

    def unpack_over(args):
        # [1, O, ...] shard_map blocks → per-device overflow kwargs
        if not has_overflow:
            return {}
        od, on, oi = args
        return dict(overflow_decoded=od[0], overflow_norms=on[0],
                    overflow_indices=oi[0], has_overflow=True)

    q = comms.shard(queries, P(None, None))

    if mode == "cache":
        q_tile, _ = _pq_tiles("cache", n_probes, res, index.list_decoded,
                              index.list_codes, index.pq_dim, index.pq_bits)

        def local_scan(q_rep, c, ro, ld, dn, li, ls, *over):
            return ivf_pq.search_cache_core(
                q_rep, c[0], ro[0], ld[0], dn[0], li[0], ls[0], empty_filter,
                index.metric, int(k), n_probes, q_tile, False,
                select_recall=select_recall, **unpack_over(over))

        in_specs = (P(None, None), P(ax, None, None), P(ax, None, None),
                    P(ax, None, None, None), P(ax, None, None),
                    P(ax, None, None), P(ax, None)) + over_specs
        args = (q, index.centers, index.rotation, index.list_decoded,
                index.decoded_norms, index.list_indices, index.list_sizes,
                *over_ops)
    else:
        # LUT engine: packed codes only (the DEEP-100M/8 memory-lean shape)
        q_tile, probe_tile = _pq_tiles(
            "lut", n_probes, res, index.list_decoded, index.list_codes,
            index.pq_dim, index.pq_bits,
            jnp.dtype(params.lut_dtype).itemsize,
            jnp.dtype(params.internal_distance_dtype).itemsize)
        lut_dtype = jnp.dtype(params.lut_dtype).name
        dist_dtype = jnp.dtype(params.internal_distance_dtype).name

        def local_scan(q_rep, c, ro, cb, lc, li, ls, *over):
            return ivf_pq.search_lut_core(
                q_rep, c[0], ro[0], cb[0], lc[0], li[0], ls[0], empty_filter,
                index.metric, int(k), n_probes, q_tile, index.per_cluster,
                index.pq_dim, index.pq_bits, False, lut_dtype, dist_dtype,
                select_recall=select_recall, probe_tile=probe_tile,
                **unpack_over(over))

        in_specs = (P(None, None), P(ax, None, None), P(ax, None, None),
                    P(ax, None, None, None), P(ax, None, None, None),
                    P(ax, None, None), P(ax, None)) + over_specs
        args = (q, index.centers, index.rotation, index.codebooks,
                index.list_codes, index.list_indices, index.list_sizes,
                *over_ops)

    sink = _span_sink()
    if sink is not None:
        return _instrumented_search(comms, local_scan, in_specs, args,
                                    "ivf_pq", queries.shape[0], int(k),
                                    minimize, sink)

    tiles = {"q_tile": int(q_tile)}
    if mode == "lut":
        tiles["probe_tile"] = int(probe_tile)
    plan = plan_sharded_search(
        comms, "ivf_pq", index.n_rows,
        getattr(index, "bounds", None), queries.shape[0], int(k), int(k),
        mode, merge_mode=merge_mode, mask_invalid=True, tiles=tiles)
    _record_plan(plan, merge_mode, {"n_probes": n_probes})

    fn = comms.run(lambda *a: _plan_merge(comms, plan, *local_scan(*a),
                                          minimize),
                   in_specs, (P(None, None), P(None, None)))
    return jax.jit(fn)(*args)


@tracing.range("sharded.search_ivf_flat")
def search_ivf_flat(
    index: ShardedIvfFlat,
    queries,
    k: int,
    params=None,
    res: Optional[Resources] = None,
    merge_mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """SPMD search: every device scans its local shard's probed lists
    (reusing the single-chip search core inside shard_map), then the
    planned cross-chip top-k merge over ICI (``merge_mode``,
    docs/sharding.md)."""
    from raft_tpu.neighbors import ivf_flat

    _SHARDED_SEARCHES.labels("ivf_flat").inc()
    res = ensure_resources(res)
    params = params or ivf_flat.SearchParams()
    comms = index.comms
    queries = jnp.asarray(queries)
    minimize = index.metric != DistanceType.InnerProduct
    n_lists = index.centers.shape[1]
    n_probes = int(min(params.n_probes, n_lists))
    list_pad = index.list_data.shape[2]
    per_q = n_probes * list_pad * queries.shape[1] * 4 * 2
    q_tile = int(np.clip(res.workspace_limit_bytes // max(per_q, 1), 1, 1024))
    if q_tile >= 8:
        q_tile -= q_tile % 8
    empty_filter = jnp.zeros((0,), jnp.uint32)
    fast_scan = getattr(params, "scan_dtype", None) is not None
    select_recall = float(getattr(params, "select_recall", 1.0))
    refine_mult = refine_multiplier(
        getattr(params, "refine_ratio", 4.0), fast_scan)
    if fast_scan:
        if jnp.dtype(params.scan_dtype) != jnp.bfloat16:
            raise ValueError(
                f"scan_dtype={params.scan_dtype!r}: only bfloat16 is "
                "supported")
        if index.list_data.dtype != jnp.float32:
            raise ValueError("scan_dtype requires fp32 list data")

    has_overflow = index.overflow_data is not None
    ax = comms.axis
    q = comms.shard(queries, P(None, None))
    if has_overflow:
        # each device scans its own spill block alongside its probed lists
        def local_scan(q_rep, c, ld, li, ls, od, oi):
            return ivf_flat.search_core(
                q_rep, c[0], ld[0], li[0], ls[0], empty_filter, index.metric,
                int(k), n_probes, q_tile, False, fast_scan=fast_scan,
                overflow_data=od[0], overflow_indices=oi[0],
                has_overflow=True, select_recall=select_recall,
                refine_mult=refine_mult)

        in_specs = (P(None, None), P(ax, None, None),
                    P(ax, None, None, None), P(ax, None, None), P(ax, None),
                    P(ax, None, None), P(ax, None))
        args = (q, index.centers, index.list_data, index.list_indices,
                index.list_sizes, index.overflow_data,
                index.overflow_indices)
    else:
        def local_scan(q_rep, c, ld, li, ls):
            return ivf_flat.search_core(
                q_rep, c[0], ld[0], li[0], ls[0], empty_filter, index.metric,
                int(k), n_probes, q_tile, False, fast_scan=fast_scan,
                select_recall=select_recall, refine_mult=refine_mult)

        in_specs = (P(None, None), P(ax, None, None),
                    P(ax, None, None, None), P(ax, None, None), P(ax, None))
        args = (q, index.centers, index.list_data, index.list_indices,
                index.list_sizes)

    sink = _span_sink()
    if sink is not None:
        return _instrumented_search(comms, local_scan, in_specs, args,
                                    "ivf_flat", queries.shape[0], int(k),
                                    minimize, sink)

    plan = plan_sharded_search(
        comms, "ivf_flat", index.n_rows,
        getattr(index, "bounds", None), queries.shape[0], int(k), int(k),
        "xla", merge_mode=merge_mode, mask_invalid=True,
        tiles={"q_tile": int(q_tile)})
    _record_plan(plan, merge_mode, {"n_probes": n_probes})

    fn = comms.run(lambda *a: _plan_merge(comms, plan, *local_scan(*a),
                                          minimize),
                   in_specs, (P(None, None), P(None, None)))
    return jax.jit(fn)(*args)


# ------------------------------------------------------------- persistence
#
# Checkpoint/resume for sharded indexes (the raft-dask role of per-worker
# local serialization): ONE file per shard rank (``prefix.rank<r>``), each
# written atomically by the controller process that addresses that shard,
# plus a per-prefix manifest naming every rank file with its whole-file
# digest. Deserialization collects whichever rank files carry the shards
# this process can address — a multi-hour from-file build no longer has to
# be rebuilt to be searched again. Older checkpoints (one multi-rank file
# per process) still load: readers key on the rank ids recorded *inside*
# each file, not on filenames.
#
# Fault model (docs/robustness.md): per-record crc + footer
# (core.serialize v2 framing) classifies a bad file as truncated vs
# corrupt; the manifest names files that are missing outright; and
# ``deserialize_*_elastic(..., allow_partial=True)`` restores around any
# of the three, reporting ``coverage`` instead of refusing the whole
# checkpoint.

_SHARD_SERIAL_VERSION = 1
_MANIFEST_VERSION = 1


class SearchResult(tuple):
    """(distances, indices) that still unpacks as a 2-tuple but carries
    ``coverage`` — the fraction of indexed rows actually searched (1.0 for
    a full index; < 1 after a degraded-mode restore) — so serving callers
    can decide whether degraded recall is acceptable per response."""

    def __new__(cls, distances, indices, coverage: float = 1.0):
        self = super().__new__(cls, (distances, indices))
        self.coverage = float(coverage)
        return self

    @property
    def distances(self):
        return self[0]

    @property
    def indices(self):
        return self[1]


def _local_shard_blocks(arr) -> dict:
    """{global shard rank r: np block} for this process's addressable
    shards of a ``P(axis, None, ...)``-sharded ``[S, ...]`` array."""
    out = {}
    for s in arr.addressable_shards:
        r = s.index[0].start or 0
        out[r] = np.asarray(s.data)[0]
    return out


def _write_field(w, block: np.ndarray) -> None:
    """bf16 has no stable .npy representation — store a uint16 view with
    a dtype flag."""
    is_bf16 = block.dtype == jnp.bfloat16
    w.scalar(1 if is_bf16 else 0, "<i4")
    w.array(block.view(np.uint16) if is_bf16 else block)


def _read_field(r) -> np.ndarray:
    is_bf16 = bool(r.scalar())
    a = r.array()
    return a.view(jnp.bfloat16) if is_bf16 else a


def _serialize_sharded(prefix: str, kind: str, scalars, fields) -> None:
    """``scalars``: [(value, dtype)], ``fields``: [arr or None] — every
    process writes one ATOMIC file per addressable shard rank
    (``prefix.rank<r>``) plus a manifest naming each file and its digest,
    so a single lost/corrupted file costs one shard, not the checkpoint."""
    import json

    from raft_tpu.core import serialize as ser

    present = [a is not None for a in fields]
    blocks = [(_local_shard_blocks(a) if p else None)
              for a, p in zip(fields, present)]
    local_ranks = sorted(next(b for b, p in zip(blocks, present) if p))
    size = int(next(a for a, p in zip(fields, present) if p).shape[0])
    entries = {}
    for r in local_ranks:
        path = f"{prefix}.rank{r}"
        with ser.writer_for(path) as stream:
            w = ser.IndexWriter(stream, kind, _SHARD_SERIAL_VERSION)
            for value, dtype in scalars:
                w.scalar(value, dtype)
            w.scalar(len(present), "<i4")
            for p in present:
                w.scalar(1 if p else 0, "<i4")
            w.scalar(1, "<i4")  # ranks in this file
            w.scalar(r, "<i4")
            for b, p in zip(blocks, present):
                if p:
                    _write_field(w, b[r])
            w.finish()
        entries[os.path.basename(path)] = {
            "ranks": [r],
            "bytes": os.path.getsize(path),
            "crc32": ser.file_crc32(path),
        }
    manifest = {
        "manifest_version": _MANIFEST_VERSION,
        "kind": kind,
        "size": size,
        "files": entries,
    }
    mpath = (f"{prefix}.manifest" if jax.process_count() == 1
             else f"{prefix}.manifest.p{jax.process_index()}")
    with ser.writer_for(mpath) as stream:
        stream.write(json.dumps(manifest, indent=1, sort_keys=True).encode())


def load_manifest(prefix: str) -> Optional[dict]:
    """Merged manifest for a checkpoint prefix (``prefix.manifest`` plus
    any multi-controller ``prefix.manifest.p<i>`` fragments), or None for
    pre-manifest checkpoints."""
    import glob as _glob
    import json

    paths = sorted(_glob.glob(_glob.escape(prefix) + ".manifest*"))
    merged: Optional[dict] = None
    for path in paths:
        if path.endswith((".tmp", )) or ".tmp." in path:
            continue
        with open(path, "rb") as f:
            m = json.load(f)
        if merged is None:
            merged = m
        else:
            if (m.get("kind") != merged.get("kind")
                    or m.get("size") != merged.get("size")):
                raise ValueError(
                    f"{path}: manifest fragment disagrees with others "
                    f"(kind/size) — stale fragments from a previous run?")
            merged["files"].update(m["files"])
    return merged


def verify_checkpoint(prefix: str) -> dict:
    """Pre-flight checkpoint validation against the manifest (TPU runbook:
    run this BEFORE burning a hardware window on a restore). Classifies
    every rank file as ``ok`` / ``missing`` / ``truncated`` / ``corrupt``
    and lists shard ranks with no healthy file. Returns
    ``{"ok": bool, "size": S, "files": {name: status}, "missing_ranks":
    [...], "coverage_ranks": [...]}``; raises FileNotFoundError when there
    is no manifest to verify against."""
    from raft_tpu.core import serialize as ser

    manifest = load_manifest(prefix)
    if manifest is None:
        raise FileNotFoundError(
            f"{prefix}.manifest not found — pre-manifest checkpoint; "
            f"re-serialize to get one, or restore with allow_partial "
            f"validation only")
    dirname = os.path.dirname(prefix) or "."
    statuses = {}
    healthy_ranks: set = set()
    for name, entry in sorted(manifest["files"].items()):
        path = os.path.join(dirname, name)
        if not os.path.exists(path):
            statuses[name] = "missing"
            continue
        nbytes = os.path.getsize(path)
        if nbytes < entry["bytes"]:
            statuses[name] = "truncated"
            continue
        if nbytes != entry["bytes"] or ser.file_crc32(path) != entry["crc32"]:
            statuses[name] = "corrupt"
            continue
        statuses[name] = "ok"
        healthy_ranks.update(entry["ranks"])
    size = int(manifest["size"])
    missing_ranks = sorted(set(range(size)) - healthy_ranks)
    for s in statuses.values():
        _CKPT_FILES.labels(s).inc()
    ok = not missing_ranks and all(s == "ok" for s in statuses.values())
    _CKPT_VERIFY.labels("ok" if ok else "unhealthy").inc()
    return {
        "ok": ok,
        "kind": manifest["kind"],
        "size": size,
        "files": statuses,
        "missing_ranks": missing_ranks,
        "coverage_ranks": sorted(healthy_ranks),
    }


def _addressable_ranks(comms: Comms) -> set:
    """Shard ranks whose devices this process can address."""
    me = jax.process_index()
    return {r for r in range(comms.size)
            if _shard_device(comms, r).process_index == me}


def _read_rank_file(path: str, kind: str, n_scalars: int, want_ranks):
    """Parse one rank file → (scalars, present, {rank: [field blocks]}).
    Blocks for ranks outside ``want_ranks`` are read and dropped (bounding
    host RAM at roughly one rank file). Raises IntegrityError (truncated/
    corrupt) or ValueError; never partially merges into shared state."""
    from raft_tpu.core import serialize as ser

    with open(path, "rb") as stream:
        r = ser.IndexReader(stream, kind, _SHARD_SERIAL_VERSION, name=path)
        s = [r.scalar() for _ in range(n_scalars)]
        n_fields = r.scalar()
        present = [bool(r.scalar()) for _ in range(n_fields)]
        n_local = r.scalar()
        local: dict = {}
        for _ in range(n_local):
            rank = int(r.scalar())
            keep = want_ranks is None or rank in want_ranks
            blocks = []
            for p in present:
                if p:
                    block = _read_field(r)
                    blocks.append(block if keep else None)
            local[rank] = blocks if keep else None
        r.finish()
    return s, present, local


def _deserialize_sharded(prefix: str, kind: str, n_scalars: int,
                         want_ranks=None, on_error: str = "raise"):
    """Read every ``prefix.rank*`` file; returns (scalars, parts, seen,
    errors) where ``parts`` is a list of {r: np block} per field (None =
    absent field) and ``errors`` maps path -> exception for files skipped
    under ``on_error="skip"``.

    Only ranks in ``want_ranks`` are RETAINED (non-addressable shards are
    read file-at-a-time and dropped), but EVERY rank seen is validated: a
    rank appearing twice means stale rank files from a previous run with a
    different process layout are mixed in — that raises even in skip mode,
    because silently picking one copy could resurrect outdated data.

    ``on_error="skip"`` is the degraded-mode path: a file that is
    truncated, corrupt, or unreadable contributes nothing (its ranks stay
    missing) instead of failing the restore — each file's blocks merge
    only after the whole file (footer included) validated."""
    import glob as _glob

    from raft_tpu.core.errors import IntegrityError

    if on_error not in ("raise", "skip"):
        raise ValueError(f"on_error={on_error!r}: use 'raise' or 'skip'")
    paths = sorted(p for p in _glob.glob(_glob.escape(prefix) + ".rank*")
                   if ".tmp." not in p)
    if not paths:
        raise FileNotFoundError(f"no shard files match {prefix}.rank*")
    scalars = None
    parts = None
    seen: dict = {}  # rank -> path
    errors: dict = {}  # path -> exception
    for path in paths:
        try:
            s, present, local = _read_rank_file(
                path, kind, n_scalars, want_ranks)
        except (IntegrityError, ValueError, OSError) as e:
            if on_error == "raise":
                raise
            errors[path] = e
            continue
        if scalars is None:
            scalars = s
            parts = [({} if p else None) for p in present]
        elif s != scalars:
            e = ValueError(f"{path}: header disagrees with other rank files")
            if on_error == "raise":
                raise e
            errors[path] = e
            continue
        for rank, blocks in local.items():
            if rank in seen:
                raise ValueError(
                    f"shard rank {rank} appears in both {seen[rank]} "
                    f"and {path} — stale rank files from a previous "
                    f"run? Remove outdated {prefix}.rank* files")
            seen[rank] = path
            if blocks is None:
                continue
            it = iter(blocks)
            for f, p in zip(parts, present):
                if p:
                    f[rank] = next(it)
    if scalars is None:
        raise IntegrityError(
            f"no readable rank file under {prefix}.rank*: "
            + "; ".join(f"{p}: {e}" for p, e in errors.items()),
            path=prefix, reason="corrupt")
    return scalars, parts, seen, errors


def _expected_rank_paths(prefix: str, ranks, manifest=None) -> list:
    """Best-effort file paths for missing shard ranks: exact names from the
    manifest when one exists, else the writer's ``prefix.rank<r>``
    convention."""
    if manifest:
        dirname = os.path.dirname(prefix) or "."
        named = {}
        for name, entry in manifest.get("files", {}).items():
            for r in entry.get("ranks", ()):
                named[r] = os.path.join(dirname, name)
        return [named.get(r, f"{prefix}.rank{r}") for r in ranks]
    return [f"{prefix}.rank{r}" for r in ranks]


def _check_rank_coverage(seen: dict, size: int, prefix: str,
                         errors=None) -> None:
    missing = sorted(set(range(size)) - set(seen))
    if missing:
        try:
            manifest = load_manifest(prefix)
        except (OSError, ValueError):
            manifest = None
        paths = _expected_rank_paths(prefix, missing, manifest)
        detail = ""
        if errors:
            detail = "; unreadable: " + "; ".join(
                f"{p} ({e})" for p, e in sorted(errors.items()))
        raise ValueError(
            f"{prefix}.rank* files cover only {sorted(seen)} of "
            f"{size} shard ranks; missing {missing} (expected files: "
            f"{', '.join(paths)}){detail} — partial checkpoint? Pass "
            f"allow_partial=True to an elastic restore to serve the "
            f"surviving shards")


def serialize_ivf_pq(index: ShardedIvfPq, prefix: str) -> None:
    """Persist a sharded IVF-PQ index (either engine) as rank files."""
    engine = 1 if index.list_codes is not None else 0
    scalars = [
        (int(index.metric), "<i4"), (index.n_rows, "<i8"),
        (index.comms.size, "<i4"), (index.pq_dim, "<i4"),
        (index.pq_bits, "<i4"), (1 if index.per_cluster else 0, "<i4"),
        (engine, "<i4"),
    ]
    fields = [index.centers, index.rotation, index.list_indices,
              index.list_sizes, index.list_decoded, index.decoded_norms,
              index.codebooks, index.list_codes, index.overflow_decoded,
              index.overflow_norms, index.overflow_indices]
    _serialize_sharded(prefix, "sharded_ivf_pq", scalars, fields)


def deserialize_ivf_pq(prefix: str, comms: Comms) -> ShardedIvfPq:
    scalars, parts, seen, _ = _deserialize_sharded(
        prefix, "sharded_ivf_pq", 7, want_ranks=_addressable_ranks(comms))
    metric, n_rows, size, pq_dim, pq_bits, per_cluster, _engine = scalars
    if size != comms.size:
        raise ValueError(
            f"index was sharded over {size} devices, comms has {comms.size}")
    _check_rank_coverage(seen, int(size), prefix)
    _CKPT_RESTORES.labels("ivf_pq", "strict").inc()
    arrs = [(_stack_sharded(comms, p) if p is not None else None)
            for p in parts]
    (centers, rotation, list_indices, list_sizes, list_decoded,
     decoded_norms, codebooks, list_codes, overflow_decoded,
     overflow_norms, overflow_indices) = arrs
    return ShardedIvfPq(
        comms, centers, rotation, list_indices, list_sizes,
        DistanceType(metric), int(n_rows), list_decoded=list_decoded,
        decoded_norms=decoded_norms, codebooks=codebooks,
        list_codes=list_codes, per_cluster=bool(per_cluster),
        pq_dim=int(pq_dim), pq_bits=int(pq_bits),
        overflow_decoded=overflow_decoded, overflow_norms=overflow_norms,
        overflow_indices=overflow_indices)


# -------------------------------------------------------- elastic restore
#
# A sharded checkpoint normally restores only onto a mesh of the SAME size
# it was built on (deserialize_ivf_pq raises otherwise). Elastic restore
# lifts that: the shard blocks are stacked [S, ...] as plain arrays on the
# default device and searched by running the per-shard core sequentially
# (lax.map) inside one jitted program, then merging with one select_k —
# numerically identical to the mesh search (same cores, same merge). This
# is the single-chip serving story for a multi-shard build: an 8-virtual-
# device CPU-built DEEP-scale index searches on the one real TPU without a
# rebuild. (The reference's raft-dask analog requires re-creating the
# cluster at the original worker count — raft_dask/common/comms.py;
# per-worker local models in cuML's kNN.)


@functools.partial(jax.jit, static_argnames=(
    "metric", "k", "n_probes", "q_tile", "probe_tile", "per_cluster",
    "pq_dim", "pq_bits", "lut_dtype", "dist_dtype", "select_recall",
    "has_overflow"))
def _elastic_lut_search(queries, centers, rotation, codebooks, list_codes,
                        list_indices, list_sizes, overflow_decoded,
                        overflow_norms, overflow_indices, *, metric, k,
                        n_probes, q_tile, probe_tile, per_cluster, pq_dim,
                        pq_bits, lut_dtype, dist_dtype, select_recall,
                        has_overflow):
    from raft_tpu.neighbors import ivf_pq

    empty_filter = jnp.zeros((0,), jnp.uint32)
    minimize = metric != DistanceType.InnerProduct

    def per_shard(blocks):
        c, ro, cb, lc, li, ls, od, on, oi = blocks
        kw = (dict(overflow_decoded=od, overflow_norms=on,
                   overflow_indices=oi, has_overflow=True)
              if has_overflow else {})
        return ivf_pq.search_lut_core(
            queries, c, ro, cb, lc, li, ls, empty_filter, metric, k,
            n_probes, q_tile, per_cluster, pq_dim, pq_bits, False,
            lut_dtype, dist_dtype, select_recall=select_recall,
            probe_tile=probe_tile, **kw)

    v, i = jax.lax.map(per_shard, (centers, rotation, codebooks, list_codes,
                                   list_indices, list_sizes,
                                   overflow_decoded, overflow_norms,
                                   overflow_indices))
    return _elastic_merge(v, i, queries.shape[0], k, minimize)


@functools.partial(jax.jit, static_argnames=(
    "metric", "k", "n_probes", "q_tile", "select_recall", "has_overflow"))
def _elastic_cache_search(queries, centers, rotation, list_decoded,
                          decoded_norms, list_indices, list_sizes,
                          overflow_decoded, overflow_norms, overflow_indices,
                          *, metric, k, n_probes, q_tile, select_recall,
                          has_overflow):
    from raft_tpu.neighbors import ivf_pq

    empty_filter = jnp.zeros((0,), jnp.uint32)
    minimize = metric != DistanceType.InnerProduct

    def per_shard(blocks):
        c, ro, ld, dn, li, ls, od, on, oi = blocks
        kw = (dict(overflow_decoded=od, overflow_norms=on,
                   overflow_indices=oi, has_overflow=True)
              if has_overflow else {})
        return ivf_pq.search_cache_core(
            queries, c, ro, ld, dn, li, ls, empty_filter, metric, k,
            n_probes, q_tile, False, select_recall=select_recall, **kw)

    v, i = jax.lax.map(per_shard, (centers, rotation, list_decoded,
                                   decoded_norms, list_indices, list_sizes,
                                   overflow_decoded, overflow_norms,
                                   overflow_indices))
    return _elastic_merge(v, i, queries.shape[0], k, minimize)


def _elastic_merge(v, i, nq: int, k: int, minimize: bool):
    """[S, nq, k] per-shard candidates → [nq, k] global top-k (the
    knn_merge_parts-across-ranks step, without the all_gather — everything
    already lives on one device)."""
    v = jnp.swapaxes(v, 0, 1).reshape(nq, -1)
    i = jnp.swapaxes(i, 0, 1).reshape(nq, -1)
    v = jnp.where(i < 0, jnp.inf if minimize else -jnp.inf, v)
    vm, sel = select_k(v, k, select_min=minimize)
    return vm, jnp.take_along_axis(i, sel, axis=1)


class ElasticIvfPq:
    """A sharded IVF-PQ checkpoint restored WITHOUT the original mesh —
    shard blocks live stacked [S, ...] on the default device; ``search``
    matches ``sharded.search_ivf_pq`` exactly (same per-shard cores, same
    merge). Under a degraded restore (``allow_partial=True``) S counts
    only the SURVIVING shards and ``coverage`` < 1.0 reports the fraction
    of indexed rows still searchable; results carry it (see
    :class:`SearchResult`)."""

    def __init__(self, n_shards, centers, rotation, list_indices,
                 list_sizes, metric, n_rows, list_decoded=None,
                 decoded_norms=None, codebooks=None, list_codes=None,
                 per_cluster=False, pq_dim=0, pq_bits=8,
                 overflow_decoded=None, overflow_norms=None,
                 overflow_indices=None, coverage: float = 1.0,
                 shard_ranks=None):
        self.n_shards = int(n_shards)
        self.centers = centers  # [S, nlist, dim]
        self.rotation = rotation  # [S, rot, dim]
        self.list_indices = list_indices  # [S, nlist, pad] global ids
        self.list_sizes = list_sizes  # [S, nlist]
        self.metric = metric
        self.n_rows = int(n_rows)
        self.list_decoded = list_decoded
        self.decoded_norms = decoded_norms
        self.codebooks = codebooks
        self.list_codes = list_codes
        self.per_cluster = bool(per_cluster)
        self.pq_dim = int(pq_dim)
        self.pq_bits = int(pq_bits)
        self.overflow_decoded = overflow_decoded
        self.overflow_norms = overflow_norms
        self.overflow_indices = overflow_indices
        self.coverage = float(coverage)
        # original shard-rank ids behind each stacked row (None = all of
        # range(n_shards), i.e. a full restore)
        self.shard_ranks = (None if shard_ranks is None
                            else [int(r) for r in shard_ranks])

    def search(self, queries, k: int, params=None,
               res: Optional[Resources] = None) -> "SearchResult":
        from raft_tpu.neighbors import ivf_pq

        res = ensure_resources(res)
        params = params or ivf_pq.SearchParams()
        queries = jnp.asarray(queries)
        n_lists = self.centers.shape[1]
        n_probes = int(min(params.n_probes, n_lists))
        select_recall = float(getattr(params, "select_recall", 1.0))
        mode = _resolve_pq_scan_mode(params, self.list_decoded,
                                     self.list_codes)
        has_overflow = self.overflow_decoded is not None
        if has_overflow:
            over = (self.overflow_decoded, self.overflow_norms,
                    self.overflow_indices)
        else:
            # stable zero-size placeholders keep the jit signature uniform
            s = self.n_shards
            rot = self.rotation.shape[1]
            over = (jnp.zeros((s, 0, rot), jnp.bfloat16),
                    jnp.zeros((s, 0), jnp.float32),
                    jnp.zeros((s, 0), jnp.int32))

        q_tile, probe_tile = _pq_tiles(
            mode, n_probes, res, self.list_decoded, self.list_codes,
            self.pq_dim, self.pq_bits,
            jnp.dtype(params.lut_dtype).itemsize,
            jnp.dtype(params.internal_distance_dtype).itemsize)
        if mode == "cache":
            v, i = _elastic_cache_search(
                queries, self.centers, self.rotation, self.list_decoded,
                self.decoded_norms, self.list_indices, self.list_sizes,
                *over, metric=self.metric, k=int(k), n_probes=n_probes,
                q_tile=q_tile, select_recall=select_recall,
                has_overflow=has_overflow)
            return SearchResult(v, i, self.coverage)

        v, i = _elastic_lut_search(
            queries, self.centers, self.rotation, self.codebooks,
            self.list_codes, self.list_indices, self.list_sizes, *over,
            metric=self.metric, k=int(k), n_probes=n_probes, q_tile=q_tile,
            probe_tile=probe_tile, per_cluster=self.per_cluster,
            pq_dim=self.pq_dim, pq_bits=self.pq_bits,
            lut_dtype=jnp.dtype(params.lut_dtype).name,
            dist_dtype=jnp.dtype(params.internal_distance_dtype).name,
            select_recall=select_recall, has_overflow=has_overflow)
        return SearchResult(v, i, self.coverage)


def _elastic_restore(prefix: str, kind: str, n_scalars: int,
                     allow_partial: bool):
    """Shared elastic-restore front half: read rank files (strict, or
    best-effort when ``allow_partial``), pick the surviving rank order,
    and return ``(scalars, parts, survivors, size)``."""
    scalars, parts, seen, errors = _deserialize_sharded(
        prefix, kind, n_scalars,
        want_ranks=None, on_error="skip" if allow_partial else "raise")
    size = int(scalars[2])
    if allow_partial:
        survivors = sorted(r for r in seen if r < size)
        if not survivors:
            from raft_tpu.core.errors import IntegrityError
            raise IntegrityError(
                f"{prefix}: no shard rank survived (of {size})",
                path=prefix, reason="missing")
    else:
        _check_rank_coverage(seen, size, prefix, errors)
        survivors = list(range(size))
    return scalars, parts, survivors, size


def _stack_survivors(parts, survivors):
    """Stack each parts dict {rank: np block} over the surviving ranks in
    order (None fields stay None)."""
    return [(None if p is None
             else jnp.asarray(np.stack([p[r] for r in survivors])))
            for p in parts]


def _elastic_coverage(list_indices_parts, overflow_parts, survivors,
                      n_rows) -> float:
    """Fraction of indexed rows actually restorable = valid (>= 0) ids
    across the surviving shards' lists + spill blocks, over ``n_rows``.
    Exact, not estimated — padding slots hold -1."""
    rows = 0
    for r in survivors:
        rows += int((np.asarray(list_indices_parts[r]) >= 0).sum())
        if overflow_parts is not None and r in overflow_parts:
            rows += int((np.asarray(overflow_parts[r]) >= 0).sum())
    return rows / max(int(n_rows), 1)


def deserialize_ivf_pq_elastic(prefix: str,
                               allow_partial: bool = False) -> ElasticIvfPq:
    """Restore a sharded IVF-PQ checkpoint on ANY device count (vs
    ``deserialize_ivf_pq``, which requires the original mesh size). All
    rank files are read and every shard is retained on the default device.

    ``allow_partial=True`` is the degraded serving mode: rank files that
    are missing, truncated, or corrupt are skipped instead of failing the
    restore, and the index serves the surviving shards with
    ``index.coverage = rows_available / n_rows`` (< 1.0); each
    ``search`` result carries that coverage. Strict mode (the default)
    raises — naming the missing file, or the bad file + record."""
    scalars, parts, survivors, size = _elastic_restore(
        prefix, "sharded_ivf_pq", 7, allow_partial)
    metric, n_rows, _size, pq_dim, pq_bits, per_cluster, _engine = scalars
    coverage = (1.0 if len(survivors) == size
                else _elastic_coverage(parts[2], parts[10], survivors,
                                       n_rows))
    _CKPT_RESTORES.labels(
        "ivf_pq", "full" if coverage >= 1.0 else "degraded").inc()
    (centers, rotation, list_indices, list_sizes, list_decoded,
     decoded_norms, codebooks, list_codes, overflow_decoded,
     overflow_norms, overflow_indices) = _stack_survivors(parts, survivors)
    return ElasticIvfPq(
        len(survivors), centers, rotation, list_indices, list_sizes,
        DistanceType(metric), int(n_rows), list_decoded=list_decoded,
        decoded_norms=decoded_norms, codebooks=codebooks,
        list_codes=list_codes, per_cluster=bool(per_cluster),
        pq_dim=int(pq_dim), pq_bits=int(pq_bits),
        overflow_decoded=overflow_decoded, overflow_norms=overflow_norms,
        overflow_indices=overflow_indices, coverage=coverage,
        shard_ranks=survivors)


def serialize_ivf_flat(index: ShardedIvfFlat, prefix: str) -> None:
    """Persist a sharded IVF-Flat index as rank files."""
    scalars = [(int(index.metric), "<i4"), (index.n_rows, "<i8"),
               (index.comms.size, "<i4")]
    fields = [index.centers, index.list_data, index.list_indices,
              index.list_sizes, index.overflow_data, index.overflow_indices]
    _serialize_sharded(prefix, "sharded_ivf_flat", scalars, fields)


def deserialize_ivf_flat(prefix: str, comms: Comms) -> ShardedIvfFlat:
    scalars, parts, seen, _ = _deserialize_sharded(
        prefix, "sharded_ivf_flat", 3, want_ranks=_addressable_ranks(comms))
    metric, n_rows, size = scalars
    if size != comms.size:
        raise ValueError(
            f"index was sharded over {size} devices, comms has {comms.size}")
    _check_rank_coverage(seen, int(size), prefix)
    _CKPT_RESTORES.labels("ivf_flat", "strict").inc()
    arrs = [(_stack_sharded(comms, p) if p is not None else None)
            for p in parts]
    centers, list_data, list_indices, list_sizes, o_data, o_ids = arrs
    return ShardedIvfFlat(comms, centers, list_data, list_indices,
                          list_sizes, DistanceType(metric), int(n_rows),
                          overflow_data=o_data, overflow_indices=o_ids)


@functools.partial(jax.jit, static_argnames=(
    "metric", "k", "n_probes", "q_tile", "select_recall", "fast_scan",
    "refine_mult", "has_overflow"))
def _elastic_flat_search(queries, centers, list_data, list_indices,
                         list_sizes, overflow_data, overflow_indices, *,
                         metric, k, n_probes, q_tile, select_recall,
                         fast_scan, refine_mult, has_overflow):
    from raft_tpu.neighbors import ivf_flat

    empty_filter = jnp.zeros((0,), jnp.uint32)
    minimize = metric != DistanceType.InnerProduct

    def per_shard(blocks):
        c, ld, li, ls, od, oi = blocks
        kw = (dict(overflow_data=od, overflow_indices=oi, has_overflow=True)
              if has_overflow else {})
        return ivf_flat.search_core(
            queries, c, ld, li, ls, empty_filter, metric, k, n_probes,
            q_tile, False, fast_scan=fast_scan, select_recall=select_recall,
            refine_mult=refine_mult, **kw)

    v, i = jax.lax.map(per_shard, (centers, list_data, list_indices,
                                   list_sizes, overflow_data,
                                   overflow_indices))
    return _elastic_merge(v, i, queries.shape[0], k, minimize)


class ElasticIvfFlat:
    """The IVF-Flat twin of :class:`ElasticIvfPq`: a sharded checkpoint
    restored without the original mesh, searched by running the single-
    chip core per stacked shard and merging — degraded restores carry
    ``coverage`` < 1.0."""

    def __init__(self, n_shards, centers, list_data, list_indices,
                 list_sizes, metric, n_rows, overflow_data=None,
                 overflow_indices=None, coverage: float = 1.0,
                 shard_ranks=None):
        self.n_shards = int(n_shards)
        self.centers = centers  # [S, L, dim]
        self.list_data = list_data  # [S, L, pad, dim]
        self.list_indices = list_indices  # [S, L, pad] global ids
        self.list_sizes = list_sizes  # [S, L]
        self.metric = metric
        self.n_rows = int(n_rows)
        self.overflow_data = overflow_data
        self.overflow_indices = overflow_indices
        self.coverage = float(coverage)
        self.shard_ranks = (None if shard_ranks is None
                            else [int(r) for r in shard_ranks])

    def search(self, queries, k: int, params=None,
               res: Optional[Resources] = None) -> "SearchResult":
        from raft_tpu.neighbors import ivf_flat

        res = ensure_resources(res)
        params = params or ivf_flat.SearchParams()
        queries = jnp.asarray(queries)
        n_lists = self.centers.shape[1]
        n_probes = int(min(params.n_probes, n_lists))
        list_pad = self.list_data.shape[2]
        dim = self.list_data.shape[3]
        per_q = n_probes * list_pad * dim * 4 * 2
        q_tile = int(np.clip(res.workspace_limit_bytes // max(per_q, 1),
                             1, 1024))
        if q_tile >= 8:
            q_tile -= q_tile % 8
        fast_scan = getattr(params, "scan_dtype", None) is not None
        select_recall = float(getattr(params, "select_recall", 1.0))
        refine_mult = refine_multiplier(
            getattr(params, "refine_ratio", 4.0), fast_scan)
        has_overflow = self.overflow_data is not None
        if has_overflow:
            over = (self.overflow_data, self.overflow_indices)
        else:
            # stable zero-size placeholders keep the jit signature uniform
            over = (jnp.zeros((self.n_shards, 0, dim), self.list_data.dtype),
                    jnp.zeros((self.n_shards, 0), jnp.int32))
        v, i = _elastic_flat_search(
            queries, self.centers, self.list_data, self.list_indices,
            self.list_sizes, *over, metric=self.metric, k=int(k),
            n_probes=n_probes, q_tile=q_tile, select_recall=select_recall,
            fast_scan=fast_scan, refine_mult=refine_mult,
            has_overflow=has_overflow)
        return SearchResult(v, i, self.coverage)


def deserialize_ivf_flat_elastic(prefix: str, allow_partial: bool = False
                                 ) -> ElasticIvfFlat:
    """IVF-Flat twin of :func:`deserialize_ivf_pq_elastic` — restore on any
    device count; ``allow_partial=True`` serves the surviving shards of a
    damaged checkpoint with ``coverage = rows_available / n_rows``."""
    scalars, parts, survivors, size = _elastic_restore(
        prefix, "sharded_ivf_flat", 3, allow_partial)
    metric, n_rows, _size = scalars
    coverage = (1.0 if len(survivors) == size
                else _elastic_coverage(parts[2], parts[5], survivors,
                                       n_rows))
    _CKPT_RESTORES.labels(
        "ivf_flat", "full" if coverage >= 1.0 else "degraded").inc()
    (centers, list_data, list_indices, list_sizes, o_data,
     o_ids) = _stack_survivors(parts, survivors)
    return ElasticIvfFlat(
        len(survivors), centers, list_data, list_indices, list_sizes,
        DistanceType(metric), int(n_rows), overflow_data=o_data,
        overflow_indices=o_ids, coverage=coverage, shard_ranks=survivors)
