"""Tier B — the jaxpr memory-budget audit (``graftcheck --jaxpr-audit``).

PR 1 made the ivf_pq LUT scan memory-bounded *dynamically*: the planner
(``plan_lut_tiles``) solves (q_tile, probe_tile) from
``workspace_limit_bytes`` using the itemized live-set oracle
``lut_bytes_per_query_probe``. This module turns that invariant into a
*static certificate*: abstract-eval each public entrypoint's traceable
core at canonical shapes (including the sift-1M crash shape from
LUT_CRASH_tpu.json — pad≈1464, pq_dim=64, nprobe=64), walk the closed
jaxpr computing a peak-live-set upper bound from eqn outvar avals, and
fail when the estimate exceeds the entrypoint's declared workspace
budget. Everything is abstract — no index is built, no array allocated —
so the audit runs in CI seconds, not TPU windows.

Accounting model (see docs/analysis.md for the mapping onto the LUT
memory model in docs/tuning.md):

- only **intermediates** count (eqn outvars); the jaxpr's invars and
  consts are resident data (the index, the queries), not workspace;
- liveness is tracked per var: a value occupies the live set from its
  defining eqn until its last use (jaxpr outvars never die);
- higher-order eqns (scan/while/cond/pjit) recurse: the body's peak is
  added on top of the outer live set at that point — the body's invars
  are outer values already accounted (or per-iteration slices).

The estimate is an upper bound on what XLA *must* keep live modulo
fusion (fusion only shrinks it), and a lower bound on a pathological
scheduler; empirically it lands within 2× of the itemized oracle at the
1M crash shape (pinned by tests/test_graftcheck.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import numpy as np
from jax.extend import core as jex_core

from raft_tpu.analysis.findings import Finding

#: the Resources CPU/unknown-backend fallback (core.resources) — the
#: budget every planner solves against when HBM stats are unavailable
DEFAULT_BUDGET_BYTES = 2 << 30

AUDIT_RULE = "B001"
AUDIT_FILE = "jaxpr-audit"


# --------------------------------------------------------------- the walker
def _aval_bytes(aval) -> int:
    try:
        size = int(math.prod(aval.shape))
        return size * np.dtype(aval.dtype).itemsize
    except Exception:  # extended dtypes (PRNG keys), tokens
        try:
            return int(math.prod(aval.shape)) * 4
        except Exception:
            return 0


def _sub_jaxprs(eqn):
    """Inner jaxprs of a higher-order eqn (scan/while/cond/pjit/...)."""
    subs = []

    def collect(v):
        if isinstance(v, jex_core.ClosedJaxpr):
            subs.append(v.jaxpr)
        elif isinstance(v, jex_core.Jaxpr):
            subs.append(v)
        elif isinstance(v, (tuple, list)):
            for e in v:
                collect(e)

    for v in eqn.params.values():
        collect(v)
    return subs


def peak_live_bytes(jaxpr) -> int:
    """Peak simultaneously-live INTERMEDIATE bytes of a (closed) jaxpr."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr

    n = len(jaxpr.eqns)
    last_use: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not isinstance(v, jex_core.Literal):
            last_use[v] = n  # results never die

    live: dict = {}
    live_bytes = 0
    peak = 0
    for i, eqn in enumerate(jaxpr.eqns):
        inner = sum(peak_live_bytes(s) for s in _sub_jaxprs(eqn))
        for v in eqn.outvars:
            b = _aval_bytes(v.aval)
            live[v] = b
            live_bytes += b
        peak = max(peak, live_bytes + inner)
        for v in list(live):
            if last_use.get(v, -1) <= i:
                live_bytes -= live.pop(v)
    return peak


# ------------------------------------------------------------- entry points
@dataclasses.dataclass
class AuditEntry:
    """One audited entrypoint: ``make()`` → ClosedJaxpr of its traceable
    core at the canonical shape, planned against ``budget_bytes`` the way
    the public API plans it."""

    name: str
    budget_bytes: int
    make: Callable

    def run(self) -> "AuditResult":
        jaxpr = self.make()
        peak = peak_live_bytes(jaxpr)
        return AuditResult(self.name, peak, self.budget_bytes,
                           len(jaxpr.jaxpr.eqns))


@dataclasses.dataclass
class AuditResult:
    name: str
    peak_bytes: int
    budget_bytes: int
    n_eqns: int

    @property
    def ok(self) -> bool:
        return self.peak_bytes <= self.budget_bytes

    def format(self) -> str:
        status = "OK  " if self.ok else "FAIL"
        return (f"  {status} {self.name}: peak "
                f"{self.peak_bytes / 2**20:.0f} MiB "
                f"/ budget {self.budget_bytes / 2**20:.0f} MiB "
                f"({self.n_eqns} eqns)")


@dataclasses.dataclass(frozen=True)
class Sift1MCrashShape:
    """The LUT_CRASH_tpu.json shape: SIFT-1M under the sift-1M bench conf
    (n=1e6 rows, dim=128, nlist=1024 → list_pad≈1464 at the 1.5× pad
    budget, pq_dim=64, pq_bits=8, nprobe=64)."""

    nq: int = 1024
    dim: int = 128
    n_lists: int = 1024
    list_pad: int = 1464
    pq_dim: int = 64
    pq_bits: int = 8
    n_probes: int = 64
    k: int = 100

    @property
    def rot_dim(self) -> int:
        return self.dim

    @property
    def book(self) -> int:
        return 1 << self.pq_bits

    @property
    def pq_len(self) -> int:
        return self.rot_dim // self.pq_dim

    @property
    def n_code_bytes(self) -> int:
        return self.pq_dim * self.pq_bits // 8


def sift1m_crash_shape() -> Sift1MCrashShape:
    return Sift1MCrashShape()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def make_ivf_pq_lut_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                         shape: Optional[Sift1MCrashShape] = None,
                         unbounded_variant: bool = False):
    """→ ``(core, args, meta)`` for the LUT-engine scan core exactly as
    ``ivf_pq.search`` would dispatch it at ``shape``: tiles from
    ``plan_lut_tiles`` against ``budget_bytes``. ``unbounded_variant=True``
    reproduces the PRE-PR-1 planning instead — one-axis q_tile solved from
    the under-counting estimate (LUT + packed-code gather only, ~1/5 of
    the true live set) and no probe tiling — the exact configuration that
    produced the ~19 GB live set in LUT_CRASH_tpu.json; the walker must
    flag it. ``meta`` carries the planner name and its predicted peak
    workspace bytes for the obs.costs calibration audit."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.ops.distance import DistanceType

    s = shape or Sift1MCrashShape()
    if unbounded_variant:
        naive_per_q = s.n_probes * (s.pq_dim * s.book * 12
                                    + s.list_pad * s.n_code_bytes)
        q_tile = int(np.clip(budget_bytes // max(naive_per_q, 1), 1, 1024))
        if q_tile >= 8:
            q_tile -= q_tile % 8
        probe_tile = 0  # all probes in one pass
        meta = {"family": "ivf_pq", "planner": None, "predicted_bytes": None,
                "tiles": {"q_tile": q_tile, "probe_tile": probe_tile}}
    else:
        q_tile, probe_tile = ivf_pq.plan_lut_tiles(
            s.n_probes, s.list_pad, s.pq_dim, s.pq_bits, budget_bytes)
        per_qp = ivf_pq.lut_bytes_per_query_probe(s.list_pad, s.pq_dim,
                                                  s.pq_bits)
        meta = {"family": "ivf_pq", "planner": "ivf_pq.plan_lut_tiles",
                "predicted_bytes": q_tile * probe_tile * per_qp,
                "tiles": {"q_tile": q_tile, "probe_tile": probe_tile}}

    def core(queries, centers, rotation, codebooks, list_codes,
             list_indices, list_sizes, filter_words):
        return ivf_pq.search_lut_core(
            queries, centers, rotation, codebooks, list_codes,
            list_indices, list_sizes, filter_words,
            metric=DistanceType.L2Expanded, k=s.k, n_probes=s.n_probes,
            q_tile=q_tile, per_cluster=False, pq_dim=s.pq_dim,
            pq_bits=s.pq_bits, has_filter=False, lut_dtype="float32",
            dist_dtype="float32",
            overflow_decoded=jnp.zeros((0, s.rot_dim), jnp.float32),
            overflow_norms=jnp.zeros((0,), jnp.float32),
            overflow_indices=jnp.zeros((0,), jnp.int32),
            has_overflow=False, probe_tile=probe_tile)

    args = (
        _sds((s.nq, s.dim), np.float32),
        _sds((s.n_lists, s.dim), np.float32),
        _sds((s.rot_dim, s.dim), np.float32),
        _sds((s.pq_dim, s.book, s.pq_len), np.float32),
        _sds((s.n_lists, s.list_pad, s.n_code_bytes), np.uint8),
        _sds((s.n_lists, s.list_pad), np.int32),
        _sds((s.n_lists,), np.int32),
        _sds((0,), np.uint32))
    return core, args, meta


def make_ivf_pq_lut_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                          shape: Optional[Sift1MCrashShape] = None,
                          unbounded_variant: bool = False):
    core, args, _ = make_ivf_pq_lut_core(budget_bytes, shape,
                                         unbounded_variant)
    return jax.make_jaxpr(core)(*args)


def make_ivf_pq_cache_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                           shape: Optional[Sift1MCrashShape] = None):
    """The decoded-cache engine at the same shape (bf16 cache)."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.ops.distance import DistanceType

    s = shape or Sift1MCrashShape()
    q_tile = ivf_pq.plan_cache_tiles(s.n_probes, s.list_pad, s.rot_dim,
                                     budget_bytes)
    meta = {"family": "ivf_pq", "planner": "ivf_pq.plan_cache_tiles",
            "predicted_bytes": q_tile * ivf_pq.cache_bytes_per_query(
                s.n_probes, s.list_pad, s.rot_dim),
            "tiles": {"q_tile": q_tile}}

    def core(queries, centers, rotation, list_decoded, decoded_norms,
             list_indices, list_sizes, filter_words):
        return ivf_pq.search_cache_core(
            queries, centers, rotation, list_decoded, decoded_norms,
            list_indices, list_sizes, filter_words,
            metric=DistanceType.L2Expanded, k=s.k, n_probes=s.n_probes,
            q_tile=q_tile, has_filter=False,
            overflow_decoded=jnp.zeros((0, s.rot_dim), jnp.float32),
            overflow_norms=jnp.zeros((0,), jnp.float32),
            overflow_indices=jnp.zeros((0,), jnp.int32),
            has_overflow=False)

    args = (
        _sds((s.nq, s.dim), np.float32),
        _sds((s.n_lists, s.dim), np.float32),
        _sds((s.rot_dim, s.dim), np.float32),
        _sds((s.n_lists, s.list_pad, s.rot_dim), jax.numpy.bfloat16),
        _sds((s.n_lists, s.list_pad), np.float32),
        _sds((s.n_lists, s.list_pad), np.int32),
        _sds((s.n_lists,), np.int32),
        _sds((0,), np.uint32))
    return core, args, meta


def make_ivf_pq_cache_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                            shape: Optional[Sift1MCrashShape] = None):
    core, args, _ = make_ivf_pq_cache_core(budget_bytes, shape)
    return jax.make_jaxpr(core)(*args)


def make_ivf_pq_encode_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                            shape: Optional[Sift1MCrashShape] = None,
                            n_rows: int = 1_000_000):
    """The build/extend residual-encode core (``encode_batch``'s row_tile
    solve) at the 1M build shape."""
    from raft_tpu.neighbors import ivf_pq

    s = shape or Sift1MCrashShape()
    row_tile = int(np.clip(
        budget_bytes // max(s.pq_dim * s.book * 4 * 4, 1), 8, 4096))
    meta = {"family": "ivf_pq", "planner": None, "predicted_bytes": None,
            "tiles": {"row_tile": row_tile}}

    def core(x, labels, centers, rotation, codebooks):
        return ivf_pq.encode_core(x, labels, centers, rotation, codebooks,
                                  per_cluster=False, row_tile=row_tile)

    args = (
        _sds((n_rows, s.dim), np.float32),
        _sds((n_rows,), np.int32),
        _sds((s.n_lists, s.dim), np.float32),
        _sds((s.rot_dim, s.dim), np.float32),
        _sds((s.pq_dim, s.book, s.pq_len), np.float32))
    return core, args, meta


def make_ivf_pq_encode_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                             shape: Optional[Sift1MCrashShape] = None,
                             n_rows: int = 1_000_000):
    core, args, _ = make_ivf_pq_encode_core(budget_bytes, shape, n_rows)
    return jax.make_jaxpr(core)(*args)


def make_ivf_flat_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                       shape: Optional[Sift1MCrashShape] = None):
    """ivf_flat search core at the 1M shape (raw fp32 lists)."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.ops.distance import DistanceType

    s = shape or Sift1MCrashShape()
    q_tile = ivf_flat.plan_scan_tiles(s.n_probes, s.list_pad, s.dim,
                                      budget_bytes)
    meta = {"family": "ivf_flat", "planner": "ivf_flat.plan_scan_tiles",
            "predicted_bytes": q_tile * ivf_flat.scan_bytes_per_query(
                s.n_probes, s.list_pad, s.dim),
            "tiles": {"q_tile": q_tile}}

    def core(queries, centers, list_data, list_indices, list_sizes,
             filter_words):
        return ivf_flat.search_core(
            queries, centers, list_data, list_indices, list_sizes,
            filter_words, metric=DistanceType.L2Expanded, k=s.k,
            n_probes=s.n_probes, q_tile=q_tile, has_filter=False,
            row_norms=None, fast_scan=False,
            overflow_data=jnp.zeros((0, s.dim), jnp.float32),
            overflow_indices=jnp.zeros((0,), jnp.int32),
            has_overflow=False)

    args = (
        _sds((s.nq, s.dim), np.float32),
        _sds((s.n_lists, s.dim), np.float32),
        _sds((s.n_lists, s.list_pad, s.dim), np.float32),
        _sds((s.n_lists, s.list_pad), np.int32),
        _sds((s.n_lists,), np.int32),
        _sds((0,), np.uint32))
    return core, args, meta


def make_ivf_flat_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                        shape: Optional[Sift1MCrashShape] = None):
    core, args, _ = make_ivf_flat_core(budget_bytes, shape)
    return jax.make_jaxpr(core)(*args)


def make_brute_force_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                          n_db: int = 1_000_000, nq: int = 10_000,
                          dim: int = 128, k: int = 100):
    """brute_force exact kNN at 1M×128 with tiles from the public plan."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import brute_force
    from raft_tpu.ops.distance import DistanceType

    q_tile, db_tile = brute_force.choose_tiles(nq, n_db, dim, k,
                                               budget_bytes)
    meta = {"family": "brute_force", "planner": "brute_force.choose_tiles",
            "predicted_bytes": brute_force.planned_peak_bytes(
                nq, n_db, dim, k, budget_bytes),
            "tiles": {"q_tile": q_tile, "db_tile": db_tile}}

    def core(queries, dataset, db_norms):
        return brute_force.knn_core(
            queries, dataset, db_norms, jnp.zeros((0,), jnp.uint32),
            DistanceType.L2Expanded, 2.0, k, q_tile, db_tile, budget_bytes,
            has_filter=False, fast_scan=False, refine_mult=1,
            select_recall=1.0)

    args = (
        _sds((nq, dim), np.float32),
        _sds((n_db, dim), np.float32),
        _sds((n_db,), np.float32))
    return core, args, meta


def make_brute_force_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                           n_db: int = 1_000_000, nq: int = 10_000,
                           dim: int = 128, k: int = 100):
    core, args, _ = make_brute_force_core(budget_bytes, n_db, nq, dim, k)
    return jax.make_jaxpr(core)(*args)


def make_select_k_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                       rows: int = 1024, width: int = 65536, k: int = 64):
    """matrix::select_k at a serving-scale [rows, width] board."""
    from raft_tpu.ops.select_k import select_k

    meta = {"family": "select_k", "planner": None, "predicted_bytes": None,
            "tiles": {}}
    return (lambda v: select_k(v, k)), (_sds((rows, width), np.float32),), \
        meta


def make_select_k_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                        rows: int = 1024, width: int = 65536, k: int = 64):
    core, args, _ = make_select_k_core(budget_bytes, rows, width, k)
    return jax.make_jaxpr(core)(*args)


def make_fused_l2_nn_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                          m: int = 100_000, n: int = 4096, dim: int = 128):
    """fused_l2_nn_argmin with its row tile solved from the budget."""
    from raft_tpu.ops import fused_l2_nn as fl

    tile = fl.choose_tile_rows(m, n, budget_bytes)
    meta = {"family": "fused_l2_nn",
            "planner": "fused_l2_nn.choose_tile_rows",
            "predicted_bytes": fl.planned_peak_bytes(m, n, budget_bytes),
            "tiles": {"row_tile": tile}}

    def core(x, y, xn, yn):
        return fl.fused_l2_nn_core.__wrapped__(x, y, xn, yn, False, tile)

    args = (
        _sds((m, dim), np.float32), _sds((n, dim), np.float32),
        _sds((m,), np.float32), _sds((n,), np.float32))
    return core, args, meta


def make_fused_l2_nn_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                           m: int = 100_000, n: int = 4096, dim: int = 128):
    core, args, _ = make_fused_l2_nn_core(budget_bytes, m, n, dim)
    return jax.make_jaxpr(core)(*args)


def make_cagra_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                    n: int = 1_000_000, nq: int = 1024, dim: int = 128,
                    graph_degree: int = 64, k: int = 10, itopk: int = 64,
                    width: int = 1):
    """cagra greedy graph search at the 1M shape (graph_degree=64,
    itopk=64, width=1 — the IndexParams/SearchParams defaults). No byte
    planner: the beam state is O(nq·itopk), shape-independent of n, so
    there is nothing for a workspace solver to tile. Not part of the
    audited entries (the walker's upper bound over a 74-iteration
    while_loop is vacuous); it exists for the compiled-cost layer, which
    needs all four ANN families in the roofline report."""
    from raft_tpu.neighbors import cagra
    from raft_tpu.ops.distance import DistanceType

    max_iter = int(np.clip(itopk // width + 10, 16, 200))
    n_seeds = min(max(itopk, 32), n)
    meta = {"family": "cagra", "planner": None, "predicted_bytes": None,
            "tiles": {"itopk": itopk, "width": width,
                      "max_iter": max_iter}}

    def core(queries, dataset, graph, seed_ids, filter_words):
        return cagra.search_core.__wrapped__(
            queries, dataset, dataset, graph, seed_ids, filter_words,
            DistanceType.L2Expanded, k, itopk, width, max_iter, False,
            False)

    args = (
        _sds((nq, dim), np.float32),
        _sds((n, dim), np.float32),
        _sds((n, graph_degree), np.int32),
        _sds((nq, n_seeds), np.int32),
        _sds((0,), np.uint32))
    return core, args, meta


def make_cagra_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES, **kw):
    core, args, _ = make_cagra_core(budget_bytes, **kw)
    return jax.make_jaxpr(core)(*args)


# The fused (Pallas scan+select) variants. Their planners solve the
# ~16 MiB VMEM budget, not ``budget_bytes`` — the HBM workspace the
# walker audits is whatever the dispatch stages around the kernel, which
# the ``fused_*_workspace_bytes`` accounting predicts for C001. The
# cores are traced with ``interpret=True`` so the obs.costs layer can
# AOT-compile them on the CPU backend; the pallas_call eqn carries its
# kernel jaxpr, which the walker recurses into (the on-chip live set is
# VMEM-scale, so it never threatens the HBM budget).

def make_brute_force_fused_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                                n_db: int = 1_000_000, nq: int = 10_000,
                                dim: int = 128, k: int = 100):
    """brute_force fused scan+select at 1M×128, VMEM tiles from the
    public plan."""
    from raft_tpu.neighbors import brute_force
    from raft_tpu.ops import pallas_kernels as pk

    tm, tn = pk.plan_fused_topk_tiles(nq, n_db, dim, k)
    meta = {"family": "brute_force",
            "planner": "pallas_kernels.plan_fused_topk_tiles",
            "predicted_bytes": pk.fused_topk_workspace_bytes(
                nq, n_db, dim, k, tm, tn),
            "tiles": {"tm": tm, "tn": tn}}

    def core(queries, dataset, db_norms):
        return brute_force.knn_fused_core(
            queries, dataset, db_norms, k=k, tm=tm, tn=tn, sqrt=False,
            interpret=True)

    args = (
        _sds((nq, dim), np.float32),
        _sds((n_db, dim), np.float32),
        _sds((n_db,), np.float32))
    return core, args, meta


def make_brute_force_fused_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                                 **kw):
    core, args, _ = make_brute_force_fused_core(budget_bytes, **kw)
    return jax.make_jaxpr(core)(*args)


def make_ivf_flat_fused_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                             shape: Optional[Sift1MCrashShape] = None):
    """ivf_flat fused scan+select at the 1M shape (fp32 slab resident,
    probed tiles DMA'd per (query, probe) grid step)."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.ops import pallas_kernels as pk
    from raft_tpu.ops.distance import DistanceType

    s = shape or Sift1MCrashShape()
    pad_tile = pk.plan_fused_ivf_tile(s.list_pad, s.dim, s.k, 4)
    meta = {"family": "ivf_flat",
            "planner": "pallas_kernels.plan_fused_ivf_tile",
            "predicted_bytes": pk.fused_ivf_workspace_bytes(
                s.nq, s.n_probes, s.dim, s.n_lists, s.list_pad, s.k, 4,
                pad_tile),
            "tiles": {"pad_tile": pad_tile}}

    def core(queries, centers, list_data, list_indices, list_sizes,
             row_norms):
        return ivf_flat.search_fused_core(
            queries, centers, list_data, list_indices, list_sizes,
            row_norms, jnp.zeros((0, s.dim), jnp.float32),
            jnp.zeros((0,), jnp.int32), DistanceType.L2Expanded, s.k,
            s.n_probes, pad_tile, has_overflow=False, interpret=True)

    args = (
        _sds((s.nq, s.dim), np.float32),
        _sds((s.n_lists, s.dim), np.float32),
        _sds((s.n_lists, s.list_pad, s.dim), np.float32),
        _sds((s.n_lists, s.list_pad), np.int32),
        _sds((s.n_lists,), np.int32),
        _sds((s.n_lists, s.list_pad), np.float32))
    return core, args, meta


def make_ivf_flat_fused_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                              shape: Optional[Sift1MCrashShape] = None):
    core, args, _ = make_ivf_flat_fused_core(budget_bytes, shape)
    return jax.make_jaxpr(core)(*args)


def make_ivf_pq_fused_lut_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                               shape: Optional[Sift1MCrashShape] = None):
    """ivf_pq fused LUT engine at the sift-1M crash shape: the per-probe
    LUT is built in VMEM from the resident codebooks and the packed code
    slab is read directly — the candidate slab that crashed PR-1's
    unbounded planning never exists in HBM."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.ops import pallas_kernels as pk
    from raft_tpu.ops.distance import DistanceType

    s = shape or Sift1MCrashShape()
    pad_tile = pk.plan_fused_pq_tile(s.list_pad, s.pq_dim, s.book,
                                     s.pq_len, s.k)
    meta = {"family": "ivf_pq",
            "planner": "pallas_kernels.plan_fused_pq_tile",
            "predicted_bytes": pk.fused_pq_workspace_bytes(
                s.nq, s.n_probes, s.rot_dim, s.n_lists, s.list_pad,
                s.pq_dim, s.book, s.pq_len, s.k, pad_tile),
            "tiles": {"pad_tile": pad_tile}}

    def core(queries, centers, rotation, codebooks, list_codes,
             list_indices, list_sizes):
        return ivf_pq.search_fused_lut_core(
            queries, centers, rotation, codebooks, list_codes,
            list_indices, list_sizes,
            jnp.zeros((0, s.rot_dim), jnp.float32),
            jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32),
            DistanceType.L2Expanded, s.k, s.n_probes, pad_tile,
            has_overflow=False, interpret=True)

    args = (
        _sds((s.nq, s.dim), np.float32),
        _sds((s.n_lists, s.dim), np.float32),
        _sds((s.rot_dim, s.dim), np.float32),
        _sds((s.pq_dim, s.book, s.pq_len), np.float32),
        _sds((s.n_lists, s.list_pad, s.n_code_bytes), np.uint8),
        _sds((s.n_lists, s.list_pad), np.int32),
        _sds((s.n_lists,), np.int32))
    return core, args, meta


def make_ivf_pq_fused_lut_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                                shape: Optional[Sift1MCrashShape] = None):
    core, args, _ = make_ivf_pq_fused_lut_core(budget_bytes, shape)
    return jax.make_jaxpr(core)(*args)


def make_ivf_pq_fused_cache_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                                 shape: Optional[Sift1MCrashShape] = None):
    """ivf_pq fused cache engine at the sift-1M shape (fp32 decoded
    cache; same kernel as ivf_flat but in the rotated ADC space, so no
    clamp)."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.ops import pallas_kernels as pk
    from raft_tpu.ops.distance import DistanceType

    s = shape or Sift1MCrashShape()
    pad_tile = pk.plan_fused_ivf_tile(s.list_pad, s.rot_dim, s.k, 4)
    meta = {"family": "ivf_pq",
            "planner": "pallas_kernels.plan_fused_ivf_tile",
            "predicted_bytes": pk.fused_ivf_workspace_bytes(
                s.nq, s.n_probes, s.rot_dim, s.n_lists, s.list_pad, s.k,
                4, pad_tile),
            "tiles": {"pad_tile": pad_tile}}

    def core(queries, centers, rotation, list_decoded, decoded_norms,
             list_indices, list_sizes):
        return ivf_pq.search_fused_cache_core(
            queries, centers, rotation, list_decoded, decoded_norms,
            list_indices, list_sizes,
            jnp.zeros((0, s.rot_dim), jnp.float32),
            jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32),
            DistanceType.L2Expanded, s.k, s.n_probes, pad_tile,
            has_overflow=False, interpret=True)

    args = (
        _sds((s.nq, s.dim), np.float32),
        _sds((s.n_lists, s.dim), np.float32),
        _sds((s.rot_dim, s.dim), np.float32),
        _sds((s.n_lists, s.list_pad, s.rot_dim), np.float32),
        _sds((s.n_lists, s.list_pad), np.float32),
        _sds((s.n_lists, s.list_pad), np.int32),
        _sds((s.n_lists,), np.int32))
    return core, args, meta


def make_ivf_pq_fused_cache_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                                  shape: Optional[Sift1MCrashShape] = None):
    core, args, _ = make_ivf_pq_fused_cache_core(budget_bytes, shape)
    return jax.make_jaxpr(core)(*args)


def make_cagra_fused_core(budget_bytes: int = DEFAULT_BUDGET_BYTES,
                          n: int = 1_000_000, nq: int = 1024,
                          dim: int = 128, graph_degree: int = 64,
                          k: int = 10, itopk: int = 64, width: int = 1):
    """cagra fused Pallas beam search at the same 1M shape as
    ``make_cagra_core``. Unlike the XLA walk (while_loop → vacuous
    walker bound, excluded from the audited entries), the fused core IS
    auditable: the traversal runs inside the kernel, whose jaxpr the
    walker recurses into with VMEM-scale shapes only — the HBM live set
    it bounds is the in-place ``ANY``-space operands + the small temps
    ``fused_cagra_workspace_bytes`` predicts for C001 (no staged slab:
    the design's whole point)."""
    from raft_tpu.neighbors import cagra
    from raft_tpu.ops import pallas_kernels as pk
    from raft_tpu.ops.distance import DistanceType

    max_iter = int(np.clip(itopk // width + 10, 16, 200))
    n_seeds = min(max(itopk, 32), n)
    ct = pk.plan_fused_cagra_tile(itopk, width, graph_degree, dim, n_seeds)
    meta = {"family": "cagra",
            "planner": "pallas_kernels.plan_fused_cagra_tile",
            "predicted_bytes": pk.fused_cagra_workspace_bytes(
                nq, n, dim, graph_degree, itopk, width, n_seeds, k, ct),
            "tiles": {"ct": ct, "itopk": itopk, "width": width,
                      "max_iter": max_iter}}

    def core(queries, dataset, graph, seed_ids):
        return cagra.search_fused_core(
            queries, dataset, graph, seed_ids, DistanceType.L2Expanded,
            k, itopk, width, max_iter, ct, interpret=True)

    args = (
        _sds((nq, dim), np.float32),
        _sds((n, dim), np.float32),
        _sds((n, graph_degree), np.int32),
        _sds((nq, n_seeds), np.int32))
    return core, args, meta


def make_cagra_fused_jaxpr(budget_bytes: int = DEFAULT_BUDGET_BYTES, **kw):
    core, args, _ = make_cagra_fused_core(budget_bytes, **kw)
    return jax.make_jaxpr(core)(*args)


def canonical_cores(budget_bytes: int = DEFAULT_BUDGET_BYTES) -> list:
    """The twelve canonical entrypoints as ``(name, make_core)`` pairs —
    the SAME names and shapes ``default_entries`` audits, exposed so the
    compiled-cost layer (:mod:`raft_tpu.obs.costs`) lowers and compiles
    exactly what the jaxpr walker abstract-evals. ``make_core()`` →
    ``(core, args, meta)`` with the planner name + predicted workspace
    bytes in ``meta``. The five ``[fused*]`` entries are the Pallas
    engines, traced in interpret mode so they compile on CPU."""
    b = budget_bytes
    return [
        ("ivf_pq.search[lut]@sift1m-crash",
         lambda: make_ivf_pq_lut_core(b)),
        ("ivf_pq.search[cache]@sift1m",
         lambda: make_ivf_pq_cache_core(b)),
        ("ivf_pq.encode_batch@1m",
         lambda: make_ivf_pq_encode_core(b)),
        ("ivf_flat.search@1m",
         lambda: make_ivf_flat_core(b)),
        ("brute_force.knn@1m",
         lambda: make_brute_force_core(b)),
        ("select_k@1024x65536",
         lambda: make_select_k_core(b)),
        ("fused_l2_nn@100kx4096",
         lambda: make_fused_l2_nn_core(b)),
        ("brute_force.knn[fused]@1m",
         lambda: make_brute_force_fused_core(b)),
        ("ivf_flat.search[fused]@sift1m",
         lambda: make_ivf_flat_fused_core(b)),
        ("ivf_pq.search[fused-lut]@sift1m-crash",
         lambda: make_ivf_pq_fused_lut_core(b)),
        ("ivf_pq.search[fused-cache]@sift1m",
         lambda: make_ivf_pq_fused_cache_core(b)),
        ("cagra.search[fused]@1m",
         lambda: make_cagra_fused_core(b)),
    ]


def default_entries(budget_bytes: int = DEFAULT_BUDGET_BYTES) -> list:
    b = budget_bytes
    return [
        AuditEntry("ivf_pq.search[lut]@sift1m-crash", b,
                   lambda: make_ivf_pq_lut_jaxpr(b)),
        AuditEntry("ivf_pq.search[cache]@sift1m", b,
                   lambda: make_ivf_pq_cache_jaxpr(b)),
        AuditEntry("ivf_pq.encode_batch@1m", b,
                   lambda: make_ivf_pq_encode_jaxpr(b)),
        AuditEntry("ivf_flat.search@1m", b,
                   lambda: make_ivf_flat_jaxpr(b)),
        AuditEntry("brute_force.knn@1m", b,
                   lambda: make_brute_force_jaxpr(b)),
        AuditEntry("select_k@1024x65536", b,
                   lambda: make_select_k_jaxpr(b)),
        AuditEntry("fused_l2_nn@100kx4096", b,
                   lambda: make_fused_l2_nn_jaxpr(b)),
        AuditEntry("brute_force.knn[fused]@1m", b,
                   lambda: make_brute_force_fused_jaxpr(b)),
        AuditEntry("ivf_flat.search[fused]@sift1m", b,
                   lambda: make_ivf_flat_fused_jaxpr(b)),
        AuditEntry("ivf_pq.search[fused-lut]@sift1m-crash", b,
                   lambda: make_ivf_pq_fused_lut_jaxpr(b)),
        AuditEntry("ivf_pq.search[fused-cache]@sift1m", b,
                   lambda: make_ivf_pq_fused_cache_jaxpr(b)),
        AuditEntry("cagra.search[fused]@1m", b,
                   lambda: make_cagra_fused_jaxpr(b)),
    ]


def run_audit(entries: Optional[list] = None,
              budget_bytes: int = DEFAULT_BUDGET_BYTES
              ) -> tuple[list, list]:
    """→ (results, findings): one AuditResult per entry, one B001 Finding
    per entry whose peak exceeds its budget."""
    entries = default_entries(budget_bytes) if entries is None else entries
    results = [e.run() for e in entries]
    findings = [
        Finding(AUDIT_RULE, AUDIT_FILE, r.name, 0,
                f"peak live-set estimate {r.peak_bytes / 2**20:.0f} MiB "
                f"exceeds workspace budget "
                f"{r.budget_bytes / 2**20:.0f} MiB")
        for r in results if not r.ok
    ]
    return results, findings


def lut_itemized_peak(shape: Optional[Sift1MCrashShape] = None,
                      budget_bytes: int = DEFAULT_BUDGET_BYTES) -> int:
    """The oracle the walker is cross-checked against: PR 1's itemized
    accounting (``lut_bytes_per_query_probe``) at the planned tiles."""
    from raft_tpu.neighbors import ivf_pq

    s = shape or Sift1MCrashShape()
    q_tile, probe_tile = ivf_pq.plan_lut_tiles(
        s.n_probes, s.list_pad, s.pq_dim, s.pq_bits, budget_bytes)
    per_qp = ivf_pq.lut_bytes_per_query_probe(s.list_pad, s.pq_dim,
                                              s.pq_bits)
    return q_tile * probe_tile * per_qp
