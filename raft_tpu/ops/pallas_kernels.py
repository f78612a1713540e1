"""Pallas TPU kernels for the hot fused ops.

Reference analogs: ``fusedL2NN`` (distance/fused_l2_nn-inl.cuh:76 — L2 +
argmin without materializing the distance matrix), the tiled pairwise
engine (detail/pairwise_distance_base.cuh) and the IVF interleaved scan.

TPU-native design: a distance tile is produced on the MXU from
VMEM-resident tiles and consumed in VMEM by the kernel's epilogue — the
candidate-distance slab never round-trips through HBM before selection
reads it back, the traffic CUDA RAFT eliminates by fusing distance +
selection in registers/SMEM. Tile sizes come from a VMEM-budget planner
(``core.resources.solve_vmem_tiles``, the ~16 MiB on-chip analog of
``solve_joint_tiles``).

Two groups of kernels, dispatched differently:

- The scan kernels the benchmark cells run, chosen in code by their
  callers from the shape and the device: ``group_scan_tile`` (the exact
  scan's tile and its group minima, ``brute_force.plan_group_scan``) and
  ``list_scan`` (the IVF-PQ decoded-cache scan, list-major,
  ``ivf_pq.plan_list_scan``).
- The fused scan+select kernels (``fused_l2_topk``, ``fused_ivf_topk``,
  ``fused_pq_topk``, ``fused_cagra_topk``) and the ring shift of the
  sharded merge. No chip measurement shows them beating XLA, so
  ``scan_mode="auto"`` routes XLA (reason ``fused_unmeasured``) and the
  sharded merge takes the tree; they run only when a caller asks for
  them (``scan_mode="pallas"``, ``merge_mode="ring"``). A PR that
  measures a win writes the rule here, in code.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.utils.shape import round_up_to


def fused_dispatch(family: str, scan_mode: str):
    """Resolve ``(use_fused, interpret)`` for a family's search dispatch.

    ``scan_mode="pallas"``: on TPU always the compiled Mosaic kernel
    (never the interpreter; a request it cannot serve raises, see
    ``require_compiled_kernel``). Off TPU, the Mosaic interpreter when
    ``RAFT_TPU_PALLAS_INTERPRET=1`` opts in (the parity-test hook), else
    the XLA engines — a CPU canary sharing a TPU fleet's serving config
    must not error.

    ``scan_mode="auto"``: the XLA engines — no fused kernel has a chip
    measurement that beats them.

    Anything else: never fused."""
    use_fused, interpret, _ = fused_dispatch_explained(family, scan_mode)
    return use_fused, interpret


def require_compiled_kernel(family: str, scan_mode: str, ineligible) -> None:
    """``scan_mode="pallas"`` on a TPU runs the compiled kernel or raises:
    a request the kernel cannot serve (``ineligible``, an
    ``obs.explain`` reason code) is an error there, never a quiet run of
    the XLA engines. Off the chip the mode keeps its CPU-canary meaning
    (``fused_dispatch``)."""
    if (scan_mode == "pallas" and ineligible
            and jax.default_backend() == "tpu"):
        raise ValueError(
            f"scan_mode='pallas': the fused {family} kernel cannot serve "
            f"this request ({ineligible}); use scan_mode='auto' or an XLA "
            "engine")


def fused_dispatch_explained(family: str, scan_mode: str):
    """``fused_dispatch`` plus the reason code: ``(use_fused, interpret,
    reason)`` with reason from ``obs.explain.REASONS`` — the attributed
    form the family ``search()`` entry points feed into their explain
    records."""
    interp = os.environ.get("RAFT_TPU_PALLAS_INTERPRET") == "1"
    on_tpu = jax.default_backend() == "tpu"
    if scan_mode == "pallas":
        if on_tpu:
            return True, False, "forced"
        if interp:
            return True, True, "interpret"
        return False, False, "tpu_absent"
    if scan_mode == "auto":
        return False, False, "fused_unmeasured" if on_tpu else "tpu_absent"
    # an explicit engine name ("xla", "cache", "lut"): honored as asked
    return False, False, "forced"


# ------------------------------------------------------ in-kernel top-k


def _extract_topk(work, ci, k: int, kp: int):
    """k rounds of (min, argmin, mask) — ascending top-k of ``work`` rows,
    returned padded to ``kp`` columns (+inf / -1 tail, merge_topk_dedup's
    pad convention). ``ci`` carries source indices ([TB, W] or None → lane
    ids are used). For small k this is ~2k VPU passes over VMEM-resident
    data, versus the ~log²(n) passes of a full bitonic sort (the
    warpsort-vs-radix trade the reference's select_k makes,
    matrix/detail/select_warpsort.cuh). A ``lax.fori_loop`` keeps the
    traced program O(1) in k (ADVICE r1: the unrolled form compiled
    linearly in k)."""
    tb = work.shape[0]
    out_col = jax.lax.broadcasted_iota(jnp.int32, (tb, kp), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, work.shape, 1)

    def body(r, carry):
        work, vals, idxs = carry
        a = jnp.argmin(work, axis=1)
        # min + argmin as two reductions: Mosaic has no 1-per-row gather
        # lowering (take_along_axis asserts in _gather_lowering_rule), and
        # reductions are VPU-native anyway
        m = jnp.min(work, axis=1)
        if ci is None:
            src = a.astype(jnp.int32)
        else:
            src = jnp.min(jnp.where(lane == a[:, None], ci,
                                    jnp.iinfo(jnp.int32).max), axis=1)
        # +inf (exactly) is the extraction sentinel: once a row is
        # exhausted (fewer than k non-sentinel entries) argmin would
        # re-pick masked slots — emit the -1 null index instead. A
        # legitimate -inf minimum keeps its real index.
        src = jnp.where(m != jnp.inf, src, -1)
        sel = out_col == r
        vals = jnp.where(sel, m[:, None], vals)
        idxs = jnp.where(sel, src[:, None], idxs)
        work = jnp.where(lane == a[:, None], jnp.inf, work)
        return work, vals, idxs

    vals0 = jnp.full((tb, kp), jnp.inf, jnp.float32)
    idxs0 = jnp.full((tb, kp), -1, jnp.int32)
    _, vals, idxs = jax.lax.fori_loop(0, k, body, (work, vals0, idxs0))
    return vals, idxs


# ---------------------------------------------------- fused scan + select
#
# The tentpole kernels: distance tile production and top-k selection fused
# into one Pallas program whose output block (the running [tile, kp] top-k
# carry) is REVISITED across the inner grid axis — the out_specs index map
# ignores the streaming axis, so Mosaic keeps the carry resident in VMEM
# while database/probe tiles flow through, and only the final k survivors
# are ever written to HBM. This is the TPU expression of the reference's
# fusedL2NN/select_k register pipeline (fused_l2_nn-inl.cuh:76 +
# matrix/detail/select_warpsort.cuh): no [queries, candidates] slab exists
# off-chip at any point.

#: per-core VMEM arena (v4/v5e/v6e: 16 MiB) and the default planning
#: budget — headroom left for Mosaic's own double-buffering and scratch
VMEM_LIMIT_BYTES = 16 << 20
DEFAULT_VMEM_BUDGET = 12 << 20


def _kp(k: int) -> int:
    """Lane-padded carry width (the _extract_topk column convention)."""
    return max(round_up_to(k, 128), 128)


def _lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane vregs (a VMEM block's minor dim)."""
    return round_up_to(max(int(n), 1), 128)


def _sublanes(n: int) -> int:
    """``n`` rounded up to whole 8-sublane tiles (the second-minor dim)."""
    return round_up_to(max(int(n), 1), 8)


def fused_topk_tile_bytes(tm: int, tn: int, dim: int, k: int) -> int:
    """VMEM that Mosaic allocates for one fused brute-force grid step:
    every pipelined block twice (the pipeline double-buffers inputs and
    outputs), each padded to (8, 128) tiles — the [tm, 1] norm column
    fills 128 lanes, the [1, tn] norm row 8 sublanes — plus the [tm, tn]
    distance tile ×3 (dots, d, the extraction working copy) and the
    running-merge set (the [tm, 2·kp] concat pair, the extraction
    accumulators). Checked against the v5e compiler's scoped-VMEM
    numbers (tests/test_chip_compile.py); public so the obs.costs
    calibration audit can compare it to compiled ground truth."""
    kp = _kp(k)
    dl = _lanes(dim)
    blocks = (tm * dl * 4 + tn * dl * 4 + tm * 128 * 4 + 8 * tn * 4
              + 2 * tm * kp * 4)
    return 2 * blocks + tm * tn * 12 + tm * 32 * kp


def plan_fused_topk_tiles(m: int, n: int, dim: int, k: int,
                          vmem_budget: Optional[int] = None):
    """(tm, tn) for ``fused_l2_topk`` from the VMEM budget via
    ``core.resources.solve_vmem_tiles`` — the VMEM analog of the HBM
    ``solve_joint_tiles`` every other planner uses. Prefers streaming the
    full database extent per query tile. When the database must be tiled,
    the whole database streams from HBM once per query tile, so the query
    tile is held at its cap and the db tile shrinks to fit instead (the
    solver's minimal query tile would re-read the database m/8 times)."""
    from raft_tpu.core.resources import solve_vmem_tiles

    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    kp = _kp(k)
    dl = _lanes(dim)
    outer_bytes = 2 * (dl * 4 + 128 * 4 + 2 * kp * 4) + 32 * kp
    inner_bytes = 2 * (dl * 4 + 8 * 4)
    inner_max = round_up_to(max(n, 1), 128)
    outer_cap = 256
    tm, tn = solve_vmem_tiles(
        budget,
        cell_bytes=12,
        outer_bytes=outer_bytes,
        inner_bytes=inner_bytes,
        inner_max=inner_max,
        outer_cap=outer_cap,
    )
    if tn < inner_max:
        tm = min(outer_cap, round_up_to(max(m, 1), 8))
        tn = (budget - tm * outer_bytes) // (inner_bytes + tm * 12)
    tm = min(tm, round_up_to(max(m, 1), 8))
    tm = max(8, tm - tm % 8)
    tn = min(tn, round_up_to(max(n, 1), 128))
    tn = max(128, tn - tn % 128)
    return tm, tn


def fused_topk_workspace_bytes(m: int, n: int, dim: int, k: int,
                               tm: Optional[int] = None, tn: Optional[int] = None,
                               vmem_budget: Optional[int] = None) -> int:
    """HBM-side workspace of one fused brute-force dispatch: the padded
    query/db copies and norm rows staged for the kernel, the [mp, kp]
    val/idx outputs (temps of the enclosing jit — the caller slices
    [:m, :k]), plus one grid step's block set (the interpreter's block
    buffers on CPU; the VMEM live set on TPU). The db slab is counted
    TWICE: the pipeline stages it once for the pad and once as the
    kernel operand held across the grid loop (measured on the CPU
    interpreter; on TPU the kernel DMAs the staged copy in place, so
    this over-predicts by ~2× — the safe direction for a crash audit).
    Public for the graftcheck ``--costs`` C001 calibration audit."""
    if tm is None or tn is None:
        tm, tn = plan_fused_topk_tiles(m, n, dim, k, vmem_budget)
    mp = round_up_to(max(m, 1), tm)
    np_ = round_up_to(max(n, 1), tn)
    kp = _kp(k)
    return (mp * dim * 4 + 2 * np_ * dim * 4 + np_ * 8 + mp * 4
            + mp * kp * 8 + fused_topk_tile_bytes(tm, tn, dim, k))


def _fused_topk_kernel(x_ref, y_ref, xn_ref, yn_ref, val_ref, idx_ref, *,
                       k: int, kp: int, tn: int):
    """One (query-tile, db-tile) step: expanded-L2 tile on the MXU, per-tile
    top-k extraction, merge into the resident carry. Global row ids are
    reconstructed from the db-tile offset (j·tn); padded db rows carry
    +inf norms so their distances hit the extraction sentinel and emit the
    -1 null id."""
    j = pl.program_id(1)
    dots = jax.lax.dot_general(
        x_ref[:], y_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [TM, TN]
    d = xn_ref[:] + yn_ref[:] - 2.0 * dots
    # match ops.distance.l2_expanded's clamp (exact-parity requirement);
    # +inf pad norms survive the maximum untouched
    d = jnp.maximum(d, 0.0)
    tv, ti = _extract_topk(d, None, k, kp)  # ascending, [TM, kp]
    ti = jnp.where(ti >= 0, ti + j * tn, -1)

    @pl.when(j == 0)
    def _():
        val_ref[...] = tv
        idx_ref[...] = ti

    @pl.when(j > 0)
    def _():
        cv = jnp.concatenate([val_ref[...], tv], axis=1)  # [TM, 2·kp]
        ci = jnp.concatenate([idx_ref[...], ti], axis=1)
        mv, mi = _extract_topk(cv, ci, k, kp)
        val_ref[...] = mv
        idx_ref[...] = mi


@functools.partial(jax.jit, static_argnames=("k", "tm", "tn", "interpret"))
def _fused_topk_pallas(x, y, x_norms, y_norms, k: int, tm: int, tn: int,
                       interpret: bool):
    m, d = x.shape
    n, _ = y.shape
    mp = round_up_to(m, tm)
    np_ = round_up_to(n, tn)
    kp = _kp(k)
    xp = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, 0)))
    yp = jnp.pad(y.astype(jnp.float32), ((0, np_ - n), (0, 0)))
    xn = jnp.pad(x_norms.astype(jnp.float32), (0, mp - m)).reshape(mp, 1)
    # padded y rows must never reach the carry
    yn = jnp.where(jnp.arange(np_) < n,
                   jnp.pad(y_norms.astype(jnp.float32), (0, np_ - n)),
                   jnp.inf).reshape(1, np_)
    grid = (mp // tm, np_ // tn)
    val, idx = pl.pallas_call(
        functools.partial(_fused_topk_kernel, k=k, kp=kp, tn=tn),
        out_shape=(jax.ShapeDtypeStruct((mp, kp), jnp.float32),
                   jax.ShapeDtypeStruct((mp, kp), jnp.int32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tn), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            # index map ignores j: the carry block stays VMEM-resident
            # while db tiles stream through
            pl.BlockSpec((tm, kp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, kp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )(xp, yp, xn, yn)
    return val[:m, :k], idx[:m, :k]


def fused_l2_topk(x, y, k: int, x_norms=None, y_norms=None,
                  tm: Optional[int] = None, tn: Optional[int] = None,
                  vmem_budget: Optional[int] = None, interpret: bool = False):
    """Fused squared-L2 scan + top-k: ``(distances [m, k], ids [m, k])``
    ascending, distances clamped at 0 (the l2_expanded convention), ids
    -1 where fewer than k rows exist. The [m, n] distance matrix never
    materializes — each [tm, tn] tile is consumed on-chip by the running
    VMEM top-k merge. Tile sizes default to the VMEM-budget solve
    (``plan_fused_topk_tiles``); ``interpret=True`` runs the Mosaic
    interpreter (CPU CI)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if k > 1024:
        raise ValueError(
            f"fused_l2_topk is a small-k kernel (k={k} > 1024); "
            "use the XLA engines")
    m, _ = x.shape
    n = y.shape[0]
    if x_norms is None:
        x_norms = jnp.sum(x.astype(jnp.float32) ** 2, -1)
    if y_norms is None:
        y_norms = jnp.sum(y.astype(jnp.float32) ** 2, -1)
    ptm, ptn = plan_fused_topk_tiles(m, n, x.shape[1], k, vmem_budget)
    tm = ptm if tm is None else int(tm)
    tn = ptn if tn is None else int(tn)
    tm = max(8, min(tm, round_up_to(m, 8)))
    tm -= tm % 8
    tn = max(128, min(tn, round_up_to(n, 128)))
    tn -= tn % 128
    return _fused_topk_pallas(x, y, x_norms, y_norms, int(k), tm, tn,
                              bool(interpret))


# ------------------------------------------- exact scan: a tile's groups
#
# The exact brute-force scan (neighbors.brute_force._group_topk) ranks
# 128-row groups by their minima and gathers the kept groups' rows. In
# XLA the distance tile comes out with the rows on the lanes, and both
# readers want it laid out again in HBM: the minima reduce across the
# lanes, the gather wants the groups major. This kernel makes each
# [queries, rows] block in VMEM, writes it as [groups, queries, 128] (the
# order the gather reads) and reduces each group's 128 lanes there, so
# the tile is written once and never relaid out.

#: rows per group: one 128-lane vreg row (``brute_force.GROUP``)
SCAN_GROUP = 128


def rows_on_lanes(device, dtype, shape) -> bool:
    """Whether ``device`` keeps a [rows, dim] array of ``shape`` with its
    rows on the lanes (layout ``{0,1}``), so that its [dim, rows]
    transpose is a bitcast. A v5e does so where that pads less than
    rows-major: f32 [n, 96] yes, [n, 128] and [n, 768] no."""
    from jax.experimental.layout import Layout

    layout = Layout.from_pjrt_layout(device.client.get_default_layout(
        jnp.dtype(dtype), tuple(shape), device))
    return layout.major_to_minor[-1] == 0


def group_scan_vmem_terms(q_tile: int, dim: int, lanes_rows: bool) -> dict:
    """The VMEM of one ``group_scan_tile`` grid step, as the terms of
    ``core.resources.solve_vmem_tiles`` (outer: the step's rows; inner:
    the resident queries). Every pipelined block twice: a cell's tile
    block and, once more, its distances (``cell_bytes``); a row's block
    of the database — [dim, rows] 8-sublane padded, or [rows, dim]
    lane-padded — and its norm row (``outer_bytes``); a query's [q, dim]
    row, norm and minima, each lane-padded (``inner_bytes``). Against the
    v5e compiler's scoped VMEM: 0.1% over at the exact-kNN cell's step
    (1000 queries × 512 rows × 96), 5–86% over at eight other steps, and
    1–3% under where at most 64 queries sit beside 768- to 1024-wide
    rows; the 12 MiB budget leaves 4 MiB below the 16 MiB limit."""
    row = _sublanes(dim) * 4 if lanes_rows else _lanes(dim) * 4
    return {"cell_bytes": 12,
            "outer_bytes": 2 * (row + 8 * 4),
            "inner_bytes": 2 * (_lanes(dim) * 4 + 2 * 128 * 4)}


def plan_group_scan(q_tile: int, width: int, dim: int, lanes_rows: bool,
                    aligned_to: int = 0,
                    vmem_budget: Optional[int] = None) -> int:
    """Groups per grid step of ``group_scan_tile`` (0: the query tile
    cannot stay resident, so the kernel cannot run): the most rows a step
    that the VMEM solve (``group_scan_vmem_terms``) fits beside the whole
    query tile, in a power of two of groups (a step's minima land in one
    128-lane block) that divides ``aligned_to`` rows when it is given (a
    tile's start must be a whole number of steps)."""
    from raft_tpu.core.resources import solve_vmem_tiles

    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    q = _sublanes(q_tile)
    rows, tq = solve_vmem_tiles(
        budget, **group_scan_vmem_terms(q_tile, dim, lanes_rows),
        inner_max=q,
        outer_cap=SCAN_GROUP * SCAN_GROUP,
        outer_multiple=SCAN_GROUP,
        inner_multiple=8,
    )
    if tq < q:
        return 0
    gb = 1
    limit = min(rows, round_up_to(max(int(width), 1), SCAN_GROUP))
    while 2 * gb * SCAN_GROUP <= limit and (
            not aligned_to or aligned_to % (2 * gb * SCAN_GROUP) == 0):
        gb *= 2
    return gb


def _group_scan_kernel(s_ref, q_ref, qn_ref, x_ref, row_ref, tile_ref,
                       min_ref, *, gb: int, lanes_rows: bool, l2: bool,
                       sqrt: bool, negate: bool, filtered: bool):
    """One step: the [q, gb·128] distances of ``gb`` groups on the MXU
    (from a [dim, rows] block, or a [rows, dim] one when the rows are not
    on the lanes) and the scan's epilogue (the ``l2_expanded`` order;
    negated when selecting the largest; +inf past ``limit`` and where the
    row term is +inf, the rows a filter clears), written groups-major;
    each group's minimum, reduced across its lanes, lands in its lane of
    the resident [q, 128] minima block."""
    j = pl.program_id(0)
    tn = gb * SCAN_GROUP
    dots = jax.lax.dot_general(
        q_ref[...], x_ref[...], (((1,), (0 if lanes_rows else 1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [q, tn]
    row = row_ref[...]
    if l2:
        d = jnp.maximum(qn_ref[...] + row - 2.0 * dots, 0.0)
        d = jnp.sqrt(d) if sqrt else d
    else:
        d = dots
    d = -d if negate else d
    first = (s_ref[0] + j) * tn
    bad = jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1) + first >= s_ref[1]
    if filtered:
        bad = bad | (row == jnp.inf)
    d = jnp.where(bad, jnp.inf, d)
    lane = jax.lax.broadcasted_iota(jnp.int32, min_ref.shape, 1)
    at = (j % (SCAN_GROUP // gb)) * gb
    mins = min_ref[...]
    for g in range(gb):
        part = d[:, g * SCAN_GROUP:(g + 1) * SCAN_GROUP]
        tile_ref[g] = part
        mins = jnp.where(lane == at + g,
                         jnp.min(part, axis=1, keepdims=True), mins)
    min_ref[...] = mins


@functools.partial(jax.jit, static_argnames=(
    "width", "gb", "lanes_rows", "l2", "sqrt", "negate", "filtered",
    "interpret"))
def group_scan_tile(queries, q_norms, data, row_terms, start, limit, *,
                    width: int, gb: int, lanes_rows: bool, l2: bool,
                    sqrt: bool, negate: bool, filtered: bool,
                    interpret: bool = False):
    """The exact scan's tile ``[start, start + width)`` of the database
    ``data`` [n, dim], by groups of ``SCAN_GROUP`` rows → ``(tile [n_g,
    q, 128], minima [q, n_g])``, n_g = ⌈width / 128⌉: the values the scan
    ranks (distances, negated when ``negate``) and each group's minimum.

    The kernel reads ``data`` in the layout it has, never a copy:
    ``lanes_rows`` (``rows_on_lanes``) reads its [dim, n] transpose, a
    bitcast there; else [rows, dim] blocks. ``row_terms`` [n] is the
    rows' squared norms for L2 (``l2``), zeros for inner product, +inf at
    rows a filter clears (``filtered``). Rows at or past ``limit`` —
    padding of a sharded collection, the last group's pad — read +inf.
    ``start`` is a whole number of ``gb·128``-row steps
    (``plan_group_scan``); a block past the end of ``data`` is read
    clipped and masked."""
    nq, dim = queries.shape
    n_g = -(-int(width) // SCAN_GROUP)
    tn = gb * SCAN_GROUP
    spb = SCAN_GROUP // gb  # steps a 128-group minima block
    scalars = jnp.stack([jnp.asarray(start, jnp.int32) // tn,
                         jnp.asarray(limit, jnp.int32)])
    data = data.astype(jnp.float32)
    if lanes_rows:
        data, x_spec = data.T, pl.BlockSpec((dim, tn),
                                            lambda j, s: (0, s[0] + j))
    else:
        x_spec = pl.BlockSpec((tn, dim), lambda j, s: (s[0] + j, 0))
    tile, mins = pl.pallas_call(
        functools.partial(_group_scan_kernel, gb=gb, lanes_rows=lanes_rows,
                          l2=l2, sqrt=sqrt, negate=negate,
                          filtered=filtered),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-n_g // gb),),
            in_specs=[
                pl.BlockSpec((nq, dim), lambda j, s: (0, 0)),
                pl.BlockSpec((nq, 1), lambda j, s: (0, 0)),
                x_spec,
                pl.BlockSpec((1, tn), lambda j, s: (0, s[0] + j)),
            ],
            out_specs=[
                pl.BlockSpec((gb, nq, SCAN_GROUP), lambda j, s: (j, 0, 0)),
                pl.BlockSpec((nq, SCAN_GROUP), lambda j, s: (0, j // spb)),
            ]),
        out_shape=(
            jax.ShapeDtypeStruct((n_g, nq, SCAN_GROUP), jnp.float32),
            jax.ShapeDtypeStruct((nq, round_up_to(n_g, SCAN_GROUP)),
                                 jnp.float32)),
        interpret=interpret,
    )(scalars, queries.astype(jnp.float32),
      q_norms.astype(jnp.float32).reshape(nq, 1), data,
      row_terms.reshape(1, -1))
    return tile, mins[:, :n_g]


# ------------------------------------- ivf_pq decoded cache, list-major
#
# The list-major scan of ivf_pq's decoded cache (neighbors.ivf_pq
# ``_search_cache_lists_core``): the (query, probed list) pairs of a batch
# are ordered by list and cut into blocks of ``T`` query rows, each block
# of one list. A grid step contracts a block's rows against its list's
# whole slab on the MXU; blocks of one list are consecutive, so their slab
# block index repeats and Pallas fetches each probed list once.


def list_scan_groups(list_pad: int) -> int:
    """Groups of ``SCAN_GROUP`` slots a list is cut into by
    ``list_scan``."""
    return -(-int(list_pad) // SCAN_GROUP)


def list_scan_group_starts(list_pad: int) -> np.ndarray:
    """The first slot of each group of ``list_scan``: whole groups from
    slot 0, the last one ending at ``list_pad`` (it overlaps the one
    before when ``list_pad`` is not a whole number of groups; its lanes
    before ``(n_g - 1)·128`` repeat earlier slots and are masked by the
    caller's row terms), so every group reads 128 slots of the slab at a
    sublane-aligned offset."""
    n_g = list_scan_groups(list_pad)
    return np.minimum(np.arange(n_g) * SCAN_GROUP, list_pad - SCAN_GROUP)


def list_scan_vmem_bytes(t: int, list_pad: int, rot: int,
                         itemsize: int = 4) -> int:
    """VMEM of one ``list_scan`` grid step: every pipelined block twice —
    the block's [T, rot] query rows, its list's centre row, the list's
    [list_pad, rot] slab and [n_g, 128] row terms, the [n_g, T, 128]
    distances and the [T, 128] minima written back — plus the step's
    residuals, one group's upcast slab rows, distances and minima."""
    n_g = list_scan_groups(list_pad)
    rl = _lanes(rot)
    blocks = (t * rl * 4 + 8 * rl * 4 + _sublanes(list_pad) * rl * itemsize
              + _sublanes(n_g) * SCAN_GROUP * 4
              + n_g * t * SCAN_GROUP * 4 + t * SCAN_GROUP * 4)
    return (2 * blocks + t * rl * 4 + SCAN_GROUP * rl * 4
            + 3 * t * SCAN_GROUP * 4)


def _list_scan_kernel(s_ref, n_ref, q_ref, c_ref, dec_ref, row_ref,
                      dist_ref, min_ref, *, starts, l2: bool, precision):
    """One block: its rows' residuals against the list's centre (L2) or
    the rows themselves with their centre term (inner product), contracted
    against each group of the list's slab on the MXU at ``precision``;
    the epilogue ``‖q_res‖² − 2·dot + row`` (L2) or ``row − (q·c + dot)``
    (inner product, negated so that least is best), where the row term is
    the slot's ``‖dec‖²`` (L2), 0 (inner product) or +inf (a slot that
    holds no row, repeats an earlier group's, or a filter clears). Each
    group's [T, 128] distances land in the block's groups-major output and
    its minimum in its lane of the minima. Blocks past the plan's last
    (``n_ref``) hold no rows and are skipped."""
    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        q = q_ref[0]  # [T, rot]
        c = c_ref[0]  # [1, rot]
        if l2:
            q = q - c
            qt = jnp.sum(q * q, axis=1, keepdims=True)
        else:
            qt = jnp.sum(q * c, axis=1, keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, min_ref.shape[1:], 1)
        mins = jnp.full(min_ref.shape[1:], jnp.inf, jnp.float32)
        for g, s in enumerate(starts):
            x = dec_ref[0, s:s + SCAN_GROUP, :].astype(jnp.float32)
            dots = jax.lax.dot_general(
                q, x, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision,
            )  # [T, 128]
            row = row_ref[0, g:g + 1, :]
            d = qt - 2.0 * dots + row if l2 else row - (qt + dots)
            dist_ref[0, g] = d
            mins = jnp.where(lane == g, jnp.min(d, axis=1, keepdims=True),
                             mins)
        min_ref[0] = mins


@functools.partial(jax.jit, static_argnames=("l2", "precision",
                                              "interpret"))
def list_scan(block_list, n_used, rows, centers_rot, list_decoded,
              row_terms, *, l2: bool, precision,
              interpret: bool = False):
    """Distances of blocks of query rows against their lists' slabs.

    ``block_list`` [NB] int32 names each block's list, blocks of one list
    consecutive; ``n_used`` [1] the blocks that hold rows (the rest are
    skipped); ``rows`` [NB, T, rot] float32 each block's rotated queries;
    ``centers_rot`` [n_lists, rot]; ``list_decoded`` [n_lists, list_pad,
    rot] (float32 or bfloat16, upcast in VMEM); ``row_terms`` [n_lists,
    n_g, 128] each group's slot terms (``_list_scan_kernel``), laid out by
    ``list_scan_group_starts``. Returns ``(dist [NB, n_g, T, 128], minima
    [NB, T, 128])``: each block's values by group (least is best) and each
    group's minimum in lane ``g`` (+inf past ``n_g``). Skipped blocks
    (past ``n_used``) are neither read nor written: their outputs are
    undefined."""
    nb, t, rot = rows.shape
    n_lists, list_pad, _ = list_decoded.shape
    if list_pad < SCAN_GROUP:
        raise ValueError(f"list_scan needs lists of at least {SCAN_GROUP} "
                         f"slots, got {list_pad}")
    n_g = list_scan_groups(list_pad)
    starts = tuple(int(s) for s in list_scan_group_starts(list_pad))

    def last(b, n):
        # a skipped block keeps the last used block's rows and outputs
        # resident: nothing is fetched or written back for it
        return jnp.minimum(b, jnp.maximum(n[0] - 1, 0))

    return pl.pallas_call(
        functools.partial(_list_scan_kernel, starts=starts, l2=l2,
                          precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((1, t, rot), lambda b, s, n: (last(b, n), 0, 0)),
                pl.BlockSpec((1, 1, rot), lambda b, s, n: (s[b], 0, 0)),
                pl.BlockSpec((1, list_pad, rot),
                             lambda b, s, n: (s[b], 0, 0)),
                pl.BlockSpec((1, n_g, SCAN_GROUP),
                             lambda b, s, n: (s[b], 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, n_g, t, SCAN_GROUP),
                             lambda b, s, n: (last(b, n), 0, 0, 0)),
                pl.BlockSpec((1, t, SCAN_GROUP),
                             lambda b, s, n: (last(b, n), 0, 0)),
            ]),
        out_shape=(
            jax.ShapeDtypeStruct((nb, n_g, t, SCAN_GROUP), jnp.float32),
            jax.ShapeDtypeStruct((nb, t, SCAN_GROUP), jnp.float32)),
        interpret=interpret,
    )(block_list.astype(jnp.int32), n_used.astype(jnp.int32),
      rows.astype(jnp.float32),
      centers_rot.astype(jnp.float32).reshape(n_lists, 1, rot),
      list_decoded, row_terms.astype(jnp.float32))


# ------------------------------------------------------- fused ivf top-k


def fused_ivf_vmem_bytes(pad_tile: int, rot: int, k: int,
                         itemsize: int = 4, n_probes: int = 1) -> int:
    """VMEM that Mosaic allocates for one fused IVF grid step: the
    double-buffered blocks — the query's [P, rot] residual set, its
    8-query ``||q_res||²`` rows, the probed slab's [pad_tile, rot] block,
    the 8-list norm and id rows, the 8-query carry pair — plus the slab's
    fp32 upcast, the [1, pad_tile] distance/id/mask rows (8 sublanes
    each) and the running-merge set. Public for the C001 calibration
    audit."""
    kp = _kp(k)
    rl = _lanes(rot)
    blocks = (_sublanes(n_probes) * rl * 4 + 8 * _lanes(n_probes) * 4
              + pad_tile * rl * itemsize + 2 * 8 * _lanes(pad_tile) * 4
              + 2 * 8 * kp * 4)
    return (2 * blocks + pad_tile * rl * 4 + 6 * 8 * _lanes(pad_tile) * 4
            + 8 * 32 * kp)


def _plan_slab_tile(list_pad: int, fits) -> int:
    """The largest legal slab tile that ``fits`` the VMEM budget: a
    divisor of ``list_pad`` (the slab cannot be re-padded — that would
    copy the whole index) that is either the whole pad or a multiple of
    128 lanes (the block rule for the [8, pad_tile] norm/id rows). With
    no legal tile fitting, the smallest legal one."""
    legal = [pt for pt in range(128, list_pad, 128) if list_pad % pt == 0]
    legal.append(list_pad)
    fitting = [pt for pt in legal if fits(pt)]
    return max(fitting) if fitting else legal[0]


def plan_fused_ivf_tile(list_pad: int, rot: int, k: int,
                        itemsize: int = 4, vmem_budget: Optional[int] = None,
                        n_probes: int = 1) -> int:
    """The list-slab row tile for ``fused_ivf_topk``: the largest legal
    tile (``_plan_slab_tile``) whose grid-step live set fits the VMEM
    budget. Returns ``list_pad`` itself whenever the whole slab fits (one
    DMA per probe, no inner axis)."""
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    return _plan_slab_tile(list_pad, lambda pt: fused_ivf_vmem_bytes(
        pt, rot, k, itemsize, n_probes) <= budget)


def fused_ivf_workspace_bytes(nq: int, n_probes: int, rot: int,
                              n_lists: int, list_pad: int, k: int,
                              itemsize: int = 4,
                              pad_tile: Optional[int] = None) -> int:
    """HBM-side workspace of one fused IVF dispatch: the probed slab
    counted twice (staged + held as the kernel operand across the grid
    loop, measured on the CPU interpreter; on TPU the slab is DMA'd in
    place so this over-predicts ~2× — the safe direction), the
    [nq, n_probes, rot] residual broadcast and its norms, the masked id
    copy, the [nq, kp] val/idx outputs, and one grid step's block set.
    Public for the graftcheck ``--costs`` C001 calibration audit."""
    if pad_tile is None:
        pad_tile = plan_fused_ivf_tile(list_pad, rot, k, itemsize)
    kp = _kp(k)
    return (2 * n_lists * list_pad * rot * itemsize
            + nq * n_probes * (rot * 4 + 4)
            + n_lists * list_pad * 4
            + nq * kp * 8
            + fused_ivf_vmem_bytes(pad_tile, rot, k, itemsize))


#: scalar-prefetch budget per pallas_call: SMEM is 1 MiB per core, and the
#: prefetched table (probe or seed ids, flattened so no lane padding
#: applies) must fit beside Mosaic's own scalars
SMEM_PREFETCH_BYTES = 256 << 10


def _by_query_chunks(call, scalars_per_query: int, table, *rows):
    """Run ``call(table_c, *rows_c)`` over query chunks whose prefetched
    ``table`` slice fits ``SMEM_PREFETCH_BYTES``, padding every operand's
    query axis to a multiple of 8 (the carry blocks hold 8 queries). The
    chunk calls are independent, so their [nq_c, kp] results concatenate;
    callers slice the padded tail off."""
    nq = table.shape[0]
    chunk = SMEM_PREFETCH_BYTES // (4 * max(int(scalars_per_query), 1))
    chunk = max(8, chunk - chunk % 8)
    nqp = round_up_to(max(nq, 1), 8)
    pad = lambda a: jnp.pad(a, ((0, nqp - nq),) + ((0, 0),) * (a.ndim - 1))
    table, rows = pad(table), [pad(a) for a in rows]
    outs = [call(table[s:s + chunk], *(a[s:s + chunk] for a in rows))
            for s in range(0, nqp, chunk)]
    if len(outs) == 1:
        return outs[0]
    return tuple(jnp.concatenate(parts) for parts in zip(*outs))


def _merge_row(val_ref, idx_ref, row, first, tv, ti, k: int, kp: int):
    """Merge one query's tile top-k ``(tv, ti)`` [1, kp] into row ``row``
    of the [8, kp] carry blocks — overwrite on the query's first step,
    extract-merge otherwise. Eight consecutive queries share one carry
    block (the (8, 128) block rule), so each reads and writes only its
    own sublane."""
    rows = pl.ds(row, 1)

    @pl.when(first)
    def _():
        val_ref[rows, :] = tv
        idx_ref[rows, :] = ti

    @pl.when(jnp.logical_not(first))
    def _():
        cv = jnp.concatenate([val_ref[rows, :], tv], axis=1)  # [1, 2·kp]
        ci = jnp.concatenate([idx_ref[rows, :], ti], axis=1)
        mv, mi = _extract_topk(cv, ci, k, kp)
        val_ref[rows, :] = mv
        idx_ref[rows, :] = mi


def _pick_lane(row, j):
    """Column ``j`` of a [1, w] row as a [1, 1] value (a masked sum —
    exact, since every other lane adds 0)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == j, row, 0.0), axis=1, keepdims=True)


def _as_column(row, fill):
    """A [1, w] row as a [w, 1] column: each sublane keeps the diagonal
    entry of the row broadcast over a [w, w] tile (Mosaic has no
    lane→sublane reshape; exact, since ``fill`` never wins the min)."""
    w = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (w, w), 1))
    return jnp.min(jnp.where(eye, row, fill), axis=1, keepdims=True)


def _fused_ivf_topk_kernel(probes_ref, qres_ref, qn_ref, dec_ref, norms_ref,
                           ids_ref, val_ref, idx_ref, *, k: int, kp: int,
                           n_probes: int, clamp: bool):
    """One (query, probe, slab-tile) step: partial distances of the probed
    slab rows against this query's residual, merged into the resident
    top-k carry. Source row ids come straight from the DMA'd
    ``list_indices`` block (-1 at unfilled slots → masked to the +inf
    sentinel, so padding can never reach the carry); distances are
    comparable ACROSS probes because the per-(query, probe) ``||q_res||²``
    base is added in-kernel. Per-query rows (``qn``, the carry) and
    per-list rows (norms, ids) arrive as 8-row blocks; the step reads its
    own sublane."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    r = pl.program_id(2)
    lrow = pl.ds(probes_ref[i * n_probes + j] % 8, 1)
    q = qres_ref[0, pl.ds(j, 1), :].astype(jnp.float32)  # [1, rot]
    dots = jax.lax.dot_general(
        q, dec_ref[0].astype(jnp.float32),  # bf16 cache; f32 math in VMEM
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [1, pt]
    qn = _pick_lane(qn_ref[pl.ds(i % 8, 1), :], j)  # [1, 1]
    d = qn + norms_ref[lrow, :] - 2.0 * dots  # [1, pt]
    if clamp:
        d = jnp.maximum(d, 0.0)  # ivf_flat's exact-L2 clamp
    ids = ids_ref[lrow, :]  # [1, pt] int32
    d = jnp.where(ids < 0, jnp.inf, d)
    tv, ti = _extract_topk(d, ids, k, kp)  # [1, kp]
    _merge_row(val_ref, idx_ref, i % 8, (j == 0) & (r == 0), tv, ti, k, kp)


@functools.partial(jax.jit,
                   static_argnames=("k", "pad_tile", "clamp", "interpret"))
def _fused_ivf_topk_pallas(probes, qres, qres_norms, list_data, row_norms,
                           list_indices, k: int, pad_tile: int, clamp: bool,
                           interpret: bool):
    nq, n_probes = probes.shape
    n_lists, list_pad, rot = list_data.shape
    pt = pad_tile
    n_r = list_pad // pt
    kp = _kp(k)

    def call(probes_c, qres_c, qn_c):
        nqc = probes_c.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nqc, n_probes, n_r),
            in_specs=[
                # the query's whole [P, rot] residual set: fetched once
                # per query (index ignores j), the step reads sublane j
                pl.BlockSpec((1, _sublanes(n_probes), rot),
                             lambda i, j, r, probes: (i, 0, 0)),
                pl.BlockSpec((8, n_probes),
                             lambda i, j, r, probes: (i // 8, 0)),
                pl.BlockSpec((1, pt, rot),
                             lambda i, j, r, probes: (
                                 probes[i * n_probes + j], r, 0)),
                pl.BlockSpec((8, pt),
                             lambda i, j, r, probes: (
                                 probes[i * n_probes + j] // 8, r)),
                pl.BlockSpec((8, pt),
                             lambda i, j, r, probes: (
                                 probes[i * n_probes + j] // 8, r)),
            ],
            # carry blocks revisited across BOTH probe and slab-tile axes
            # (and the 8 queries that share them)
            out_specs=(
                pl.BlockSpec((8, kp), lambda i, j, r, probes: (i // 8, 0)),
                pl.BlockSpec((8, kp), lambda i, j, r, probes: (i // 8, 0))),
        )
        return pl.pallas_call(
            functools.partial(_fused_ivf_topk_kernel, k=k, kp=kp,
                              n_probes=n_probes, clamp=clamp),
            out_shape=(jax.ShapeDtypeStruct((nqc, kp), jnp.float32),
                       jax.ShapeDtypeStruct((nqc, kp), jnp.int32)),
            grid_spec=grid_spec,
            interpret=interpret,
        )(probes_c.reshape(-1), qres_c, qn_c, list_data, row_norms,
          list_indices)

    # probe axis of the residual block padded to whole sublane tiles (the
    # step reads row j < n_probes only)
    qres_p = jnp.pad(qres.astype(jnp.float32),
                     ((0, 0), (0, _sublanes(n_probes) - n_probes), (0, 0)))
    val, idx = _by_query_chunks(
        call, n_probes, probes.astype(jnp.int32), qres_p,
        qres_norms.astype(jnp.float32))
    return val[:nq, :k], idx[:nq, :k]


def fused_ivf_topk(probes, qres, qres_norms, list_data, row_norms,
                   list_indices, k: int, pad_tile: Optional[int] = None,
                   clamp: bool = True, vmem_budget: Optional[int] = None,
                   interpret: bool = False):
    """Fused probe-gather + scan + top-k for the IVF families.

    probes [nq, P] int32; qres [nq, P, rot] (per-probe query residual for
    ivf_pq's decoded cache, or the query replicated for flat scans);
    qres_norms [nq, P] = ||q_res||² (the per-probe base making distances
    comparable across probes); list_data [L, pad, rot] (fp32 or bf16 —
    upcast in-kernel, fp32 accumulation); row_norms [L, pad] fp32;
    list_indices [L, pad] int32 with -1 padding. Returns
    ``(distances [nq, k], ids [nq, k])`` ascending squared-L2, -1 ids
    where fewer than k valid candidates were probed.

    The [nq, P, pad] candidate slab never exists in HBM: each probed slab tile is DMA'd to VMEM (scalar-prefetch block
    index) and merged straight into the query's resident top-k carry.
    ``pad_tile`` must divide the list layout's pad exactly (default: the
    VMEM-budget solve, ``plan_fused_ivf_tile``); ``clamp`` applies
    ivf_flat's max(d, 0) exact-L2 clamp (ivf_pq's ADC space is unclamped)."""
    if k > 1024:
        raise ValueError(
            f"fused_ivf_topk is a small-k kernel (k={k} > 1024); "
            "use the XLA engines")
    list_pad = list_data.shape[1]
    if pad_tile is None:
        pad_tile = plan_fused_ivf_tile(
            list_pad, list_data.shape[2], k,
            jnp.dtype(list_data.dtype).itemsize, vmem_budget)
    if list_pad % pad_tile:
        raise ValueError(
            f"pad_tile={pad_tile} does not divide list_pad={list_pad}")
    return _fused_ivf_topk_pallas(probes, qres, qres_norms, list_data,
                                  row_norms, list_indices, int(k),
                                  int(pad_tile), bool(clamp),
                                  bool(interpret))


# ---------------------------------------------------- fused pq-lut top-k


def fused_pq_vmem_bytes(pad_tile: int, pq_dim: int, book: int, pq_len: int,
                        k: int) -> int:
    """VMEM that Mosaic allocates for one fused PQ grid step: the
    double-buffered blocks — subspace-major query and center rows, the
    resident [pq_len, pq_dim, book] codebooks and [pq_dim, book] norms,
    the [pq_dim, pad_tile] uint8 code block, the 8-list id rows, the
    8-query carry pair — plus the LUT and int32 code scratch, one
    subspace's [book, pad_tile] one-hot, the per-component LUT terms,
    the distance rows and the running-merge set. Public for the C001
    calibration audit."""
    kp = _kp(k)
    bl, ptl = _lanes(book), _lanes(pad_tile)
    dsub = _sublanes(pq_dim)
    blocks = (2 * _sublanes(pq_len) * _lanes(pq_dim) * 4
              + pq_len * dsub * bl * 4 + dsub * bl * 4
              + round_up_to(pq_dim, 32) * ptl + 8 * ptl * 4
              + 2 * 8 * kp * 4)
    return (2 * blocks + 3 * dsub * bl * 4 + dsub * ptl * 4
            + _sublanes(book) * ptl * 4 + 4 * 8 * ptl * 4 + 8 * 32 * kp)


def plan_fused_pq_tile(list_pad: int, pq_dim: int, book: int, pq_len: int,
                       k: int, vmem_budget: Optional[int] = None) -> int:
    """Code-slab row tile for ``fused_pq_topk`` — the largest legal tile
    fitting the VMEM budget, exactly like ``plan_fused_ivf_tile``."""
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    return _plan_slab_tile(list_pad, lambda pt: fused_pq_vmem_bytes(
        pt, pq_dim, book, pq_len, k) <= budget)


def fused_pq_workspace_bytes(nq: int, n_probes: int, rot: int,
                             n_lists: int, list_pad: int, pq_dim: int,
                             book: int, pq_len: int, k: int,
                             pad_tile: Optional[int] = None) -> int:
    """HBM-side workspace of one fused PQ (LUT-engine) dispatch: the
    packed code slab counted twice (staged + kernel operand, same CPU
    interpreter measurement / TPU over-prediction note as
    ``fused_ivf_workspace_bytes``), the rotated queries and centers, the
    codebook norms, the masked id copy, the [nq, kp] outputs, and one
    grid step's block set. No per-probe LUT or candidate slab appears —
    that is the point of the fusion. Public for the C001 audit."""
    if pad_tile is None:
        pad_tile = plan_fused_pq_tile(list_pad, pq_dim, book, pq_len, k)
    kp = _kp(k)
    return (2 * n_lists * list_pad * pq_dim
            + n_lists * list_pad * 4
            + (nq + n_lists) * rot * 4
            + pq_dim * book * 4
            + nq * kp * 8
            + fused_pq_vmem_bytes(pad_tile, pq_dim, book, pq_len, k))


def _fused_pq_topk_kernel(probes_ref, q_ref, c_ref, cb_ref, cbn_ref,
                          codes_ref, ids_ref, val_ref, idx_ref, lut_s,
                          codes_s, *, k: int, kp: int, n_probes: int):
    """One (query, probe, slab-tile) step of the LUT engine, entirely
    on-chip: build this probe's LUT from the residual and the resident
    codebooks, accumulate per-code contributions across subspaces, merge
    into the top-k carry. The per-probe LUT and the code slab never exist
    in HBM. Mosaic has no per-row gather lowering, so each subspace's LUT
    lookup is a [1, book] × one-hot [book, pt] MXU product (exact: every
    product is a LUT entry times 1 or 0). Operands arrive subspace-major
    (residual [pq_len, pq_dim], codebooks [pq_len, pq_dim, book], codes
    [pq_dim, pt]) so every in-kernel read is a whole row."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    r = pl.program_id(2)
    lrow = pl.ds(probes_ref[i * n_probes + j] % 8, 1)
    res = q_ref[0] - c_ref[0]  # [pq_len, pq_dim] residual vs probed center
    pq_len, pq_dim = res.shape
    base = jnp.sum(jnp.sum(res * res, axis=1, keepdims=True), axis=0,
                   keepdims=True)  # [1, 1] ||q_res||² (the ADC base term)
    dots = None
    for l in range(pq_len):
        # subspace s's residual component l, as a [pq_dim, 1] column
        col = _as_column(res[l:l + 1, :], jnp.inf)
        term = col * cb_ref[l]  # [pq_dim, book]
        dots = term if dots is None else dots + term
    lut_s[...] = cbn_ref[...] - 2.0 * dots  # [pq_dim, book]
    codes_s[...] = codes_ref[0].astype(jnp.int32)  # [pq_dim, pt]
    book = lut_s.shape[1]
    book_id = jax.lax.broadcasted_iota(jnp.int32, (book, 1), 0)

    def body(s, acc):
        onehot = (book_id == codes_s[pl.ds(s, 1), :]).astype(jnp.float32)
        return acc + jax.lax.dot_general(
            lut_s[pl.ds(s, 1), :], onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)  # [1, pt]

    pt = codes_s.shape[1]
    d = base + jax.lax.fori_loop(0, pq_dim, body,
                                 jnp.zeros((1, pt), jnp.float32))
    ids = ids_ref[lrow, :]
    d = jnp.where(ids < 0, jnp.inf, d)
    tv, ti = _extract_topk(d, ids, k, kp)
    _merge_row(val_ref, idx_ref, i % 8, (j == 0) & (r == 0), tv, ti, k, kp)


@functools.partial(jax.jit,
                   static_argnames=("k", "pad_tile", "interpret"))
def _fused_pq_topk_pallas(probes, q_rot, centers_rot, codebooks, cb_norms,
                          list_codes, list_indices, k: int, pad_tile: int,
                          interpret: bool):
    nq, n_probes = probes.shape
    n_lists, list_pad, _ = list_codes.shape
    pq_dim, book, pq_len = codebooks.shape
    pt = pad_tile
    n_r = list_pad // pt
    kp = _kp(k)

    def subspace_major(x):  # [n, pq_dim·pq_len] -> [n, pq_len, pq_dim]
        x = x.astype(jnp.float32)[:, :pq_dim * pq_len]
        return x.reshape(-1, pq_dim, pq_len).transpose(0, 2, 1)

    c_t = subspace_major(centers_rot)
    cb_t = codebooks.astype(jnp.float32).transpose(2, 0, 1)
    codes_t = list_codes.transpose(0, 2, 1)  # [L, pq_dim, pad]

    def call(probes_c, q_c):
        nqc = probes_c.shape[0]
        slot = lambda i, j, probes: probes[i * n_probes + j]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nqc, n_probes, n_r),
            in_specs=[
                pl.BlockSpec((1, pq_len, pq_dim),
                             lambda i, j, r, probes: (i, 0, 0)),
                pl.BlockSpec((1, pq_len, pq_dim),
                             lambda i, j, r, probes: (
                                 slot(i, j, probes), 0, 0)),
                # codebooks + norms: whole-array blocks, revisited every step
                pl.BlockSpec((pq_len, pq_dim, book),
                             lambda i, j, r, probes: (0, 0, 0)),
                pl.BlockSpec((pq_dim, book), lambda i, j, r, probes: (0, 0)),
                pl.BlockSpec((1, pq_dim, pt),
                             lambda i, j, r, probes: (
                                 slot(i, j, probes), 0, r)),
                pl.BlockSpec((8, pt),
                             lambda i, j, r, probes: (
                                 slot(i, j, probes) // 8, r)),
            ],
            out_specs=(
                pl.BlockSpec((8, kp), lambda i, j, r, probes: (i // 8, 0)),
                pl.BlockSpec((8, kp), lambda i, j, r, probes: (i // 8, 0))),
            scratch_shapes=[pltpu.VMEM((pq_dim, book), jnp.float32),
                            pltpu.VMEM((pq_dim, pt), jnp.int32)],
        )
        return pl.pallas_call(
            functools.partial(_fused_pq_topk_kernel, k=k, kp=kp,
                              n_probes=n_probes),
            out_shape=(jax.ShapeDtypeStruct((nqc, kp), jnp.float32),
                       jax.ShapeDtypeStruct((nqc, kp), jnp.int32)),
            grid_spec=grid_spec,
            interpret=interpret,
        )(probes_c.reshape(-1), q_c, c_t, cb_t,
          cb_norms.astype(jnp.float32), codes_t, list_indices)

    val, idx = _by_query_chunks(call, n_probes, probes.astype(jnp.int32),
                                subspace_major(q_rot))
    return val[:nq, :k], idx[:nq, :k]


def fused_pq_topk(probes, q_rot, centers_rot, codebooks, cb_norms,
                  list_codes, list_indices, k: int, pad_tile: Optional[int] = None,
                  vmem_budget: Optional[int] = None, interpret: bool = False):
    """Fused PQ LUT build + code gather + accumulate + top-k (ivf_pq's
    LUT regime without the per-probe candidate slab in HBM).

    Restricted to ``pq_bits=8`` PER_SUBSPACE codebooks: the packed code
    bytes ARE the codes (no unpack shuffle in-kernel). probes [nq, P];
    q_rot [nq, rot]; centers_rot [L, rot]; codebooks [pq_dim, book,
    pq_len] with cb_norms [pq_dim, book] = ||codebook row||²; list_codes
    [L, pad, pq_dim] uint8; list_indices [L, pad] int32, -1 padding.
    Returns ascending ADC squared-L2 ``(distances [nq, k], ids [nq, k])``."""
    if k > 1024:
        raise ValueError(
            f"fused_pq_topk is a small-k kernel (k={k} > 1024); "
            "use the XLA engines")
    n_lists, list_pad, n_code_bytes = list_codes.shape
    pq_dim, book, pq_len = codebooks.shape
    if n_code_bytes != pq_dim:
        raise ValueError(
            f"fused_pq_topk requires pq_bits=8 (one byte per code); got "
            f"{n_code_bytes} code bytes for pq_dim={pq_dim}")
    if pad_tile is None:
        pad_tile = plan_fused_pq_tile(list_pad, pq_dim, book, pq_len, k,
                                      vmem_budget)
    if list_pad % pad_tile:
        raise ValueError(
            f"pad_tile={pad_tile} does not divide list_pad={list_pad}")
    return _fused_pq_topk_pallas(probes, q_rot, centers_rot, codebooks,
                                 cb_norms, list_codes, list_indices,
                                 int(k), int(pad_tile), bool(interpret))


# ------------------------------------------------ fused cagra beam search
#
# The graph-traversal analog of the fused scan+select engines: one grid
# step per query, the whole beam walk INSIDE the kernel. The itopk beam
# state (distances, global ids, expanded flags) lives in the fori_loop
# carry — VMEM/vector registers for the entire traversal — instead of
# round-tripping through HBM as the XLA path's [nq, itopk + W·D] concat
# does every hop. Graph and dataset stay HBM-resident (``ANY`` memory
# space); seed rows are gathered via the scalar-prefetched seed table,
# and each hop's parent/target rows via in-kernel ``make_async_copy``
# with data-dependent row indices (the beam's picks exist only on-chip,
# so unlike the IVF probes they cannot be grid block indices — the
# prefetch pattern's dynamic-index continuation). Semantics are exactly
# ``cagra._search_jit``'s: same parent pick, same dedup-before-merge
# masks, same stable merge order — the XLA fallback stays bit-checked
# (tests/test_pallas_fused.py pins interpret-mode bit-parity).


def _extract_topk_flagged(work, ci, cf, k: int, kp: int):
    """``_extract_topk`` carrying a per-entry flag (CAGRA's "already
    expanded as a parent" bit, int32 0/1 — Mosaic cannot carry bool
    vectors through a loop): k rounds of (min, argmin, mask) where the
    winning lane's id AND flag are pulled out by masked reductions —
    first-occurrence tie-break, i.e. exactly the order a stable ascending
    ``lax.sort`` of the same row would produce, which is what keeps the
    in-kernel merge bit-compatible with the XLA beam body's concat+sort."""
    tb = work.shape[0]
    out_col = jax.lax.broadcasted_iota(jnp.int32, (tb, kp), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, work.shape, 1)

    def body(r, carry):
        work, vals, idxs, flags = carry
        a = jnp.argmin(work, axis=1)
        m = jnp.min(work, axis=1)
        sel_lane = lane == a[:, None]
        src = jnp.min(jnp.where(sel_lane, ci, jnp.iinfo(jnp.int32).max),
                      axis=1)
        fl = jnp.max(jnp.where(sel_lane, cf, 0), axis=1)
        # +inf extraction sentinel (see _extract_topk): exhausted rows
        # emit the -1 null id with a clear flag
        alive = m != jnp.inf
        src = jnp.where(alive, src, -1)
        fl = jnp.where(alive, fl, 0)
        sel = out_col == r
        vals = jnp.where(sel, m[:, None], vals)
        idxs = jnp.where(sel, src[:, None], idxs)
        flags = jnp.where(sel, fl[:, None], flags)
        work = jnp.where(sel_lane, jnp.inf, work)
        return work, vals, idxs, flags

    vals0 = jnp.full((tb, kp), jnp.inf, jnp.float32)
    idxs0 = jnp.full((tb, kp), -1, jnp.int32)
    flags0 = jnp.zeros((tb, kp), jnp.int32)
    _, vals, idxs, flags = jax.lax.fori_loop(
        0, k, body, (work, vals0, idxs0, flags0))
    return vals, idxs, flags


def _dup_of_earlier(col, row):
    """[1, w] mask: entry j of ``row`` equals an entry i < j (``col`` is
    the same values as a column)."""
    w = row.shape[1]
    earlier = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
               < jax.lax.broadcasted_iota(jnp.int32, (w, w), 1))
    return jnp.max(jnp.where((col == row) & earlier, 1, 0), axis=0,
                   keepdims=True) > 0


def fused_cagra_vmem_bytes(ct: int, dim: int, itopk: int, width: int,
                           degree: int, n_seeds: int) -> int:
    """TRUE VMEM live set of one fused cagra grid step: the [ct, dim]
    candidate-row gather scratch (+ its working copy through the dot),
    the per-chunk dot/distance/id lanes, the query row, the beam carry
    (dist/id/flag ×itopk-pad, plus the extraction working set over the
    [kp + ct] merge concat), the dedup masks ([wd, kp] + [wd, wd]
    bools), the graph-row scratch, and the seed/target id lanes. The
    itemized accounting ``plan_fused_cagra_tile`` solves against —
    public for the obs.costs C001 calibration audit."""
    kp = _kp(itopk)
    wd = width * degree
    return (ct * dim * 8          # gather scratch + f32 working copy
            + ct * 24             # dots / distances / chunk id lanes
            + dim * 8             # query row (+ residual temp)
            + kp * 40             # carry + extraction accumulators
            + (kp + ct) * 18      # merge concat (d/id/fl, work copy)
            + wd * (kp + wd)      # dedup membership masks (bool)
            + wd * 12 + n_seeds * 12   # target/seed id lanes + masks
            + width * degree * 4)      # graph-row scratch (int32)


def plan_fused_cagra_tile(itopk: int, width: int, degree: int, dim: int,
                          n_seeds: int,
                          vmem_budget: Optional[int] = None) -> int:
    """The candidate-chunk tile for ``fused_cagra_topk``: how many
    gathered rows (seed or expansion targets) stream through the VMEM
    scratch per merge. Solved from the VMEM budget via
    ``core.resources.solve_vmem_tiles`` — the chunk rows are the outer
    axis (8-aligned sublanes), the feature dim the inner — then capped
    at the widest stream the walk ever scores (max(W·D, n_seeds),
    rounded up to sublanes): a larger scratch would just sit empty."""
    from raft_tpu.core.resources import solve_vmem_tiles

    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    kp = _kp(itopk)
    wd = width * degree
    fixed = (dim * 8 + kp * 40 + kp * 18
             + wd * (kp + wd) + wd * 12 + n_seeds * 12
             + width * degree * 4)
    ct, _ = solve_vmem_tiles(
        budget,
        cell_bytes=8,
        outer_bytes=24 + 18,   # id/dist lanes + merge-concat share
        inner_bytes=0,
        inner_max=round_up_to(max(dim, 1), 128),
        fixed_bytes=fixed,
        outer_cap=256,
    )
    cap = round_up_to(max(wd, n_seeds, 8), 8)
    return max(8, min(int(ct), cap))


def fused_cagra_workspace_bytes(nq: int, n: int, dim: int, degree: int,
                                itopk: int, width: int, n_seeds: int,
                                k: int, ct: Optional[int] = None) -> int:
    """HBM-side TEMP workspace of one fused cagra dispatch. Deliberately
    small: dataset and graph enter the kernel as ``ANY``-memory-space
    operands and are DMA'd row-by-row in place — they are ARGUMENTS, not
    staged temporaries, which is the point of the design (every other
    fused family pays a staged slab copy; the beam walk touches too
    little of the slab per query to justify one). What remains: the
    padded seed table twice (scalar-prefetch copy + the VMEM-blocked
    vector side), the query/norm rows, the pre-slice [nq, kp] val/idx
    outputs, and one grid step's VMEM block set. Calibrated against the
    AOT CPU-interpreter compile's ``temp_size_in_bytes`` (C001,
    graftcheck ``--costs``)."""
    if ct is None:
        ct = plan_fused_cagra_tile(itopk, width, degree, dim, n_seeds)
    kp = _kp(itopk)
    sp = round_up_to(max(n_seeds, 1), ct)
    return (nq * (dim * 4 + 4)
            + 2 * nq * sp * 4
            + nq * kp * 8
            + fused_cagra_vmem_bytes(ct, dim, itopk, width, degree,
                                     n_seeds))


def _fused_cagra_kernel(seeds_sref, seeds_ref, q_ref, qn_ref, data_ref,
                        graph_ref, val_ref, idx_ref, vec_s, g_s, sem, *,
                        itopk: int, kp: int, width: int, degree: int,
                        dg: int, max_iter: int, ct: int, n_seeds: int):
    """One query's whole beam walk. Carry = (buf_d, buf_ids, buf_fl,
    done), all [1, kp] rows (done [1, 1]) resident on-chip; HBM is
    touched only by the per-row gather DMAs and the final [1, kp] result
    write.

    Dedup against the visited set is two small membership compares over
    the buffer-RESIDENT ids ([kp, wd] + [wd, wd]) — the buffer is
    dup-free and monotone under the merge so its flags are a complete
    visited set (see cagra.py) — not the XLA path's full-width
    [nq, wd, itopk] one-hot compare materialized per hop in HBM. A
    masked (dup or invalid) target still has its row gathered — the id
    mask sends its distance to +inf, so only the DMA is wasted.

    Tie-break note: merges extract by first-occurrence argmin, matching
    the XLA body's stable concat-sort exactly; the SEED init orders
    equal-distance distinct ids by seed position where
    ``merge_topk_dedup_flagged`` orders them by id — unobservable unless
    two distinct rows tie bitwise at the itopk boundary. Duplicate seed
    ids collapse identically (first copy kept, flags all clear)."""
    i = pl.program_id(0)
    row = pl.ds(i % 8, 1)  # this query's sublane of the 8-query blocks
    wd = width * degree
    per_row = g_s.shape[1] // dg  # graph rows packed per 128-lane row
    sp = seeds_ref.shape[1]  # seed table padded to a whole number of chunks
    imax = jnp.iinfo(jnp.int32).max
    lane_kp = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)

    q_row = q_ref[row, :]  # [1, dim]
    qn = qn_ref[row, :]  # [1, 1]

    def gather_rows(get_id, count):
        """DMA ``count`` dataset rows (row ids from ``get_id(j)``) into
        the scratch, serially — correctness first; overlap is a measured
        probe follow-up."""
        def body(j, carry):
            cp = pltpu.make_async_copy(
                data_ref.at[pl.ds(get_id(j), 1), :],
                vec_s.at[pl.ds(j, 1), :], sem)
            cp.start()
            cp.wait()
            return carry
        jax.lax.fori_loop(0, count, body, 0)

    def score_chunk(ids_chunk, n_rows):
        """[1, n_rows] minimized squared-L2 of the gathered scratch rows —
        the exact ``gathered_distances`` arithmetic (HIGHEST-precision
        dot, fp32 norms, max(…, 0) clamp), invalid ids → +inf."""
        v = vec_s[...]
        if n_rows < ct:
            v = v[:n_rows]
        dots = jax.lax.dot_general(
            q_row, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)  # [1, rows]
        vn = jnp.sum(v * v, axis=-1, keepdims=True)  # [rows, 1]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_rows), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_rows), 1))
        vn = jnp.sum(jnp.where(eye, vn, 0.0), axis=0, keepdims=True)
        d = jnp.maximum(qn + vn - 2.0 * dots, 0.0)
        return jnp.where(ids_chunk < 0, jnp.inf, d)

    def merge(carry, cd, ci, cf):
        bd, bi, bf = carry
        work = jnp.concatenate([bd, cd], axis=1)
        wi = jnp.concatenate([bi, ci], axis=1)
        wf = jnp.concatenate([bf, cf], axis=1)
        return _extract_topk_flagged(work, wi, wf, itopk, kp)

    # ---- seed phase: dedup-mask the full seed row, then stream chunks
    # of seed rows through the scratch into the carry (flags all clear —
    # merge_topk_dedup_flagged's init semantics)
    sv = seeds_ref[row, :]  # [1, sp] (pad lanes are -1)
    if sp > 1:
        sv = jnp.where(_dup_of_earlier(_as_column(sv, imax), sv), -1, sv)
    carry = (jnp.full((1, kp), jnp.inf, jnp.float32),
             jnp.full((1, kp), -1, jnp.int32),
             jnp.zeros((1, kp), jnp.int32))
    for c in range(sp // ct):
        base = c * ct
        nr = min(ct, sp - base)
        gather_rows(
            lambda j: jnp.maximum(seeds_sref[i * sp + base + j], 0), nr)
        ids_c = sv[:, base:base + nr]
        cd = score_chunk(ids_c, nr)
        carry = merge(carry, cd, ids_c, jnp.zeros((1, nr), jnp.int32))

    # ---- traversal: beam state rides the fori_loop carry; a done query
    # freezes (bit-compatible with the XLA while_loop's all-done exit,
    # which also only ever freezes per-query state)
    wdp = round_up_to(wd, ct)
    n_tc = wdp // ct
    lane_wd = jax.lax.broadcasted_iota(jnp.int32, (1, wdp), 1)

    def step(_, state):
        buf_d, buf_ids, buf_fl, done = state
        # pickup_next_parents: best `width` unexpanded entries, by
        # iterated min + first-index-of-min (== lax.top_k's
        # lowest-index-first tie order)
        cand = jnp.where((buf_fl > 0) | (buf_ids < 0), jnp.inf, buf_d)
        targets, valids = [], []
        for w in range(width):
            m = jnp.min(cand, axis=1, keepdims=True)  # [1, 1]
            a = jnp.min(jnp.where(cand == m, lane_kp, imax), axis=1,
                        keepdims=True)
            sel = lane_kp == a
            valid_w = jnp.isfinite(m) & (done == 0)  # [1, 1]
            pid = jnp.min(jnp.where(sel, buf_ids, imax), axis=1,
                          keepdims=True)
            pid = jnp.where(valid_w, pid, -1)
            buf_fl = jnp.where(sel & valid_w, 1, buf_fl)
            cand = jnp.where(sel, jnp.inf, cand)
            # expand: DMA the parent's graph row (clamped like the XLA
            # gather), mask an invalid parent's targets to -1
            pid0 = jnp.maximum(pid, 0)
            cp = pltpu.make_async_copy(
                graph_ref.at[pl.ds(jnp.max(pid0) // per_row, 1), :],
                g_s.at[pl.ds(w, 1), :], sem)
            cp.start()
            cp.wait()
            grow = g_s[w:w + 1, :]
            if per_row > 1:  # pick the parent's segment of the packed row
                slot = pid0 % per_row
                seg = grow[:, :dg]
                for sl in range(1, per_row):
                    seg = jnp.where(slot == sl, grow[:, sl * dg:(sl + 1) * dg],
                                    seg)
                grow = seg
            targets.append(jnp.where(valid_w, grow[:, :degree], -1))
            valids.append(valid_w)
        newly_done = jnp.where(valids[0], 0, 1)
        t0 = (jnp.concatenate(targets, axis=1) if width > 1
              else targets[0])  # [1, wd]
        # visited-set test against the RESIDENT buffer + earlier-target
        # dedup (parents sharing neighbors), before any distance math
        in_buf = jnp.max(jnp.where(_as_column(buf_ids, imax) == t0, 1, 0),
                         axis=0, keepdims=True) > 0
        if wd > 1:
            in_buf = in_buf | _dup_of_earlier(_as_column(t0, imax), t0)
        t1 = jnp.where(in_buf, -1, t0)
        t1p = (jnp.concatenate(
            [t1, jnp.full((1, wdp - wd), -1, jnp.int32)], axis=1)
            if wdp > wd else t1)

        # score + merge, chunk by chunk (streaming top-k == one stable
        # sort of the full concat — the merge keeps survivor order)
        merged = (buf_d, buf_ids, buf_fl)
        for c in range(n_tc):
            base = c * ct

            def tid(j, base=base):
                raw = jnp.min(jnp.where(lane_wd == base + j, t1p, imax))
                return jnp.maximum(raw, 0)

            gather_rows(tid, ct)
            ids_c = t1p[:, base:base + ct]
            cd = score_chunk(ids_c, ct)
            merged = merge(merged, cd, ids_c, jnp.zeros((1, ct), jnp.int32))

        keep = done > 0
        buf_d = jnp.where(keep, buf_d, merged[0])
        buf_ids = jnp.where(keep, buf_ids, merged[1])
        buf_fl = jnp.where(keep, buf_fl, merged[2])
        return buf_d, buf_ids, buf_fl, jnp.maximum(done, newly_done)

    buf_d, buf_ids, _, _ = jax.lax.fori_loop(
        0, max_iter, step, (*carry, jnp.zeros((1, 1), jnp.int32)))
    val_ref[row, :] = buf_d
    idx_ref[row, :] = buf_ids


@functools.partial(jax.jit, static_argnames=("k", "itopk", "width",
                                             "max_iter", "ct", "interpret"))
def _fused_cagra_pallas(queries, dataset, graph, seed_ids, q_norms,
                        k: int, itopk: int, width: int, max_iter: int,
                        ct: int, interpret: bool):
    nq, dim = queries.shape
    n, degree = graph.shape
    n_seeds = seed_ids.shape[1]
    kp = _kp(itopk)
    sp = round_up_to(max(n_seeds, 1), ct)
    seeds = jnp.pad(seed_ids.astype(jnp.int32),
                    ((0, 0), (0, sp - n_seeds)), constant_values=-1)
    # a row DMA moves whole 128-lane tiles: zero-pad the feature dim (exact
    # — zeros add nothing to a dot or a norm), and pack the graph so each
    # 128-lane row holds 128/dg whole neighbor lists (dg = the degree
    # rounded up to a divisor of 128, or to whole tiles past 128)
    dim_p = _lanes(dim)
    dataset = dataset.astype(jnp.float32)
    queries = queries.astype(jnp.float32)
    if dim_p != dim:
        dataset = jnp.pad(dataset, ((0, 0), (0, dim_p - dim)))
        queries = jnp.pad(queries, ((0, 0), (0, dim_p - dim)))
    dg = (1 << (degree - 1).bit_length()) if degree < 128 else _lanes(degree)
    per_row = max(128 // dg, 1)
    graph = jnp.pad(graph.astype(jnp.int32),
                    ((0, round_up_to(n, per_row) - n), (0, dg - degree)),
                    constant_values=-1).reshape(-1, dg * per_row)

    def call(seeds_c, qf, qn):
        nqc = seeds_c.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nqc,),
            in_specs=[
                # the seed table again, VMEM-blocked: the vector side of
                # the same scalars the prefetch ref feeds to the gather
                # DMAs. Per-query rows arrive as 8-query blocks.
                pl.BlockSpec((8, sp), lambda i, seeds: (i // 8, 0)),
                pl.BlockSpec((8, dim_p), lambda i, seeds: (i // 8, 0)),
                pl.BlockSpec((8, 1), lambda i, seeds: (i // 8, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=(pl.BlockSpec((8, kp), lambda i, seeds: (i // 8, 0)),
                       pl.BlockSpec((8, kp), lambda i, seeds: (i // 8, 0))),
            scratch_shapes=[
                pltpu.VMEM((ct, dim_p), jnp.float32),
                pltpu.VMEM((width, dg * per_row), jnp.int32),
                pltpu.SemaphoreType.DMA,
            ],
        )
        return pl.pallas_call(
            functools.partial(_fused_cagra_kernel, itopk=itopk, kp=kp,
                              width=width, degree=degree, dg=dg,
                              max_iter=max_iter, ct=ct, n_seeds=n_seeds),
            out_shape=(jax.ShapeDtypeStruct((nqc, kp), jnp.float32),
                       jax.ShapeDtypeStruct((nqc, kp), jnp.int32)),
            grid_spec=grid_spec,
            interpret=interpret,
        )(seeds_c.reshape(-1), seeds_c, qf, qn, dataset, graph)

    val, idx = _by_query_chunks(
        call, sp, seeds, queries, q_norms.astype(jnp.float32).reshape(nq, 1))
    return val[:nq, :k], idx[:nq, :k]


def fused_cagra_topk(queries, dataset, graph, seed_ids, k: int,
                     itopk: int, width: int = 1, max_iter: int = 0,
                     ct: Optional[int] = None,
                     vmem_budget: Optional[int] = None,
                     interpret: bool = False):
    """Fused CAGRA beam search + top-k: the whole greedy graph walk runs
    inside one Pallas kernel per query, beam state VMEM-resident across
    iterations. Returns ``(distances [nq, k], ids [nq, k])`` ascending
    squared-L2 (the minimized quantity — the caller applies the
    L2SqrtExpanded epilogue), ids -1 where the walk surfaced fewer than
    k nodes.

    Semantics match ``cagra.search_core`` at the same resolved
    ``(itopk, width, max_iter)`` bit-for-bit (L2 metrics, unfiltered,
    fp32): same seed dedup, parent pick, visited-set masks, and stable
    merge order. ``max_iter=0`` applies the search-plan auto heuristic.
    ``ct`` is the candidate-chunk tile (default: the VMEM-budget solve,
    ``plan_fused_cagra_tile``); ``interpret=True`` runs the Mosaic
    interpreter (CPU CI)."""
    queries = jnp.asarray(queries)
    dataset = jnp.asarray(dataset)
    graph = jnp.asarray(graph)
    seed_ids = jnp.asarray(seed_ids)
    itopk = max(int(itopk), int(k))
    if itopk > 1024:
        raise ValueError(
            f"fused_cagra_topk is a small-beam kernel (itopk={itopk} > "
            "1024); use the XLA engine")
    width = max(int(width), 1)
    max_iter = int(max_iter)
    if max_iter <= 0:
        import numpy as np
        max_iter = int(np.clip(itopk // width + 10, 16, 200))
    degree = graph.shape[1]
    n_seeds = seed_ids.shape[1]
    if ct is None:
        ct = plan_fused_cagra_tile(itopk, width, degree, queries.shape[1],
                                   n_seeds, vmem_budget)
    q_norms = jnp.sum(queries.astype(jnp.float32) ** 2, -1)
    return _fused_cagra_pallas(queries, dataset, graph, seed_ids, q_norms,
                               int(k), itopk, width, max_iter, int(ct),
                               bool(interpret))


# ------------------------------------------------- cross-chip ring shift
#
# The RDMA leg of the sharded ring top-k merge (parallel/comms.py
# ring_topk_merge): each device pushes one fixed-shape candidate block to
# its +1 ring neighbor over ICI via ``make_async_remote_copy``, so the
# transfer overlaps the local lex-merge of the block received last step
# instead of round-tripping through an XLA collective slab. Same contract
# as ``Comms.shift(x, 1)``: device r's output is device (r-1)'s input.
# Like the fused scan kernels it has no chip measurement: only
# ``merge_mode="ring"`` takes it; ``auto`` merges by the tree.

_RING_COLLECTIVE_ID = 1


def _ring_shift_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis: str,
                       size: int, barrier: bool):
    my = jax.lax.axis_index(axis)
    right = jax.lax.rem(my + 1, size)
    left = jax.lax.rem(my + size - 1, size)
    if barrier:
        # neighbor barrier: both neighbors must have entered the kernel
        # (output buffers live) before any RDMA lands; signal each, wait
        # for each of them to signal us. Hardware-only — the Mosaic
        # interpreter has no barrier semaphore and steps devices in
        # lockstep, so the hazard cannot arise there.
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(bar, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(bar, 2)
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=o_ref, send_sem=send_sem, recv_sem=recv_sem,
        device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)
    rdma.start()
    rdma.wait()


def pallas_ring_shift(x, axis: str, size: int, interpret: bool = False):
    """+1 ring rotation of a per-device block inside ``shard_map`` via a
    remote-DMA Pallas kernel — the ``Comms.shift`` analog that bypasses
    the XLA collective scheduler so the copy can overlap the caller's
    compute. ``x`` is the local block (any dtype/shape, kept whole in
    ``ANY`` memory space); returns the left neighbor's block."""
    return pl.pallas_call(
        functools.partial(_ring_shift_kernel, axis=axis, size=int(size),
                          barrier=not interpret),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(
            collective_id=_RING_COLLECTIVE_ID),
        interpret=interpret,
    )(x)
