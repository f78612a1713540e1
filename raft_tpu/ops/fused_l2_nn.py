"""Fused L2 nearest-neighbor (1-NN) — the core of k-means assignment.

Reference: ``fusedL2NN`` / ``fusedL2NNMinReduce`` (distance/fused_l2_nn-inl.cuh
:76,:181) — computes, for each row of x, the argmin (and min value) of the L2
distance to rows of y *without materializing the full distance matrix*, via a
KVP min-reduce fused into the pairwise kernel's epilogue.

TPU-native design: tile over x rows; per tile, the expanded-L2 matmul's
[tile, n_y] output is consumed immediately by a min/argmin reduction that XLA
fuses into the matmul epilogue, so only [tile, n_y] (not [m, n_y]) ever exists
in HBM. For k-means shapes (n_y = n_clusters, small), a tile of x rows keeps
the MXU saturated while the reduction stays on the VPU. The tile loop is a
``lax.map`` (sequential, compiled once).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.ops.distance import l2_expanded, row_norms_sq
from raft_tpu.utils.shape import balanced_tile, cdiv


def choose_tile_rows(m: int, n: int, budget_bytes: int) -> int:
    tile = max(1, budget_bytes // (8 * max(n, 1) * 4))
    tile = min(tile, m, 65536)
    return balanced_tile(m, tile, 128)


def planned_peak_bytes(m: int, n: int, budget_bytes: int) -> int:
    """The peak live set ``choose_tile_rows`` solves for: ~8 concurrent
    fp32 [tile, n] intermediates of the expanded-L2 + argmin chain at the
    planned row tile (public for the obs.costs calibration audit)."""
    return choose_tile_rows(m, n, budget_bytes) * max(n, 1) * 8 * 4


@functools.partial(jax.jit, static_argnames=("sqrt", "tile"))
def _fused_l2_nn_jit(x, y, x_norms, y_norms, sqrt: bool, tile: int):
    m, k = x.shape

    def tile_body(args):
        xt, xnt = args
        # Expanded L2 with the matmul on the MXU; argmin fused into epilogue.
        d = l2_expanded(xt, y, sqrt=False, x_norms=xnt, y_norms=y_norms)
        idx = jnp.argmin(d, axis=1)
        val = jnp.min(d, axis=1)
        return val, idx

    if m <= tile:
        val, idx = tile_body((x, x_norms))
    else:
        n_tiles = cdiv(m, tile)
        pad = n_tiles * tile - m
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        xnp_ = jnp.pad(x_norms, (0, pad))
        vals, idxs = jax.lax.map(
            tile_body,
            (xp.reshape(n_tiles, tile, k), xnp_.reshape(n_tiles, tile)),
        )
        val = vals.reshape(-1)[:m]
        idx = idxs.reshape(-1)[:m]
    if sqrt:
        val = jnp.sqrt(val)
    return val, idx.astype(jnp.int32)


#: public traceable-core name — the cross-package contract for clients that
#: compose the fused kernel inside their own jit (kmeans E-step, graftcheck
#: jaxpr audit).  Keeps ``_fused_l2_nn_jit`` module-private (R004).
fused_l2_nn_core = _fused_l2_nn_jit


def fused_l2_nn_argmin(
    x,
    y,
    sqrt: bool = False,
    x_norms: Optional[jax.Array] = None,
    y_norms: Optional[jax.Array] = None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """For each x row, the (min L2 distance, argmin index) into y's rows.

    API analog of ``fusedL2NNMinReduce`` (fused_l2_nn-inl.cuh:181) /
    ``pylibraft.distance.fused_l2_nn_argmin``.
    """
    res = ensure_resources(res)
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    xn = row_norms_sq(x) if x_norms is None else x_norms
    yn = row_norms_sq(y) if y_norms is None else y_norms
    tile = choose_tile_rows(x.shape[0], y.shape[0], res.workspace_limit_bytes)
    return _fused_l2_nn_jit(x, y, xn, yn, bool(sqrt), tile)


@functools.partial(jax.jit, static_argnames=("sqrt", "tile"))
def _masked_l2_nn_jit(x, y, x_norms, y_norms, adj, group_of_y, sqrt: bool,
                      tile: int):
    m, k = x.shape

    def tile_body(args):
        xt, xnt, adjt = args
        d = l2_expanded(xt, y, sqrt=False, x_norms=xnt, y_norms=y_norms)
        # adjt[i, g] says whether x-row i may match group g; expand to y rows
        allowed = jnp.take(adjt, group_of_y, axis=1)
        d = jnp.where(allowed, d, jnp.inf)
        return jnp.min(d, axis=1), jnp.argmin(d, axis=1)

    if m <= tile:
        val, idx = tile_body((x, x_norms, adj))
    else:
        n_tiles = cdiv(m, tile)
        pad = n_tiles * tile - m
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        xnp_ = jnp.pad(x_norms, (0, pad))
        adjp = jnp.pad(adj, ((0, pad), (0, 0)))
        vals, idxs = jax.lax.map(
            tile_body,
            (xp.reshape(n_tiles, tile, k), xnp_.reshape(n_tiles, tile),
             adjp.reshape(n_tiles, tile, adj.shape[1])),
        )
        val = vals.reshape(-1)[:m]
        idx = idxs.reshape(-1)[:m]
    if sqrt:
        val = jnp.sqrt(jnp.maximum(val, 0.0))
    return val, idx.astype(jnp.int32)


def masked_l2_nn_argmin(
    x,
    y,
    adj,
    group_idxs,
    sqrt: bool = False,
    x_norms: Optional[jax.Array] = None,
    y_norms: Optional[jax.Array] = None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Masked fused L2 1-NN (reference: distance/masked_nn.cuh).

    ``adj`` is a [m, num_groups] boolean adjacency; ``group_idxs``
    [num_groups] holds each group's *end* offset into y's rows (the
    reference's prefix-sum convention, masked_nn.cuh:49-57): group g spans
    y rows [group_idxs[g-1], group_idxs[g]). An x row with no allowed group
    gets distance inf and index 0. The mask is applied in the distance
    tile's epilogue, so the full matrix never reaches HBM — same fusion
    the reference gets from its masked kernel.
    """
    res = ensure_resources(res)
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    adj = jnp.asarray(adj, jnp.bool_)
    group_idxs = jnp.asarray(group_idxs, jnp.int32)
    # map each y row to its group id: counts of ends <= row index
    y_rows = jnp.arange(y.shape[0], dtype=jnp.int32)
    group_of_y = jnp.sum(y_rows[:, None] >= group_idxs[None, :],
                         axis=1).astype(jnp.int32)
    group_of_y = jnp.minimum(group_of_y, adj.shape[1] - 1)
    xn = row_norms_sq(x) if x_norms is None else x_norms
    yn = row_norms_sq(y) if y_norms is None else y_norms
    tile = choose_tile_rows(x.shape[0], y.shape[0], res.workspace_limit_bytes)
    return _masked_l2_nn_jit(x, y, xn, yn, adj, group_of_y, bool(sqrt), tile)
