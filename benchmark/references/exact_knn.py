"""Plain exact k-nearest-neighbour reference (squared L2).

Independent of ``raft_tpu``: it reads only the rows the benchmark made
from ``--seed``. Distances are computed in blocks of rows and of queries
in ``jnp`` float32 at ``precision="highest"`` in the expanded form
||q||^2 + ||x||^2 - 2 q.x, which selects ``k + margin`` candidates per
block; the candidates' distances are then recomputed on the host in
float64 in the direct form
sum((q - x)^2), which has no cancellation, and the best ``k`` kept. The
same function at ``precision="high"`` stands in for a program that
computes in the precision below the configuration's (the control): the
products are taken in three bfloat16 passes, as the TPU's ``HIGH``
precision takes them, written out so that the CPU computes the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")


def _split(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _products(q, xb, precision: str):
    """q @ xb.T in float32: at ``highest``, or in the three bfloat16
    passes hi.hi + hi.lo + lo.hi of ``high``."""
    if precision == "highest":
        return jnp.matmul(q, xb.T, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}")
    (qh, ql), (xh, xl) = _split(q), _split(xb)

    def mm(a, b):
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return mm(qh, xh) + (mm(qh, xl) + mm(ql, xh))


@functools.partial(jax.jit, static_argnames=("kc", "start", "n_blocks",
                                              "block", "precision"))
def _scan_shard(q, x, kc: int, start: int, n_blocks: int, block: int,
                precision: str):
    """Best ``kc`` of the rows ``[start, start + n_blocks * block)`` of
    ``x`` for each query by the expanded form: (values [nq, kc], local row
    ids [nq, kc])."""
    nq = q.shape[0]
    qn = jnp.sum(q * q, axis=1)

    def step(carry, b):
        best_v, best_i = carry
        xb = jax.lax.dynamic_slice_in_dim(x, start + b * block, block,
                                          axis=0)
        d = (qn[:, None] + jnp.sum(xb * xb, axis=1)[None, :]
             - 2.0 * _products(q, xb, precision))
        v, i = jax.lax.top_k(-d, kc)
        v = jnp.concatenate([best_v, -v], axis=1)
        i = jnp.concatenate(
            [best_i, i.astype(jnp.int32) + start + b * block], axis=1)
        nv, sel = jax.lax.top_k(-v, kc)
        return (-nv, jnp.take_along_axis(i, sel, axis=1)), None

    init = (jnp.full((nq, kc), jnp.inf, jnp.float32),
            jnp.full((nq, kc), -1, jnp.int32))
    (v, i), _ = jax.lax.scan(step, init, jnp.arange(n_blocks))
    return v, i


def _gather(shards, ids):
    """Rows of global ``ids`` [nq, m] (numpy) from ``shards``, a list of
    (device array, first global row), as one host array [nq, m, d]. Each
    shard is read at every id, clipped into its rows, so the gather's
    shape follows ``ids.shape`` alone and not the seed: a shape that
    changed with the seed would compile anew in every run."""
    dim = shards[0][0].shape[1]
    flat = ids.reshape(-1)
    out = np.zeros((len(flat), dim), np.float32)
    for arr, lo in shards:
        n = arr.shape[0]
        hit = (flat >= lo) & (flat < lo + n)
        if hit.any():
            local = jax.device_put(np.clip(flat - lo, 0, n - 1)
                                   .astype(np.int32), list(arr.devices())[0])
            out[hit] = np.asarray(arr[local])[hit]
    return out.reshape(ids.shape + (dim,))


def true_distances(shards, queries: np.ndarray, ids: np.ndarray,
                   q_block: int = 1024) -> np.ndarray:
    """Direct-form squared distances of each query to each of its ``ids``
    [nq, m], in float64 on the host; an id outside the rows reads +inf.
    The ids go in blocks of ``q_block`` rows, the last one padded, so
    every gather has one shape."""
    n_rows = sum(a.shape[0] for a, _ in shards)
    ids = np.asarray(ids, np.int64)
    ok = (ids >= 0) & (ids < n_rows)
    safe = np.where(ok, ids, 0)
    out = np.empty(ids.shape, np.float64)
    for s in range(0, len(queries), q_block):
        blk = safe[s:s + q_block]
        full = np.pad(blk, ((0, q_block - len(blk)), (0, 0)))
        rows = _gather(shards, full)[:len(blk)].astype(np.float64)
        diff = rows - queries[s:s + q_block, None, :].astype(np.float64)
        out[s:s + q_block] = np.einsum("qmd,qmd->qm", diff, diff)
    return np.where(ok, out, np.inf)


def knn(shards, queries: np.ndarray, k: int, precision: str = "highest",
        block: int = 65536, q_block: int = 1024, margin: int = 0):
    """Exact kNN of ``queries`` (host [nq, d] float32) over ``shards``, a
    list of (device array [rows, d], first global row). Returns
    ``(selected, ids, true)``: the expanded-form values at ``precision``
    of the best ``k`` [nq, k], their global ids, and their direct-form
    distances, all in the order of ``selected``. With ``margin`` > 0 the
    expanded form picks ``k + margin`` candidates and the direct form
    chooses the best ``k`` of them (the reference); with 0 the expanded
    form's own choice stands (the control)."""
    kc = k + margin
    # every shard's scans are dispatched before any is read back, so the
    # devices work at once
    launched = []
    for arr, lo in shards:
        dev = list(arr.devices())[0]
        n = arr.shape[0]
        b = min(block, n)
        n_full = (n // b) * b
        blocks = []
        for s in range(0, len(queries), q_block):
            q = jax.device_put(jnp.asarray(queries[s:s + q_block]), dev)
            parts = []
            if n_full:
                parts.append(_scan_shard(q, arr, min(kc, b), 0, n_full // b,
                                         b, precision))
            if n_full < n:
                parts.append(_scan_shard(q, arr, min(kc, n - n_full), n_full,
                                         1, n - n_full, precision))
            blocks.append(parts)
        launched.append((lo, blocks))
    cand_v, cand_i = [], []
    for lo, blocks in launched:
        cand_v.append(np.concatenate([np.concatenate(
            [np.asarray(v) for v, _ in parts], 1) for parts in blocks], 0))
        cand_i.append(np.concatenate([np.concatenate(
            [np.asarray(i) for _, i in parts], 1) for parts in blocks], 0)
            .astype(np.int64) + lo)
    v = np.concatenate(cand_v, 1)
    i = np.concatenate(cand_i, 1)
    order = np.argsort(v, axis=1, kind="stable")[:, :kc]
    v = np.take_along_axis(v, order, 1)
    i = np.take_along_axis(i, order, 1)
    true = true_distances(shards, queries, i, q_block)
    if margin:
        order = np.argsort(true, axis=1, kind="stable")[:, :k]
        v = np.take_along_axis(v, order, 1)
        i = np.take_along_axis(i, order, 1)
        true = np.take_along_axis(true, order, 1)
    return v[:, :k], i[:, :k], true[:, :k]
