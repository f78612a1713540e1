"""Shape/tile arithmetic (TPU analog of util/pow2_utils.cuh): lane-aligned
padding helpers used by the IVF list layouts and Pallas kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128  # TPU lane count: last-dim tiling unit
SUBLANES_F32 = 8


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up_to(n: int, multiple: int) -> int:
    return cdiv(n, multiple) * multiple


def balanced_tile(total: int, tile: int, multiple: int) -> int:
    """Balance a 1-d tile grid: split ``total`` evenly over the tile count
    a budget-derived ``tile`` implies, aligned up to ``multiple`` when that
    stays within the budget.

    Rounding a budget tile DOWN to the alignment multiple (the old
    pattern) turned total=10000 / tile=10000 into 9984 -> TWO tiles, the
    second 99.8% padding — double the scan work on the headline shape.
    Invariants: result <= max(tile, 1) (a [tile, ...] workspace budget is
    never exceeded — alignment yields to budget when tile < multiple),
    result * cdiv(total, result) - total < multiple * n_tiles (bounded
    padding), and total == 0 degrades to 1 (callers produce empty
    outputs, not a ZeroDivisionError)."""
    tile = max(tile, 1)
    if total <= tile:
        return max(total, 1)
    n_tiles = cdiv(total, tile)
    balanced = cdiv(total, n_tiles)
    aligned = round_up_to(balanced, multiple)
    return aligned if aligned <= tile else balanced


def pad_rows(x, target_rows: int, fill=0):
    """Pad a [n, ...] array to [target_rows, ...]. Host arrays pad on the
    host (numpy) so serving wrappers don't pay an eager device dispatch
    per call — the padded batch then rides the jit call's single
    transfer; device arrays pad on device as before."""
    n = x.shape[0]
    if n == target_rows:
        return x
    pad_widths = [(0, target_rows - n), *[(0, 0)] * (x.ndim - 1)]
    if isinstance(x, np.ndarray):
        return np.pad(x, pad_widths, constant_values=fill)
    return jnp.pad(x, pad_widths, constant_values=fill)


def as_query_array(queries, dtype=None):
    """Wrapper-side query normalization that KEEPS host inputs on the
    host: lists/numpy become a numpy array (validated/shaped for free),
    device arrays pass through; ``dtype`` casts on whichever side the
    data lives. The device transfer then happens once, inside the
    search's jit call, instead of as an eager ``jnp.asarray`` dispatch
    (+ a second eager pad) per serving call — each eager op is a
    separate runtime enqueue."""
    if isinstance(queries, jax.Array):
        return queries if dtype is None else queries.astype(dtype)
    queries = np.asarray(queries)
    if dtype is not None:
        queries = queries.astype(dtype, copy=False)
    return queries


def query_bucket(nq: int, max_bucket: int = 256) -> int:
    """Serving-latency batch bucket: round small query batches up to the
    next power of two (min 8) so repeated small-batch searches of varying
    size reuse ONE compiled program instead of recompiling per shape (the
    role of the reference's MULTI_CTA/MULTI_KERNEL small-batch modes,
    cagra_types.hpp:66-116 — on TPU the recompile, not the kernel shape,
    is what kills small-batch latency). Batches above ``max_bucket`` keep
    their exact size: throughput runs have stable shapes, and rounding
    10k → 16k would waste real compute."""
    if nq > max_bucket:
        return nq
    b = 8
    while b < nq:
        b *= 2
    return b
