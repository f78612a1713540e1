"""Timing primitives shared by every benchmark entry point.

Measured on one TPU v5e (chip_smoke.py's fence phase, PR 21): 32 chained
8192² bf16 matmuls took 189.40 ms to ``jax.block_until_ready`` and
189.73 ms to a host readback of one element of the result —
``block_until_ready`` waits for the device to finish. So the fence is
``block_until_ready`` and nothing is subtracted from a timed loop. A
timed region still (a) dispatches its repeats asynchronously with ONE
fence at the end (throughput mode), and (b) never contains a host→device
upload of benchmark inputs.

This is the TPU analog of the CUDA-event timing fixture the reference
benches use (``/root/reference/cpp/bench/prims/common/benchmark.hpp:84-105``):
events fence device work without stalling the pipeline per iteration.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "fence",
    "fence_index",
    "prepare",
    "time_dispatches",
    "time_latency_chained",
    "chain_perturb",
    "last_info",
]

# Populated by time_dispatches / time_latency_chained after every
# measurement: {"samples_s": [per-round per-iter seconds]}. One sample
# per fenced round (len == the rounds argument), so callers can report
# percentiles instead of a mean that hides host-contention skew.
# Contract: read IMMEDIATELY after the timing call returns — the next
# timing call (including any nested inside a dispatch fn) overwrites it.
last_info: dict = {"samples_s": []}


def fence(out: Any) -> int:
    """Block until every execution producing ``out``'s array leaves has
    completed (``jax.block_until_ready``). Returns the number of leaves
    fenced (0 = pure-host data)."""
    leaves = [l for l in jax.tree_util.tree_leaves(out)
              if isinstance(l, jax.Array)]
    jax.block_until_ready(leaves)
    return len(leaves)


def fence_index(index: Any) -> None:
    """Fence a built ANN index: every jax.Array it holds (indexes are
    plain classes; a slotted/NamedTuple type without ``__dict__``
    degrades to fencing nothing rather than raising)."""
    attrs = getattr(index, "__dict__", {})
    fence(list(attrs.values()))


def prepare(x: Any) -> Any:
    """Move inputs to device OUTSIDE the timed region and fence so the
    transfer cannot leak into timing."""
    def _put(a):
        if isinstance(a, np.ndarray):
            return jax.device_put(a)
        return a  # device arrays stay put; non-arrays pass through

    out = jax.tree_util.tree_map(_put, x)
    fence(out)
    return out


def time_dispatches(dispatch: Callable[[], Any], iters: int = 5,
                    warmup: int = 1) -> float:
    """Wall seconds per ``dispatch()``: ``iters`` asynchronous dispatches,
    one fence at the end (throughput mode — the chip stays saturated by
    in-flight work, matching the reference's thread-pool throughput mode,
    raft_ann_benchmarks.md:154)."""
    for _ in range(warmup):
        fence(dispatch())
    t0 = time.perf_counter()
    fence([dispatch() for _ in range(iters)])
    per_iter = (time.perf_counter() - t0) / iters
    last_info["samples_s"] = [per_iter]
    return per_iter


def time_latency_chained(step: Callable[[Any], Any], x0: Any,
                         iters: int = 8, rounds: int = 1) -> float:
    """Per-call device latency of ``iters`` calls serialized by a data
    dependency (each call's input depends on the previous call's output;
    the caller encodes it, e.g. via :func:`chain_perturb`), one fence per
    round.

    ``rounds > 1`` repeats the measurement, each round fenced
    separately, leaving one per-iter sample per round in
    ``last_info["samples_s"]`` (read immediately — the next timing call
    overwrites it) and returning their mean."""
    fence(step(x0))  # warm / compile
    samples = []
    for _ in range(max(int(rounds), 1)):
        t0 = time.perf_counter()
        out = x0
        for _ in range(iters):
            out = step(out)
        fence(out)
        samples.append((time.perf_counter() - t0) / iters)
    last_info["samples_s"] = list(samples)
    return sum(samples) / len(samples)


def chain_perturb(x: jax.Array, prev_out: Any) -> jax.Array:
    """Return ``x`` plus a zero-valued contribution of ``prev_out``'s
    first leaf — value-identical to ``x`` but data-dependent on the
    previous call, forcing serial on-device execution in chained-latency
    loops."""
    leaves = [l for l in jax.tree_util.tree_leaves(prev_out)
              if isinstance(l, jax.Array)]
    if not leaves:
        return x
    p = jnp.ravel(leaves[0])[0]
    # inf/NaN probes (top-k pad values, bf16 overflow) must not poison the
    # chain: inf * 0 = NaN would turn every later input into NaN
    z = (jnp.where(jnp.isfinite(p), p, 0) * 0).astype(x.dtype)
    return x + z
