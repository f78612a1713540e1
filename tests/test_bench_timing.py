"""bench/timing.py — fences and timed loops (CPU-checked).

The fence is ``jax.block_until_ready`` (measured on a v5e to wait for
the device as long as a readback does), and timed loops divide the
fenced wall time by the iteration count with nothing subtracted; the
same code path produces the on-TPU artifacts."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.bench.timing import (chain_perturb, fence, prepare,
                                   time_dispatches, time_latency_chained)

pytestmark = pytest.mark.fast


def test_fence_handles_mixed_trees():
    x = jnp.arange(6.0).reshape(2, 3)
    fence({"a": x, "b": [x.astype(jnp.int32), None, "str"], "c": 3})
    fence(None)  # no leaves: no-op


def test_fence_counts_device_leaves_and_results_are_ready():
    x = jax.jit(lambda a: a @ a.T)(jnp.ones((64, 64)))
    assert fence({"x": x, "host": np.zeros(3), "n": 1}) == 1
    assert fence([np.zeros(3), "str"]) == 0
    assert x.is_ready()


def test_prepare_moves_to_device_and_roundtrips():
    h = np.random.default_rng(0).standard_normal((4, 8)).astype(np.float32)
    d = prepare({"x": h, "meta": "keep"})
    assert isinstance(d["x"], jax.Array)
    assert d["meta"] == "keep"
    np.testing.assert_array_equal(np.asarray(d["x"]), h)


def test_time_dispatches_positive_and_runs_fn():
    calls = []
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))

    def dispatch():
        calls.append(1)
        return f(x)

    dt = time_dispatches(dispatch, iters=3, warmup=1)
    assert dt > 0
    assert len(calls) == 4  # warmup + iters


def test_time_dispatches_subtracts_nothing():
    # a dispatch that takes 20 ms on the host: per-iteration time is the
    # fenced wall time over iters, never less (no round-trip correction)
    def dispatch():
        time.sleep(0.02)
        return jnp.ones((4,))

    assert time_dispatches(dispatch, iters=3, warmup=0) >= 0.02


def test_chain_perturb_is_value_identity_but_dependent():
    x = jnp.arange(8.0)
    out = (jnp.ones((3,)), jnp.arange(3))
    y = chain_perturb(x, out)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    y2 = chain_perturb(x, None)  # no leaves: passthrough
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(x))


def test_time_latency_chained_serializes_and_returns_positive():
    f = jax.jit(lambda q: q @ q.T)
    q0 = jnp.ones((4, 4))

    def step(q):
        return chain_perturb(q0, f(q))

    dt = time_latency_chained(step, q0, iters=4)
    assert dt > 0


def test_time_latency_chained_rounds_collects_samples():
    from raft_tpu.bench.timing import last_info

    f = jax.jit(lambda q: q @ q.T)
    q0 = jnp.ones((4, 4))

    def step(q):
        return chain_perturb(q0, f(q))

    dt = time_latency_chained(step, q0, iters=4, rounds=5)
    samples = last_info["samples_s"]
    assert len(samples) == 5
    assert all(s > 0 for s in samples)
    # the return value is the mean of the recorded samples
    assert dt == pytest.approx(sum(samples) / len(samples))
    # a single-round call resets the samples to exactly one entry
    time_latency_chained(step, q0, iters=4)
    assert len(last_info["samples_s"]) == 1


def test_percentile_fields_shape():
    """The bench extras' latency percentile helper: nearest-rank keys the
    artifact schema promises (p50/p95/p99)."""
    from raft_tpu.serving.stats import percentiles

    pct = percentiles([0.001, 0.002, 0.040])  # one contended round
    assert set(pct) == {"p50", "p95", "p99"}
    assert pct["p99"] == 0.040  # the outlier survives; a mean hides it
