"""Aux core subsystems: tracing ranges, interruptible sync, pallas kernel
(interpret mode) — reference: core/nvtx.hpp, core/interruptible.hpp,
distance/fused_l2_nn-inl.cuh."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.core import interruptible, tracing


def test_tracing_range_context_and_decorator():
    with tracing.range("test::scope"):
        x = jnp.ones((4,)) * 2

    @tracing.annotate("test::fn")
    def fn(a):
        return a + 1

    np.testing.assert_array_equal(np.asarray(fn(x)), 3.0)


def test_tracing_inside_jit():
    @jax.jit
    def f(a):
        with tracing.range("inner"):
            return a * 2

    assert float(f(jnp.float32(3.0))) == 6.0


def test_interruptible_synchronize_ready():
    x = jnp.ones((8, 8)) @ jnp.ones((8, 8))
    interruptible.synchronize(x)  # completes without raising


def test_interruptible_cancel():
    main_id = threading.get_ident()
    interruptible.cancel(main_id)
    with pytest.raises(interruptible.InterruptedException):
        interruptible.yield_now()
    # token cleared after raise: next sync passes
    interruptible.synchronize(jnp.ones((2,)))


def test_interruptible_cancel_from_other_thread():
    target_ready = threading.Event()
    result = {}

    def worker():
        result["tid"] = threading.get_ident()
        target_ready.set()
        try:
            while True:
                interruptible.yield_now()
                time.sleep(0.005)
        except interruptible.InterruptedException:
            result["cancelled"] = True

    t = threading.Thread(target=worker)
    t.start()
    target_ready.wait()
    interruptible.cancel(result["tid"])
    t.join(timeout=5)
    assert result.get("cancelled")


# ---------------------------------------------------------------------------
# operators / errors / resources_manager (core/operators.hpp, core/error.hpp,
# core/device_resources_manager.hpp)

def test_operators():
    from raft_tpu.core import operators as ops

    x = jnp.asarray([1.0, -2.0, 3.0])
    np.testing.assert_allclose(np.asarray(ops.sq_op(x)), [1, 4, 9])
    np.testing.assert_allclose(np.asarray(ops.abs_op(x)), [1, 2, 3])
    np.testing.assert_allclose(
        np.asarray(ops.compose_op(ops.sqrt_op, ops.abs_op)(x)),
        np.sqrt([1, 2, 3]), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ops.div_checkzero_op(x, jnp.asarray([1.0, 0.0, 2.0]))),
        [1.0, 0.0, 1.5])
    addc = ops.add_const_op(10.0)
    np.testing.assert_allclose(np.asarray(addc(x)), [11, 8, 13])
    mapped = ops.map_args_op(ops.add_op, ops.sq_op, ops.abs_op)
    np.testing.assert_allclose(np.asarray(mapped(x, x)), [2, 6, 12])


def test_errors():
    import pytest
    from raft_tpu.core import errors

    errors.expects(True, "fine")
    with pytest.raises(errors.LogicError):
        errors.expects(False, "boom")
    with pytest.raises(errors.LogicError):
        errors.fail("nope")
    assert issubclass(errors.LogicError, errors.RaftError)


def test_resources_manager_round_robin():
    from raft_tpu.core import resources_manager as rm

    rm.reset()
    rm.set_resources_per_device(3)
    got = [rm.get_resources() for _ in range(4)]
    assert got[0] is got[3]          # pool of 3 wraps around
    assert len({id(r) for r in got[:3]}) == 3
    # options are frozen after first hand-out (reference semantics)
    rm.set_resources_per_device(5)
    got2 = [rm.get_resources() for _ in range(5)]
    assert len({id(r) for r in got2}) == 3
    rm.reset()


# ---------------------------------------------------------------------------
# label / solver / spatial namespaces

def test_make_monotonic_and_unique():
    from raft_tpu import label

    labs = np.array([7, 3, 7, 9, 3, -1], np.int32)
    mono = np.asarray(label.make_monotonic(labs, max_labels=8))
    assert mono[0] == mono[2] and mono[1] == mono[4]
    assert set(mono[[0, 1, 3]]) == {0, 1, 2}
    assert mono[5] == -1
    uniq, n = label.get_unique_labels(labs[:-1], max_labels=8)
    assert int(n) == 3
    assert list(np.asarray(uniq)[:3]) == [3, 7, 9]


def test_merge_labels():
    from raft_tpu import label

    # a: {0,1},{2,3}; b: {1,2},{0},{3} → all four merge into one group
    a = np.array([0, 0, 1, 1], np.int32)
    b = np.array([0, 1, 1, 2], np.int32)
    out = np.asarray(label.merge_labels(a, b))
    assert len(set(out)) == 1
    # disjoint groups stay separate
    a = np.array([0, 0, 1, 1], np.int32)
    b = np.array([2, 2, 3, 3], np.int32)
    out = np.asarray(label.merge_labels(a, b))
    assert out[0] == out[1] and out[2] == out[3] and out[0] != out[2]


def test_lap_auction_matches_scipy(rng):
    from raft_tpu import solver

    for n in (5, 12):
        cost = rng.random((n, n)).astype(np.float32)
        assign, total = solver.solve(cost)
        ref_assign, ref_total = solver.solve_host(cost)
        assign = np.asarray(assign)
        assert (assign >= 0).all() and len(set(assign.tolist())) == n
        # auction is eps-optimal: within n*eps of the exact optimum
        assert float(total) <= ref_total + n * (1.0 / (n + 1)) + 1e-4


def test_spatial_namespace(rng):
    from raft_tpu import spatial

    db = rng.standard_normal((50, 8)).astype(np.float32)
    q = rng.standard_normal((5, 8)).astype(np.float32)
    d, i = spatial.knn.knn(db, q, k=3, metric="sqeuclidean")
    ref = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(i)[:, 0], ref.argmin(1))
    pts = np.radians([[51.5, -0.13], [48.86, 2.35]]).astype(np.float32)
    h = np.asarray(spatial.haversine_distance(pts, pts))
    assert h.shape == (2, 2) and h[0, 1] > 0


def test_device_ndarray_torch_interop():
    """pylibraft's cai_wrapper role: foreign-framework tensors (torch CPU)
    convert through device_ndarray/to_host without copying semantics
    surprises."""
    torch = pytest.importorskip("torch")
    from raft_tpu import common

    t = torch.arange(12, dtype=torch.float32).reshape(3, 4)
    a = common.device_ndarray(t)
    assert a.shape == (3, 4)
    np.testing.assert_array_equal(common.to_host(a), t.numpy())


def test_balanced_tile():
    """Tile-grid balancing: even splits, bounded padding, budget never
    exceeded, empty input degrades to 1 (shape.balanced_tile)."""
    from raft_tpu.utils.shape import balanced_tile, cdiv

    assert balanced_tile(10_000, 10_000, 128) == 10_000  # single tile
    assert balanced_tile(0, 4096, 128) == 1
    assert balanced_tile(5, 3, 8) == 3  # alignment yields to budget
    # budget tile below the multiple never inflates (workspace invariant)
    assert balanced_tile(1_000_000, 33, 128) <= 33
    assert balanced_tile(1_000_000, 1, 8) == 1
    for total, tile, mult in [(200_000, 131_072, 128), (10_000, 4_096, 8),
                              (131_073, 65_536, 128), (999, 1024, 128),
                              (1_000_000, 131_072, 128)]:
        t = balanced_tile(total, tile, mult)
        assert 1 <= t <= max(tile, 1)
        n_tiles = cdiv(total, t)
        assert n_tiles * t - total < mult * n_tiles + mult, (total, tile, t)
