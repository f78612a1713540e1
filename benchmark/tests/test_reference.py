"""The plain reference against a float64 numpy kNN at a tiny size, on
one and on several shards; and the control's precision."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen, harness

GEN = {"n_centers": 8, "intrinsic": 4, "spread": 1.5}


def numpy_knn(x, q, k):
    d = ((q[:, None, :].astype(np.float64) - x[None].astype(np.float64))
         ** 2).sum(-1)
    i = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, i, 1), i


@pytest.fixture(scope="module")
def data():
    x = datagen.make_rows(5, "base", 0, 4096, 16, GEN, 1024)
    q = np.asarray(datagen.make_rows(5, "queries", 0, 64, 16, GEN, 64))
    return x, q


def test_exact_matches_numpy(data):
    ref = harness.load_module("references", "exact_knn")
    x, q = data
    want_d, want_i = numpy_knn(np.asarray(x), q, 10)
    for shards in ([(x, 0)],
                   [(x[:1024], 0), (x[1024:3000], 1024), (x[3000:], 3000)]):
        _, ids, true = ref.knn(shards, q, 10, block=512, q_block=16,
                               margin=10)
        np.testing.assert_array_equal(ids, want_i)
        np.testing.assert_allclose(true, want_d, rtol=1e-12)


def test_true_distances_and_bad_ids(data):
    ref = harness.load_module("references", "exact_knn")
    x, q = data
    ids = np.array([[0, 5, -1, 4096]] * len(q))
    t = ref.true_distances([(x, 0)], q, ids)
    xn = np.asarray(x).astype(np.float64)
    assert t[3, 1] == pytest.approx(((q[3] - xn[5]) ** 2).sum())
    assert np.isinf(t[:, 2:]).all()


def test_high_is_three_bf16_passes(data):
    ref = harness.load_module("references", "exact_knn")
    x, q = data
    a = jnp.asarray(q)
    hi = np.asarray(ref._products(a, x, "high"), np.float64)
    exact = q.astype(np.float64) @ np.asarray(x).astype(np.float64).T
    top = np.asarray(ref._products(a, x, "highest"), np.float64)
    err_high = np.abs(hi - exact).max()
    err_top = np.abs(top - exact).max()
    assert err_high > 4 * err_top  # the control is measurably coarser
    assert err_high < 1e-3 * np.abs(exact).max()


def test_datagen_is_the_seed(data):
    x, _ = data
    again = datagen.make_rows(5, "base", 1024, 1024, 16, GEN, 1024)
    np.testing.assert_array_equal(np.asarray(x[1024:2048]),
                                  np.asarray(again))
    other = datagen.make_rows(2**40 + 5, "base", 0, 1024, 16, GEN, 1024)
    assert not np.array_equal(np.asarray(x[:1024]), np.asarray(other))
    on = datagen.make_rows(5, "base", 0, 1024, 16, GEN, 1024,
                           device=jax.devices()[1])
    assert list(on.devices())[0] == jax.devices()[1]


def test_fixed_collection_queries_from_seed():
    """A configuration with ``base_seed`` keeps one collection for every
    seed and draws only the queries from the seed."""
    cfg = {"dataset": {"rows": 2048, "dim": 16, "queries": 64,
                       "chunk": 1024, "base_seed": 3},
           "assumed": {"generator": GEN}}
    dev = jax.devices()[:1]
    a, b = (harness.Data(cfg, s, dev, lambda n: _Null()) for s in (7, 8))
    np.testing.assert_array_equal(np.asarray(a.shards[0][0]),
                                  np.asarray(b.shards[0][0]))
    assert not np.array_equal(a.queries, b.queries)
    np.testing.assert_array_equal(
        a.queries, np.asarray(datagen.make_rows(7, "queries", 0, 64, 16, GEN,
                                                64, model_seed=3)))


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("pq_bits", [4, 5, 8])
def test_adc_unpack_hand_worked(pq_bits):
    """Codes laid out as one little-endian bit string, pq_bits each."""
    adc = harness.load_module("references", "ivf_pq_adc")
    rng = np.random.default_rng(pq_bits)
    codes = rng.integers(0, 1 << pq_bits, (7, 8))
    bits = ((codes[:, :, None] >> np.arange(pq_bits)) & 1).reshape(7, -1)
    packed = (bits.reshape(7, -1, 8) << np.arange(8)).sum(-1)
    assert (adc.unpack(packed.astype(np.uint8), 8, pq_bits) == codes).all()


def test_adc_distance_hand_worked():
    """One list, one id, identity rotation: ||q - c - decode||^2 by hand;
    an id in no list reads inf."""
    adc = harness.load_module("references", "ivf_pq_adc")
    view = {"centers": np.array([[1.0, 2.0, 0.0, 0.0]]),
            "rotation": np.eye(4), "n_rows": 3, "pq_dim": 2, "pq_bits": 8,
            "per_cluster": False,
            # subspace s, code c -> [c + s, -c]
            "codebooks": np.array([[[c + s, -c] for c in range(256)]
                                   for s in range(2)], np.float32),
            "list_codes": np.array([[[3, 1], [0, 0]]], np.uint8),
            "list_indices": np.array([[2, -1]]), "list_sizes": np.array([1]),
            "overflow_codes": np.zeros((0, 2), np.uint8),
            "overflow_labels": np.zeros(0, np.int64),
            "overflow_indices": np.zeros(0, np.int64)}
    q = np.array([[5.0, 0.0, 3.0, 1.0]])
    # decode = [3, -3, 2, -1]; q - c - decode = [1, 1, 1, 2]
    d = adc.distances(view, q, np.array([[2, 0]]))
    assert d[0, 0] == pytest.approx(7.0)
    assert d[0, 1] == np.inf
