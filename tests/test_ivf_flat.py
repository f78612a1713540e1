"""IVF-Flat tests — recall against exact brute-force ground truth, the
reference's acceptance pattern (cpp/test/neighbors/ann_ivf_flat.cuh:
build→(serialize→load)→search, assert recall ≥ floor)."""

import io

import numpy as np
import pytest

from raft_tpu import Resources
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import brute_force, ivf_flat
from raft_tpu.stats import neighborhood_recall


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    db = rng.standard_normal((5000, 32)).astype(np.float32)
    q = rng.standard_normal((100, 32)).astype(np.float32)
    return db, q


@pytest.fixture(scope="module")
def gt(data):
    db, q = data
    _, idx = brute_force.knn(q, db, k=10, metric="sqeuclidean")
    return np.asarray(idx)


def test_build_shapes(data):
    db, _ = data
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=32))
    assert index.n_lists == 32
    assert index.size == len(db)
    assert int(np.asarray(index.list_sizes).sum()) == len(db)
    # balanced lists
    sizes = np.asarray(index.list_sizes)
    assert sizes.max() <= 4 * len(db) / 32


@pytest.mark.parametrize("n_probes,floor", [(4, 0.4), (8, 0.6), (32, 0.999)])
@pytest.mark.slow
def test_recall_increases_with_probes(data, gt, n_probes, floor):
    db, q = data
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=32))
    d, i = ivf_flat.search(index, q, 10, ivf_flat.SearchParams(n_probes=n_probes))
    recall = float(neighborhood_recall(np.asarray(i), gt))
    assert recall >= floor, f"recall {recall} < {floor} at n_probes={n_probes}"


@pytest.mark.slow
def test_full_probe_is_exact(data, gt):
    db, q = data
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=16))
    d, i = ivf_flat.search(index, q, 10, ivf_flat.SearchParams(n_probes=16))
    assert float(neighborhood_recall(np.asarray(i), gt)) >= 0.999
    # distances match brute force
    bf_d, _ = brute_force.knn(q, db, k=10, metric="sqeuclidean")
    np.testing.assert_allclose(np.asarray(d), np.asarray(bf_d), rtol=1e-3, atol=1e-3)


def test_inner_product(data):
    db, q = data
    dbn = db / np.linalg.norm(db, axis=1, keepdims=True)
    index = ivf_flat.build(
        dbn, ivf_flat.IndexParams(n_lists=16, metric="inner_product"))
    d, i = ivf_flat.search(index, q, 10, ivf_flat.SearchParams(n_probes=16))
    ip = q @ dbn.T
    want = np.argsort(-ip, 1)[:, :10]
    assert float(neighborhood_recall(np.asarray(i), want)) >= 0.999


def test_extend_matches_single_shot_lists(data):
    """Device-side extend must place rows/ids exactly where a from-scratch
    pack of the same rows would (the ivf_flat analog of the ivf_pq gate)."""
    from raft_tpu.neighbors import ivf_flat as fl

    db, _ = data
    params = fl.IndexParams(n_lists=12, add_data_on_build=False)
    base = fl.build(db, params)
    one = fl.extend(base, db)
    half = len(db) // 2
    two = fl.extend(base, db[:half])
    two = fl.extend(two, db[half:])
    assert two.size == one.size == len(db)
    np.testing.assert_array_equal(np.asarray(one.list_sizes),
                                  np.asarray(two.list_sizes))
    np.testing.assert_array_equal(np.asarray(one.list_indices),
                                  np.asarray(two.list_indices))
    np.testing.assert_array_equal(np.asarray(one.list_data),
                                  np.asarray(two.list_data))


def test_extend(data, gt):
    db, q = data
    half = len(db) // 2
    index = ivf_flat.build(db[:half], ivf_flat.IndexParams(n_lists=32))
    index = ivf_flat.extend(index, db[half:])
    assert index.size == len(db)
    d, i = ivf_flat.search(index, q, 10, ivf_flat.SearchParams(n_probes=32))
    assert float(neighborhood_recall(np.asarray(i), gt)) >= 0.999


def test_build_no_data_then_extend(data, gt):
    db, q = data
    params = ivf_flat.IndexParams(n_lists=32, add_data_on_build=False)
    index = ivf_flat.build(db, params)
    with pytest.raises(ValueError, match="no data"):
        ivf_flat.search(index, q, 10)
    index = ivf_flat.extend(index, db)
    d, i = ivf_flat.search(index, q, 10, ivf_flat.SearchParams(n_probes=32))
    assert float(neighborhood_recall(np.asarray(i), gt)) >= 0.999


def test_bitset_filter(data):
    db, q = data
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=16))
    # forbid the true top-1 of each query
    _, bf_i = brute_force.knn(q, db, k=1, metric="sqeuclidean")
    banned = np.unique(np.asarray(bf_i).ravel())
    filt = Bitset.create(len(db)).set(banned, value=False)
    d, i = ivf_flat.search(index, q, 10, ivf_flat.SearchParams(n_probes=16),
                           filter=filt)
    got = np.asarray(i)
    assert not np.isin(got, banned).any()


def test_serialize_roundtrip(data, gt):
    db, q = data
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=32))
    buf = io.BytesIO()
    ivf_flat.serialize(index, buf)
    buf.seek(0)
    index2 = ivf_flat.deserialize(buf)
    d1, i1 = ivf_flat.search(index, q, 10, ivf_flat.SearchParams(n_probes=8))
    d2, i2 = ivf_flat.search(index2, q, 10, ivf_flat.SearchParams(n_probes=8))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


def test_small_workspace_tiles(data, gt):
    db, q = data
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=32))
    small = Resources(workspace_limit_bytes=8_000_000)
    d, i = ivf_flat.search(index, q, 10, ivf_flat.SearchParams(n_probes=32),
                           res=small)
    assert float(neighborhood_recall(np.asarray(i), gt)) >= 0.999


def test_helpers_pack_unpack(data):
    db, _ = data
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=16))
    vecs = ivf_flat.helpers.unpack_list_data(index, 2)
    ids = ivf_flat.helpers.unpack_list_ids(index, 2)
    assert len(vecs) == len(ids) == int(np.asarray(index.list_sizes)[2])
    np.testing.assert_allclose(vecs, db[ids], rtol=1e-6)
    # overwrite list 2 with its first 3 vectors
    idx2 = ivf_flat.helpers.pack_list_data(index, 2, vecs[:3], ids[:3])
    assert int(np.asarray(idx2.list_sizes)[2]) == 3
    np.testing.assert_allclose(ivf_flat.helpers.unpack_list_data(idx2, 2),
                               vecs[:3], rtol=1e-6)


@pytest.mark.parametrize("dt", [np.int8, np.uint8])
def test_int8_dataset(dt, rng):
    """int8/uint8 datasets (reference: ivf_flat's dp4a paths support
    int8/uint8 natively — ivf_flat_interleaved_scan-inl.cuh:99-251); storage
    stays narrow (4x less scan bandwidth), math is f32."""
    lo = -120 if dt == np.int8 else 0
    db = rng.integers(lo, 120, (2000, 32)).astype(dt)
    q = rng.integers(lo, 120, (100, 32)).astype(dt)
    idx = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=16))
    assert idx.list_data.dtype == dt
    _, i = ivf_flat.search(idx, q, 5, ivf_flat.SearchParams(n_probes=16))
    ref = ((q.astype(np.float32)[:, None, :]
            - db.astype(np.float32)[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(i)[:, 0], ref.argmin(1))


def test_bf16_fast_scan(data, gt):
    """bf16 fine scan with exact fp32 norms matches the fp32 scan's recall
    at full probing (all lists probed → only scan precision differs)."""
    db, q = data
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=32),
                           res=Resources(seed=5))
    sp = ivf_flat.SearchParams(n_probes=32, scan_dtype="bfloat16")
    _, i = ivf_flat.search(index, q, 10, sp)
    assert float(neighborhood_recall(np.asarray(i), gt)) >= 0.99
    with pytest.raises(ValueError, match="bfloat16"):
        ivf_flat.search(index, q, 10,
                        ivf_flat.SearchParams(n_probes=4, scan_dtype="float16"))
