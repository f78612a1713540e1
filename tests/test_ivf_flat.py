"""IVF-Flat tests — recall against exact brute-force ground truth, the
reference's acceptance pattern (cpp/test/neighbors/ann_ivf_flat.cuh:
build→(serialize→load)→search, assert recall ≥ floor)."""

import io
import itertools

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


# ------------------------------- the plain probe-restricted reference


def _numpy_ivf_flat(index, q, k: int, n_probes: int, inner: bool, ctol):
    """IVF-Flat search in float64 numpy, independent of the jitted core:
    the index's own centres, each query's ``n_probes`` nearest lists,
    exact distances over those lists' valid rows and the overflow block,
    the k best. Lists whose coarse distance lies within ``ctol`` [nq] of
    the ``n_probes``-th are a tie at float32, which the program may break
    either way: each query gets one answer per way of filling its probe
    set from the tied lists, as (distances [k], ids [k], the distance of
    every row id the probes saw)."""
    centers = np.asarray(index.centers, np.float64)
    data = np.asarray(index.list_data, np.float64)
    ids = np.asarray(index.list_indices)
    sizes = np.asarray(index.list_sizes)
    over_ok = np.asarray(index.overflow_indices) >= 0
    o_rows = np.asarray(index.overflow_data, np.float64)[over_ok]
    o_ids = np.asarray(index.overflow_indices)[over_ok]
    qd = q.astype(np.float64)
    coarse = (-(qd @ centers.T) if inner
              else ((qd[:, None, :] - centers[None]) ** 2).sum(-1))
    answers = []
    for qi, c, tol in zip(qd, coarse, ctol):
        edge = np.sort(c)[n_probes - 1]
        sure = np.flatnonzero(c < edge - tol).tolist()
        tied = np.flatnonzero(np.abs(c - edge) <= tol).tolist()
        alts = []
        for pick in itertools.combinations(tied, n_probes - len(sure)):
            p = sure + list(pick)
            rows = np.concatenate([data[l, :sizes[l]] for l in p] + [o_rows])
            rid = np.concatenate([ids[l, :sizes[l]] for l in p] + [o_ids])
            d = -(rows @ qi) if inner else ((rows - qi) ** 2).sum(-1)
            order = np.argsort(d, kind="stable")[:k]
            sign = -1.0 if inner else 1.0
            alts.append((sign * d[order], rid[order],
                         dict(zip(rid.tolist(), (sign * d).tolist()))))
        answers.append(alts)
    return answers


def _same_answer(d, i, ref, tol) -> bool:
    """One query's answer is the reference's: each distance within ``tol``
    of the reference's at its rank and of its id's own distance, and ids
    that differ only where their distances tie with the k-th place."""
    ref_d, ref_i, seen = ref
    if not (np.abs(d - ref_d) <= tol).all():
        return False
    if any(j not in seen or abs(seen[j] - dj) > tol
           for j, dj in zip(i.tolist(), d)):
        return False
    return all(abs(seen[j] - ref_d[-1]) <= tol
               for j in set(i.tolist()) ^ set(ref_i.tolist()))


@pytest.fixture(scope="module")
def wide():
    rng = np.random.default_rng(30)
    db = rng.standard_normal((20000, 128)).astype(np.float32)
    q = rng.standard_normal((200, 128)).astype(np.float32)
    return db, q


@pytest.fixture(scope="module")
def wide_index(wide):
    db, _ = wide
    return {m: ivf_flat.build(db, ivf_flat.IndexParams(n_lists=64, metric=m))
            for m in ("sqeuclidean", "inner_product")}


@pytest.mark.parametrize("metric", ["sqeuclidean", "inner_product"])
@pytest.mark.parametrize("n_probes", [8, 64])
def test_search_matches_numpy_reference(wide, wide_index, metric, n_probes):
    """``ivf_flat.search`` returns the plain reference's neighbours among
    the lists it probes, at L2Expanded and InnerProduct, probing a few
    lists or all of them.

    Tolerance: the program computes ``‖q‖² + ‖v‖² − 2·q·v`` (or ``q·v``)
    in float32, so each distance may differ from the float64 one by a few
    float32 roundings of ``‖q‖² + ‖v‖²`` (for the inner product,
    ``‖q‖·‖v‖``); 1e-5 of that scale is about 80 ulps. Ids may differ
    only where the two candidates' float64 distances lie within that
    tolerance of each other at the k-th place, a tie at float32. The
    coarse distances to the centres carry the same rounding (1e-5 of
    ``‖q‖² + ‖c‖²``, or ``‖q‖·‖c‖``): lists that close to the last
    probed one are a tie too, and the answer of any probe set the tie
    allows is accepted."""
    db, q = wide
    index = wide_index[metric]
    inner = metric == "inner_product"
    k = 10
    d, i = ivf_flat.search(index, q, k,
                           ivf_flat.SearchParams(n_probes=n_probes))
    d, i = np.asarray(d, np.float64), np.asarray(i)
    qn = (q.astype(np.float64) ** 2).sum(1)
    vn = float((db.astype(np.float64) ** 2).sum(1).max())
    cn = float((np.asarray(index.centers, np.float64) ** 2).sum(1).max())
    tol = 1e-5 * (np.sqrt(qn * vn) if inner else qn + vn)
    ctol = 1e-5 * (np.sqrt(qn * cn) if inner else qn + cn)
    answers = _numpy_ivf_flat(index, q, k, n_probes, inner, ctol)
    for r, alts in enumerate(answers):
        assert any(_same_answer(d[r], i[r], ref, tol[r]) for ref in alts), r
    # a tie at the probe edge is rare: nearly every query has one answer
    assert sum(len(alts) > 1 for alts in answers) <= len(q) // 20


@pytest.mark.parametrize("case,engine,want", [
    ("float32", "xla", "highest"),
    ("bfloat16_screen", "xla", "default"),
    ("int8", "xla", "default"),
    ("fused", "pallas", "highest"),
])
def test_search_counts_its_engine_and_precision(data, rng, case, engine,
                                                want, monkeypatch):
    """Each search counts one dispatch in
    ``raft_tpu_ivf_flat_scan_plans_total{engine,precision}`` with the
    engine the explain record names (its ``pallas`` counted as ``fused``)
    and the precision in its plan; on the XLA engine that precision is the
    one the probe scan's contraction is lowered at."""
    from raft_tpu.obs.metrics import REGISTRY

    db, q = data
    params = ivf_flat.SearchParams(n_probes=8)
    if case == "bfloat16_screen":
        params.scan_dtype = "bfloat16"
    if case == "int8":
        db = rng.integers(-120, 120, db.shape).astype(np.int8)
        q = rng.integers(-120, 120, q.shape).astype(np.int8)
    if case == "fused":
        monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
        params.scan_mode = "pallas"
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=16))
    lowered = []
    real = ivf_flat._search_jit

    def spy(*args):
        lowered.append(real.lower(*args))
        return real(*args)

    monkeypatch.setattr(ivf_flat, "_search_jit", spy)
    plans = REGISTRY.get("raft_tpu_ivf_flat_scan_plans_total")
    before = dict((key, c.value) for key, c in plans.collect())
    _, ids, rec = ivf_flat.search(index, q, 10, params, explain=True)
    after = dict((key, c.value) for key, c in plans.collect())
    label = "fused" if rec.engine == "pallas" else rec.engine
    assert (rec.engine, rec.plan["precision"]) == (engine, want)
    assert after[(label, want)] - before.get((label, want), 0) == 1
    assert sum(after.values()) - sum(before.values()) == 1
    assert (np.asarray(ids) >= 0).all()
    if engine == "xla":
        dots = [line for line in lowered[0].as_text().splitlines()
                if "stablehlo.dot_general" in line]
        # the coarse step, then the probe scan
        assert "precision = [HIGHEST" in dots[0]
        assert ("precision = [HIGHEST" in dots[1]) == (want == "highest")
