"""IVF-PQ tests — recall against exact ground truth with PQ-compression-aware
floors, the reference's acceptance pattern (cpp/test/neighbors/ann_ivf_pq.cuh:
build→(serialize→load)→search, recall floor from search params + compression)."""

import io

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import brute_force, ivf_pq
from raft_tpu.ops.distance import DistanceType
from raft_tpu.stats import neighborhood_recall


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(1)
    # clustered data — PQ on pure iid gaussian is adversarially hard
    centers = rng.standard_normal((50, 32)) * 4.0
    labels = rng.integers(0, 50, 4000)
    db = (centers[labels] + rng.standard_normal((4000, 32))).astype(np.float32)
    q = (centers[rng.integers(0, 50, 100)]
         + rng.standard_normal((100, 32))).astype(np.float32)
    return db, q


@pytest.fixture(scope="module")
def gt(data):
    db, q = data
    _, idx = brute_force.knn(q, db, k=10, metric="sqeuclidean")
    return np.asarray(idx)


def test_build_shapes(data):
    db, _ = data
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=16, pq_bits=8)
    index = ivf_pq.build(db, params)
    assert index.n_lists == 32
    assert index.pq_dim == 16
    assert index.pq_len == 2  # rot_dim 32 / pq_dim 16
    assert index.size == len(db)
    assert index.codebooks.shape == (16, 256, 2)
    assert index.list_codes.shape[2] == 16 * 8 // 8
    # every row lives either in a list slot or in the overflow block
    n_over = int((np.asarray(index.overflow_indices) >= 0).sum())
    assert int(np.asarray(index.list_sizes).sum()) + n_over == len(db)
    # the padded-storage budget holds (VERDICT r2 #2)
    slots = (index.list_codes.shape[0] * index.list_codes.shape[1]
             + index.overflow_codes.shape[0])
    assert slots <= 1.5 * len(db) + 8 * index.n_lists


def test_rotation_orthonormal():
    import jax

    r = ivf_pq.make_rotation_matrix(jax.random.key(0), 48, 32, True)
    with jax.default_matmul_precision("highest"):
        rtr = np.asarray(r.T @ r)
    np.testing.assert_allclose(rtr, np.eye(32), atol=1e-5)


@pytest.mark.parametrize("pq_bits", [4, 5, 8])
def test_pack_unpack_roundtrip(pq_bits):
    rng = np.random.default_rng(0)
    pq_dim = 16 if pq_bits != 5 else 8 * 5  # pq_dim*pq_bits % 8 == 0
    codes = rng.integers(0, 1 << pq_bits, (64, pq_dim)).astype(np.uint8)
    packed = ivf_pq._pack_codes_np(codes, pq_bits)
    assert packed.shape == (64, pq_dim * pq_bits // 8)
    un = np.asarray(ivf_pq._unpack_codes(jnp.asarray(packed), pq_dim, pq_bits))
    np.testing.assert_array_equal(un, codes)


@pytest.mark.parametrize("kind", [ivf_pq.CodebookGen.PER_SUBSPACE,
                                  ivf_pq.CodebookGen.PER_CLUSTER])
@pytest.mark.slow
def test_recall(data, gt, kind):
    db, q = data
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=16, pq_bits=8,
                                codebook_kind=kind)
    index = ivf_pq.build(db, params)
    d, i = ivf_pq.search(index, q, 10, ivf_pq.SearchParams(n_probes=32))
    recall = float(neighborhood_recall(np.asarray(i), gt))
    # bf16 decoded-scan cache costs ~1e-3 recall vs the f32 LUT path
    assert recall >= 0.79, f"recall {recall} ({kind.name})"
    d32, i32 = ivf_pq.search(
        index, q, 10, ivf_pq.SearchParams(n_probes=32,
                                          scan_cache_dtype=jnp.float32))
    recall32 = float(neighborhood_recall(np.asarray(i32), gt))
    assert recall32 >= 0.8, f"f32-cache recall {recall32} ({kind.name})"


def test_recall_increases_with_probes(data, gt):
    db, q = data
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=16)
    index = ivf_pq.build(db, params)
    recalls = []
    for n_probes in (2, 8, 32):
        _, i = ivf_pq.search(index, q, 10, ivf_pq.SearchParams(n_probes=n_probes))
        recalls.append(float(neighborhood_recall(np.asarray(i), gt)))
    assert recalls[0] <= recalls[1] <= recalls[2] + 0.02
    # full-probe recall = pure ADC quantization quality; the coarse
    # quantizer's balance polish (kmeans_balanced.target_balance_cv)
    # trades a sliver of quantization error for bounded list sizes, so
    # the floor sits just under the historical 0.80
    assert recalls[2] >= 0.77


def test_bf16_lut(data, gt):
    db, q = data
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=16)
    index = ivf_pq.build(db, params)
    sp = ivf_pq.SearchParams(n_probes=32, lut_dtype=jnp.bfloat16,
                             internal_distance_dtype=jnp.float32)
    _, i = ivf_pq.search(index, q, 10, sp)
    assert float(neighborhood_recall(np.asarray(i), gt)) >= 0.75


def test_inner_product(data):
    db, q = data
    dbn = (db / np.linalg.norm(db, axis=1, keepdims=True)).astype(np.float32)
    # pq_len=1 config: validates the IP ADC path with minimal quantization
    # loss (normalized vectors make IP rank gaps tiny — the erfc-model
    # floors in ann_ivf_pq.cuh:164-199 exist for exactly this reason)
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=32,
                                metric="inner_product")
    # pinned seed: the global default Resources' key stream advances with
    # every unseeded build, so recall would depend on test order otherwise
    from raft_tpu import Resources

    index = ivf_pq.build(dbn, params, res=Resources(seed=3))
    _, i = ivf_pq.search(index, q, 10, ivf_pq.SearchParams(n_probes=16))
    ip = q @ dbn.T
    want = np.argsort(-ip, 1)[:, :10]
    assert float(neighborhood_recall(np.asarray(i), want)) >= 0.8


def test_l2sqrt_distances_sqrted(data, res):
    db, q = data
    # identical index state under both metrics (same seed → same build);
    # L2SqrtExpanded distances must be the sqrt of L2Expanded's
    from raft_tpu import Resources

    params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, metric="euclidean")
    index = ivf_pq.build(db, params, res=Resources(seed=7))
    d_sqrt, i1 = ivf_pq.search(index, q, 5, ivf_pq.SearchParams(n_probes=16))
    params2 = ivf_pq.IndexParams(n_lists=16, pq_dim=16, metric="sqeuclidean")
    index2 = ivf_pq.build(db, params2, res=Resources(seed=7))
    d_sq, i2 = ivf_pq.search(index2, q, 5, ivf_pq.SearchParams(n_probes=16))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d_sqrt),
                               np.sqrt(np.maximum(np.asarray(d_sq), 0.0)),
                               rtol=1e-4, atol=1e-4)


def test_extend(data, gt):
    db, q = data
    half = len(db) // 2
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=16)
    index = ivf_pq.build(db[:half], params)
    index = ivf_pq.extend(index, db[half:])
    assert index.size == len(db)
    _, i = ivf_pq.search(index, q, 10, ivf_pq.SearchParams(n_probes=32))
    # codebooks were trained on the first half only → slightly lower floor
    assert float(neighborhood_recall(np.asarray(i), gt)) >= 0.7


def test_device_pack_matches_numpy_pack():
    """_pack_codes_jit (device) must be bit-identical to _pack_codes_np
    (host, shared with the native packers) for every pq_bits."""
    from raft_tpu.neighbors.ivf_pq import _pack_codes_jit, _pack_codes_np

    rng = np.random.default_rng(0)
    for pq_bits in (4, 5, 6, 7, 8):
        pq_dim = 16 if (16 * pq_bits) % 8 == 0 else 8
        codes = rng.integers(0, 1 << pq_bits,
                             (37, pq_dim)).astype(np.uint8)
        got = np.asarray(_pack_codes_jit(jnp.asarray(codes), pq_dim,
                                         pq_bits))
        want = _pack_codes_np(codes, pq_bits)
        np.testing.assert_array_equal(got, want, err_msg=f"bits={pq_bits}")


def test_extend_matches_single_shot_lists(data):
    """Device-side extend must place codes/ids exactly where a from-scratch
    pack of the same rows would (VERDICT r1 #3 gate: list contents identical
    to the host packer's)."""
    db, _ = data
    # a huge expansion budget disables the list cap: both paths must then
    # place every row identically (the capped policy is order-dependent by
    # design and covered by the overflow tests instead)
    params = ivf_pq.IndexParams(n_lists=24, pq_dim=16,
                                add_data_on_build=False,
                                list_pad_expansion=1e9)
    base = ivf_pq.build(db, params)

    # one-shot: everything through the native host packer
    one = ivf_pq.extend(base, db)

    # two-step: first half via the packer, second half via the device
    # scatter (the new path exercised only when lists already exist)
    half = len(db) // 2
    two = ivf_pq.extend(base, db[:half])
    two = ivf_pq.extend(two, db[half:])

    assert two.size == one.size == len(db)
    np.testing.assert_array_equal(np.asarray(one.list_sizes),
                                  np.asarray(two.list_sizes))
    np.testing.assert_array_equal(np.asarray(one.list_indices),
                                  np.asarray(two.list_indices))
    np.testing.assert_array_equal(np.asarray(one.list_codes),
                                  np.asarray(two.list_codes))


@pytest.mark.slow
def test_extend_many_lists_no_per_list_cost():
    """Extend into a many-list index completes without per-list host work
    (the old path paid ~n_lists Python iterations per batch)."""
    import time

    rng = np.random.default_rng(3)
    db = rng.standard_normal((6000, 32)).astype(np.float32)
    params = ivf_pq.IndexParams(n_lists=1500, pq_dim=16,
                                kmeans_n_iters=2, add_data_on_build=True)
    index = ivf_pq.build(db, params)
    more = rng.standard_normal((2000, 32)).astype(np.float32)
    t0 = time.time()
    index = ivf_pq.extend(index, more)
    assert index.size == 8000
    assert time.time() - t0 < 30  # generous CI bound; was minutes-scale


def test_bitset_filter(data):
    db, q = data
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=16)
    index = ivf_pq.build(db, params)
    _, bf_i = brute_force.knn(q, db, k=1, metric="sqeuclidean")
    banned = np.unique(np.asarray(bf_i).ravel())
    filt = Bitset.create(len(db)).set(banned, value=False)
    _, i = ivf_pq.search(index, q, 10, ivf_pq.SearchParams(n_probes=16),
                         filter=filt)
    assert not np.isin(np.asarray(i), banned).any()


def test_serialize_roundtrip(data):
    db, q = data
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=16)
    index = ivf_pq.build(db, params)
    buf = io.BytesIO()
    ivf_pq.serialize(index, buf)
    buf.seek(0)
    index2 = ivf_pq.deserialize(buf)
    d1, i1 = ivf_pq.search(index, q, 10, ivf_pq.SearchParams(n_probes=8))
    d2, i2 = ivf_pq.search(index2, q, 10, ivf_pq.SearchParams(n_probes=8))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


def test_validation():
    with pytest.raises(ValueError, match="pq_bits"):
        ivf_pq.IndexParams(pq_bits=3)
    with pytest.raises(ValueError, match="supports"):
        ivf_pq.IndexParams(metric="cosine")
    rng = np.random.default_rng(0)
    db = rng.standard_normal((100, 32)).astype(np.float32)
    with pytest.raises(ValueError, match="multiple of 8"):
        ivf_pq.build(db, ivf_pq.IndexParams(n_lists=4, pq_dim=10, pq_bits=5))


def test_helpers_codepacker_roundtrip(data):
    db, _ = data
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, pq_bits=8,
                                kmeans_n_iters=4)
    index = ivf_pq.build(db, params)
    codes = ivf_pq.helpers.unpack_list_codes(index, 3)
    assert codes.ndim == 2 and codes.shape[1] == 16
    # repack identical codes → index searches the same
    idx2 = ivf_pq.helpers.pack_list_codes(
        index, 3, codes, ids=np.asarray(index.list_indices)[3, :len(codes)])
    np.testing.assert_array_equal(
        np.asarray(idx2.list_codes)[3], np.asarray(index.list_codes)[3])
    # reconstruction approximates member vectors
    rec = ivf_pq.helpers.reconstruct_list_data(index, 3)
    members = np.asarray(index.list_indices)[3, :len(rec)]
    orig = db[members]
    rel = np.linalg.norm(rec - orig) / np.linalg.norm(orig)
    assert rel < 0.5  # coarse: PQ reconstruction error bounded


@pytest.mark.parametrize("pq_bits", [4, 5])
def test_low_bit_end_to_end(data, gt, pq_bits):
    """Whole-index build→search at pq_bits<8 (the deep-100M reference config
    uses pq_bits=5 — run/conf/deep-100M.json:252)."""
    db, q = data
    pq_dim = 16 if pq_bits == 4 else 8  # keep pq_dim*pq_bits % 8 == 0
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=pq_dim, pq_bits=pq_bits,
                                kmeans_n_iters=8)
    index = ivf_pq.build(db, params)
    assert index.pq_book_size == 1 << pq_bits
    _, i = ivf_pq.search(index, q, 10, ivf_pq.SearchParams(n_probes=32))
    rec = float(neighborhood_recall(np.asarray(i), gt))
    # fewer bits + coarser codebooks → much lower floor than the 8-bit
    # tests (compression 12.8x / 25.6x; cf. the erfc floor model,
    # ann_ivf_pq.cuh:164-199); measured ~0.52 / ~0.32 on this fixture
    floor = 0.45 if pq_bits == 4 else 0.25
    assert rec >= floor, f"pq_bits={pq_bits} recall {rec}"
    # exact re-rank recovers most of the quantization loss
    from raft_tpu.neighbors import refine as refine_mod

    _, cand = ivf_pq.search(index, q, 30, ivf_pq.SearchParams(n_probes=32))
    _, refined = refine_mod.refine(db, q, np.asarray(cand), 10)
    rec_ref = float(neighborhood_recall(np.asarray(refined), gt))
    assert rec_ref >= rec + 0.1, f"refine didn't recover: {rec}→{rec_ref}"


def test_fp8_lut(data, gt):
    """fp8 LUT (max-abs scaled per subspace, fp_8bit analog) holds recall
    within a few points of the fp32 LUT on the forced-LUT path."""
    from raft_tpu import Resources

    db, q = data
    params = ivf_pq.IndexParams(n_lists=32, pq_dim=16)
    index = ivf_pq.build(db, params, res=Resources(seed=11))
    recalls = {}
    for lut in (jnp.float32, jnp.float8_e4m3fn):
        sp = ivf_pq.SearchParams(n_probes=32, lut_dtype=lut,
                                 scan_mode="lut")
        _, i = ivf_pq.search(index, q, 10, sp)
        recalls[str(lut)] = float(
            neighborhood_recall(np.asarray(i), gt))
    assert recalls["<class 'jax.numpy.float8_e4m3fn'>"] >= \
        recalls["<class 'jax.numpy.float32'>"] - 0.05
    assert recalls["<class 'jax.numpy.float8_e4m3fn'>"] >= 0.7


def test_auto_scan_mode_respects_memory(data):
    """scan_mode='auto' falls back to the LUT engine when the decoded cache
    would not fit the device's memory headroom (DEEP-100M shape analog)."""
    from raft_tpu import Resources

    db, q = data
    params = ivf_pq.IndexParams(n_lists=16, pq_dim=16)
    index = ivf_pq.build(db, params, res=Resources(seed=4))
    # tiny workspace → cache estimate exceeds 4× headroom → LUT engine,
    # which leaves the decoded cache unbuilt
    res = Resources(seed=4, workspace_limit_bytes=1 << 16)
    _, i = ivf_pq.search(index, q, 10, ivf_pq.SearchParams(n_probes=16),
                         res=res)
    assert index.list_decoded is None
    # generous workspace → cache engine builds its decoded slabs
    _, i = ivf_pq.search(index, q, 10, ivf_pq.SearchParams(n_probes=16))
    assert index.list_decoded is not None


def test_scan_mode_auto_is_memory_aware(data):
    """VERDICT r2 #3: "auto" must never materialize a decoded cache the
    device can't afford — the engine choice keys off device/workspace
    memory, and the DEEP-100M flagship shapes resolve to LUT."""
    from raft_tpu import Resources

    # shapes-only: DEEP-100M single-chip (nlist=50000, 1.5x-capped pads
    # for 1e8 rows, rot_dim=96, pq_bits=8, bf16 cache) vs a 16 GB v5e —
    # decoded cache ~29 GB: must pick LUT
    pad = int(1e8 / 50000 * 1.5)
    mode = ivf_pq.resolve_scan_mode(
        n_lists=50000, list_pad=pad, rot_dim=96, n_code_bytes=96,
        cache_itemsize=2, device_memory_bytes=16 << 30,
        workspace_limit_bytes=4 << 30)
    assert mode == "lut"
    # same shapes, 8-chip shard (rows/8): cache fits a 16 GB chip
    mode8 = ivf_pq.resolve_scan_mode(
        n_lists=6250, list_pad=pad, rot_dim=96, n_code_bytes=96,
        cache_itemsize=2, device_memory_bytes=16 << 30,
        workspace_limit_bytes=4 << 30)
    assert mode8 == "cache"

    # end-to-end crossover on a real index: tiny workspace -> LUT (no
    # decoded cache materialized), big workspace -> cache
    db, q = data
    index = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=16, pq_dim=16,
                                                kmeans_n_iters=4))
    lean = Resources(seed=0, workspace_limit_bytes=1 << 10)
    ivf_pq.search(index, q[:8], 5, ivf_pq.SearchParams(n_probes=4),
                  res=lean)
    assert index.list_decoded is None, "auto must not decode under a tiny budget"
    roomy = Resources(seed=0, workspace_limit_bytes=1 << 30)
    ivf_pq.search(index, q[:8], 5, ivf_pq.SearchParams(n_probes=4),
                  res=roomy)
    assert index.list_decoded is not None


@pytest.mark.slow
def test_pq_bits5_end_to_end_both_engines(rng):
    """The DEEP-100M build shape (pq_bits=5, pq_dim=96 → 60 packed
    bytes/row) must build and search on both scan engines with sane
    recall — 5-bit packing is exercised beyond the pack/unpack
    roundtrip (deep-100M.json:252-340 is the chip pareto config)."""
    from raft_tpu.stats import neighborhood_recall

    c = (rng.standard_normal((32, 96)) * 4).astype(np.float32)
    db = (c[rng.integers(0, 32, 20000)]
          + rng.standard_normal((20000, 96))).astype(np.float32)
    q = (c[rng.integers(0, 32, 100)]
         + rng.standard_normal((100, 96))).astype(np.float32)
    gt = np.argsort(((q[:, None, :] - db[None]) ** 2).sum(-1), 1)[:, :10]
    idx = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=64, pq_dim=96,
                                              pq_bits=5))
    for mode in ("lut", "cache"):
        _, i = ivf_pq.search(idx, q, 10,
                             ivf_pq.SearchParams(n_probes=16,
                                                 scan_mode=mode))
        r = float(neighborhood_recall(np.asarray(i), gt))
        assert r > 0.7, (mode, r)


def test_lut_probe_tiling_bit_identical(data):
    """A workspace too small to hold all probes at once forces the
    probe-tile loop (probe_tile < n_probes); the tiled scan must complete
    and return identical ids to the untiled single-tile run. Distances
    agree to a few ulp, not bitwise: the LUT einsum runs at a different
    batch shape per tile, and XLA:CPU blocks (hence rounds) the
    contraction by shape, so the two paths cannot share one reduction
    order without leaving the engine's matmul (measured: ≤ 2.1e-6
    relative on jaxlib 0.9.0)."""
    from raft_tpu import Resources

    db, q = data
    index = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=32, pq_dim=16),
                         res=Resources(seed=7))
    n_probes = 12
    sp = ivf_pq.SearchParams(n_probes=n_probes, scan_mode="lut")
    v0, i0 = ivf_pq.search(index, q, 10, sp,
                           res=Resources(workspace_limit_bytes=1 << 34))
    list_pad = index.list_codes.shape[1]
    per_qp = ivf_pq.lut_bytes_per_query_probe(list_pad, index.pq_dim,
                                              index.pq_bits)
    tight = Resources(workspace_limit_bytes=per_qp * 8 * 3)
    q_tile, probe_tile = ivf_pq.plan_lut_tiles(
        n_probes, list_pad, index.pq_dim, index.pq_bits,
        tight.workspace_limit_bytes)
    assert probe_tile < n_probes, (q_tile, probe_tile)
    assert q_tile * probe_tile * per_qp <= tight.workspace_limit_bytes
    v1, i1 = ivf_pq.search(index, q, 10, sp, res=tight)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=5e-6)


def test_lut_probe_tiling_matches_cache_engine(data, gt):
    """Tiled-LUT results stay within the existing lut-vs-cache parity
    tolerance: both engines compute the same ADC distances (fp32 LUT vs
    fp32 decoded cache differ only in accumulation order), so where the
    returned ids agree the distances agree to float tolerance, the
    neighbor sets overlap almost entirely (near-tie rank swaps only),
    and recall holds the same floor."""
    from raft_tpu import Resources

    db, q = data
    index = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=32, pq_dim=16),
                         res=Resources(seed=7))
    n_probes = 12
    list_pad = index.list_codes.shape[1]
    per_qp = ivf_pq.lut_bytes_per_query_probe(list_pad, index.pq_dim,
                                              index.pq_bits)
    tight = Resources(workspace_limit_bytes=per_qp * 8 * 3)
    # scan_cache_dtype also governs the overflow-block decode on the lut
    # path — hold it at fp32 on BOTH engines so spilled rows don't drift
    v1, i1 = ivf_pq.search(
        index, q, 10, ivf_pq.SearchParams(n_probes=n_probes,
                                          scan_mode="lut",
                                          scan_cache_dtype=jnp.float32),
        res=tight)
    vc, ic = ivf_pq.search(
        index, q, 10, ivf_pq.SearchParams(n_probes=n_probes,
                                          scan_mode="cache",
                                          scan_cache_dtype=jnp.float32))
    v1, i1, vc, ic = map(np.asarray, (v1, i1, vc, ic))
    same = i1 == ic
    assert same.mean() >= 0.95, same.mean()
    np.testing.assert_allclose(v1[same], vc[same], rtol=1e-4, atol=1e-3)
    overlap = np.mean([len(np.intersect1d(a, b)) / 10.0
                       for a, b in zip(i1, ic)])
    assert overlap >= 0.97, overlap
    r_lut = float(neighborhood_recall(i1, gt))
    r_cache = float(neighborhood_recall(ic, gt))
    assert r_lut >= r_cache - 0.02 and r_lut >= 0.7, (r_lut, r_cache)


def test_resolve_scan_mode_lut_at_1m_shape_with_fitting_tiles():
    """The sift-1M crash shape (LUT_CRASH_tpu.json: nlist=1024, ~1464
    list pad, pq_dim=64, pq_bits=8, nprobe=64): when the decoded cache
    does not fit the headroom, auto resolves to LUT — which is now safe
    because plan_lut_tiles bounds the scan workspace by construction
    (the old one-axis solve under-counted the live set ~5x and sized
    q_tile=136 -> ~19 GB on a 16 GB chip)."""
    list_pad, pq_dim, pq_bits, n_probes = 1464, 64, 8, 64
    # fp32 cache at this shape ~ 774 MB on top of ~102 MB packed; a
    # 512 MB headroom (no reported device memory, 128 MB workspace x4)
    # cannot hold it -> LUT
    mode = ivf_pq.resolve_scan_mode(
        n_lists=1024, list_pad=list_pad, rot_dim=128, n_code_bytes=64,
        cache_itemsize=4, device_memory_bytes=None,
        workspace_limit_bytes=128 << 20)
    assert mode == "lut"
    q_tile, probe_tile = ivf_pq.plan_lut_tiles(
        n_probes, list_pad, pq_dim, pq_bits, 128 << 20)
    per_qp = ivf_pq.lut_bytes_per_query_probe(list_pad, pq_dim, pq_bits)
    assert q_tile >= 1 and 1 <= probe_tile <= n_probes
    assert q_tile * probe_tile * per_qp <= 128 << 20
    # the crash accounting: at the old q_tile=136 with all 64 probes the
    # true live set was multiple device memories — the joint solve must
    # never produce it under ANY budget that reports the 16 GB chip
    q16, p16 = ivf_pq.plan_lut_tiles(n_probes, list_pad, pq_dim, pq_bits,
                                     (16 << 30) // 4)
    assert q16 * p16 * per_qp <= (16 << 30) // 4


# ------------------------------------------------ precision of the contractions

_F32, _BF16 = jnp.float32, jnp.bfloat16


def _dot_precisions(lowered) -> list:
    """The operand precision of each ``dot_general`` of a lowered program,
    in program order (an op without the attribute is at DEFAULT)."""
    import re

    out = []
    for line in lowered.as_text().splitlines():
        if "stablehlo.dot_general" in line:
            m = re.search(r"precision = \[(\w+),", line)
            out.append(m.group(1) if m else "DEFAULT")
    return out


def _lower_scan(engine: str, overflow: bool, table, dist):
    """The cache or LUT engine lowered at a tiny size, its cache or LUT
    in ``table`` and its distances in ``dist``; the overflow block (8
    rows) is decoded in the table's dtype, as ``search`` decodes it."""
    import jax

    from raft_tpu.ops.distance import DistanceType

    def s(shape, dt=_F32):
        return jax.ShapeDtypeStruct(shape, dt)

    n_lists, pad, rot, dim, pq_dim = 8, 16, 16, 16, 8
    n_over = 8 if overflow else 0
    head = (s((24, dim)), s((n_lists, dim)), s((rot, dim)))
    tail = (s((n_lists, pad), jnp.int32), s((n_lists,), jnp.int32),
            s((0,), jnp.uint32))
    over = dict(overflow_decoded=s((n_over, rot), table),
                overflow_norms=s((n_over,)),
                overflow_indices=s((n_over,), jnp.int32),
                has_overflow=overflow)
    common = dict(metric=DistanceType.L2Expanded, k=5, n_probes=3,
                  q_tile=8, has_filter=False)
    if engine == "cache":
        return ivf_pq._search_cache_jit.lower(
            *head, s((n_lists, pad, rot), table), s((n_lists, pad)), *tail,
            dist_dtype=jnp.dtype(dist).name, **common, **over)
    return ivf_pq._search_jit.lower(
        *head, s((pq_dim, 256, rot // pq_dim)),
        s((n_lists, pad, pq_dim), jnp.uint8), *tail, per_cluster=False,
        pq_dim=pq_dim, pq_bits=8, lut_dtype=jnp.dtype(table).name,
        dist_dtype=jnp.dtype(dist).name, **common, **over)


@pytest.mark.parametrize("dtypes,want", [
    ((_F32,), "HIGHEST"),
    ((_F32, "float32"), "HIGHEST"),
    ((_BF16, _F32), "DEFAULT"),
    ((_F32, "bfloat16"), "DEFAULT"),
    ((jnp.float8_e4m3fn, _F32), "DEFAULT"),
])
def test_contraction_precision_follows_stated_dtypes(dtypes, want):
    assert ivf_pq.contraction_precision(*dtypes).name == want


def _kernel_precisions(jaxpr, inside: bool = False) -> list:
    """The precision of each ``dot_general`` inside a ``pallas_call`` of
    ``jaxpr``: the precision a kernel is given, which lowers to no HLO
    dot."""
    out = []
    for eqn in jaxpr.eqns:
        if inside and eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"][0].name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _kernel_precisions(
                        sub, inside or eqn.primitive.name == "pallas_call")
    return out


def _lists_precisions(overflow: bool, table, dist) -> list:
    """The list-major cache core's contraction precisions at a tiny size:
    the HLO dots of its program as a TPU lowers it (the coarse steps and
    the overflow block), then the precision its kernel is given (each of
    the kernel's per-group dots at one precision)."""
    import jax

    from raft_tpu.ops.distance import DistanceType

    def s(shape, dt=_F32):
        return jax.ShapeDtypeStruct(shape, dt)

    n_lists, pad, rot, dim = 8, 256, 16, 16
    n_over = 8 if overflow else 0
    traced = ivf_pq._search_cache_lists_jit.trace(
        s((24, dim)), s((n_lists, dim)), s((rot, dim)),
        s((n_lists, pad, rot), table), s((n_lists, pad)),
        s((n_lists, pad), jnp.int32), s((n_lists,), jnp.int32),
        s((0,), jnp.uint32), metric=DistanceType.L2Expanded, k=5,
        n_probes=3, block_rows=8, super_tile=24, has_filter=False,
        overflow_decoded=s((n_over, rot), table),
        overflow_norms=s((n_over,)),
        overflow_indices=s((n_over,), jnp.int32), has_overflow=overflow,
        dist_dtype=jnp.dtype(dist).name)
    kernel = _kernel_precisions(traced.jaxpr.jaxpr)
    assert len(kernel) == 2 and len(set(kernel)) == 1  # two groups
    return (_dot_precisions(traced.lower(lowering_platforms=("tpu",)))
            + kernel[:1])


def _scan_precisions(engine: str, overflow: bool, table, dist) -> list:
    if engine == "cache_lists":
        return _lists_precisions(overflow, table, dist)
    return _dot_precisions(_lower_scan(engine, overflow, table, dist))


@pytest.mark.parametrize("engine", ["cache", "lut", "cache_lists"])
@pytest.mark.parametrize("overflow", [False, True],
                         ids=["lists", "overflow"])
def test_float32_scan_lowers_every_contraction_at_highest(engine, overflow):
    """With float32 stated throughout, every contraction of the engine —
    the coarse step, the cache scan or the LUT build, and the overflow
    block — is lowered at HIGHEST: on a TPU a DEFAULT float32 dot is one
    bfloat16 pass, which the CPU suite cannot see in the answers."""
    got = _scan_precisions(engine, overflow, _F32, _F32)
    assert got == ["HIGHEST"] * (4 + overflow)


@pytest.mark.parametrize("engine", ["cache", "lut", "cache_lists"])
@pytest.mark.parametrize("overflow", [False, True],
                         ids=["lists", "overflow"])
def test_half_scan_lowers_its_contractions_at_default(engine, overflow):
    """The bfloat16 path (cache or LUT and internal distances bfloat16)
    keeps its one-pass contractions: only the three coarse steps, which
    are always float32, carry HIGHEST."""
    got = _scan_precisions(engine, overflow, _BF16, _BF16)
    assert got[:3] == ["HIGHEST"] * 3
    assert got[3:] == ["DEFAULT"] * (1 + overflow)


@pytest.mark.parametrize("per_cluster", [False, True],
                         ids=["per_subspace", "per_cluster"])
def test_encoder_lowers_at_highest(per_cluster):
    """The build's encoder picks each row's code by an expanded distance
    to the float32 codebooks, so it contracts at HIGHEST too."""
    import jax

    n, dim, n_lists, pq_dim = 64, 16, 8, 8
    books = (n_lists if per_cluster else pq_dim, 256, dim // pq_dim)
    lowered = ivf_pq._encode_jit.lower(
        jax.ShapeDtypeStruct((n, dim), _F32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n_lists, dim), _F32),
        jax.ShapeDtypeStruct((dim, dim), _F32),
        jax.ShapeDtypeStruct(books, _F32), per_cluster, 32)
    assert _dot_precisions(lowered) == ["HIGHEST", "HIGHEST"]


@pytest.fixture(scope="module")
def pq_index(data):
    db, _ = data
    return ivf_pq.build(db, ivf_pq.IndexParams(n_lists=32, pq_dim=16,
                                               kmeans_n_iters=4))


@pytest.mark.parametrize("engine,dtype,want", [
    ("cache", _F32, "highest"),
    ("cache", _BF16, "default"),
    ("lut", _F32, "highest"),
    ("lut", _BF16, "default"),
    ("cache_lists", _F32, "highest"),
    ("cache_lists", _BF16, "default"),
])
def test_search_records_and_counts_its_precision(pq_index, data, engine,
                                                 dtype, want, monkeypatch):
    """Each dispatch names the precision of its float contractions in the
    explain record's plan and counts it in
    ``raft_tpu_ivf_pq_scan_plans_total{engine,precision}``; the
    list-major cache core (``cache_lists``) under the interpreter."""
    from raft_tpu.obs.metrics import REGISTRY

    _, q = data
    if engine == "cache_lists":
        monkeypatch.setattr(ivf_pq, "_LIST_MAJOR_PLATFORMS", ("tpu", "cpu"))
    params = ivf_pq.SearchParams(n_probes=8, scan_mode=engine.split("_")[0],
                                 lut_dtype=dtype, scan_cache_dtype=dtype,
                                 internal_distance_dtype=dtype)
    plans = REGISTRY.get("raft_tpu_ivf_pq_scan_plans_total")
    before = dict((key, c.value) for key, c in plans.collect())
    _, ids, rec = ivf_pq.search(pq_index, q, 10, params, explain=True)
    after = dict((key, c.value) for key, c in plans.collect())
    assert (rec.engine, rec.plan["precision"]) == (engine, want)
    assert after[(engine, want)] - before.get((engine, want), 0) == 1
    assert sum(after.values()) - sum(before.values()) == 1
    assert (np.asarray(ids) >= 0).all()


# ------------------------------------------ the list-major cache core


def _skewed_rows(n: int, rng):
    """Clustered rows, half of them in one cluster: ragged lists."""
    centers = rng.standard_normal((20, 32)) * 4.0
    labels = np.where(rng.random(n) < 0.5, 0, rng.integers(1, 20, n))
    return (centers[labels] + rng.standard_normal((n, 32))).astype(
        np.float32)


#: case → (build params, search params, queries, k, filtered, resources)
_LIST_CASES = {
    "l2": ({}, {}, "data", 10, False, None),
    "inner_product": ({"metric": "inner_product"}, {}, "data", 10, False,
                      None),
    "l2sqrt": ({"metric": DistanceType.L2SqrtExpanded}, {}, "data", 10,
               False, None),
    "filter": ({}, {}, "data", 10, True, None),
    "overflow": ({"list_pad_expansion": 1.01}, {}, "data", 10, False, None),
    "ragged": ({}, {}, "skewed", 10, False, None),
    "one_list": ({}, {}, "same", 10, False, None),
    "k_above_candidates": ({}, {"n_probes": 1}, "data", 600, False, None),
    "bfloat16_cache": ({}, {"scan_cache_dtype": jnp.bfloat16,
                            "internal_distance_dtype": jnp.bfloat16},
                       "data", 10, False, None),
    "super_tiles": ({}, {}, "data", 10, False, 4 << 20),
}


def _assert_same_answers(d0, i0, d1, i1, k):
    """The list-major answers ``(d1, i1)`` [nq, k] against the query-major
    core's ``(d0, i0)`` [nq, k + 1]: distances to 1e-5 of the k-th (of
    the row's widest finite one where fewer than k pass), ids equal except
    where two candidates tie, -1 where no candidate passes."""
    for r in range(d0.shape[0]):
        ref, row_i = d0[r], i0[r]
        fin = np.isfinite(ref[:k])
        scale = max(abs(ref[k - 1]) if fin.all() else
                    np.abs(ref[:k][fin]).max(initial=1.0), 1.0)
        tol = 1e-5 * scale
        assert (np.isfinite(d1[r]) == fin).all(), r
        assert (i1[r][~fin] == -1).all(), r
        np.testing.assert_allclose(d1[r][fin], ref[:k][fin], rtol=0,
                                   atol=tol)
        for j in np.flatnonzero(fin & (i1[r] != row_i[:k])):
            others = np.delete(ref, j)
            assert np.abs(others - ref[j]).min() <= tol, (r, j)


@pytest.mark.parametrize("case", list(_LIST_CASES))
def test_list_major_core_matches_query_major(data, case, monkeypatch):
    """The list-major core (its kernel interpreted) gives the query-major
    core's answers: every metric, a filter, an overflow block, ragged
    lists, one list probed by every query, a batch that is not a whole
    number of blocks, k above the probed candidates, a bfloat16 cache and
    several super-tiles."""
    from raft_tpu.core.resources import Resources

    db, q = data
    build, search, queries, k, filtered, workspace = _LIST_CASES[case]
    rng = np.random.default_rng(7)
    if queries == "skewed":
        db = _skewed_rows(4000, rng)
        q = db[rng.integers(0, 4000, 100)] + 0.1
    elif queries == "same":
        q = np.repeat(q[:1], 40, axis=0)
    index = ivf_pq.build(db, ivf_pq.IndexParams(**{
        "n_lists": 16, "pq_dim": 16, "kmeans_n_iters": 4,
        "list_pad_expansion": 8.0, **build}))
    n_over = int((np.asarray(index.overflow_indices) >= 0).sum())
    assert (n_over > 0) == (case == "overflow")
    sizes = np.asarray(index.list_sizes)
    if case == "ragged":
        # some list leaves a whole 128-slot group of its pad empty
        assert sizes.min() + 128 < index.list_codes.shape[1], sizes
    params = ivf_pq.SearchParams(**{"n_probes": 4, "scan_mode": "cache",
                                    "scan_cache_dtype": jnp.float32,
                                    **search})
    filt, banned = None, np.zeros((0,), np.int64)
    if filtered:
        _, top = brute_force.knn(q, db, k=2, metric="sqeuclidean")
        banned = np.unique(np.asarray(top))
        filt = Bitset.create(len(db)).set(banned, value=False)
    res = Resources(workspace_limit_bytes=workspace) if workspace else None
    d0, i0, r0 = ivf_pq.search(index, q, k + 1, params, filter=filt,
                               res=res, explain=True)
    monkeypatch.setattr(ivf_pq, "_LIST_MAJOR_PLATFORMS", ("tpu", "cpu"))
    d1, i1, r1 = ivf_pq.search(index, q, k, params, filter=filt, res=res,
                               explain=True)
    assert (r0.engine, r0.reason) == ("cache", "tpu_absent")
    assert (r1.engine, r1.reason) == ("cache_lists", "list_kernel")
    assert r1.plan["interpret"]
    if case == "super_tiles":
        assert r1.plan["super_tiles"] > 1
    if case != "one_list":
        assert len(q) % r1.plan["block_rows"]  # a ragged last block
    d0, i0, d1, i1 = map(np.asarray, (d0, i0, d1, i1))
    if case == "k_above_candidates":
        assert (i1 == -1).any() and (i1 >= 0).any()
    assert not np.isin(i1, banned).any()
    _assert_same_answers(d0, i0, d1, i1, k)


@pytest.mark.parametrize("skew", ["uniform", "one_list", "two_lists"])
def test_list_major_plan_keeps_every_pair_once(skew):
    """The plan puts each (query, list) pair in exactly one slot of a block
    of its list, the blocks sorted by list, within the NB bound, and
    the blocks past the last used hold no rows and repeat its list."""
    import jax

    rng = np.random.default_rng(3)
    nq, n_probes, n_lists, t = 37, 5, 12, 8
    probes = np.stack([rng.permutation(n_lists)[:n_probes]
                       for _ in range(nq)])
    if skew == "one_list":
        probes[:, 0] = 7  # every query probes list 7
    elif skew == "two_lists":
        probes = np.tile(np.arange(n_probes), (nq, 1))
    nb = ivf_pq.list_scan_blocks(nq * n_probes, n_lists, t)
    assert nb == (nq * n_probes + min(n_lists, nq * n_probes) * (t - 1)) // t
    block_list, n_used, block_queries, pair_at = map(
        np.asarray, jax.jit(ivf_pq._list_major_plan, static_argnums=(1, 2, 3))(
            jnp.asarray(probes, jnp.int32), n_lists, t, nb))
    used = int(n_used[0])
    counts = np.bincount(probes.ravel(), minlength=n_lists)
    assert used == int((-(-counts // t)).sum()) <= nb
    assert (np.diff(block_list) >= 0).all()
    assert (block_list[used:] == block_list[used - 1]).all()
    assert (block_queries[used:] == -1).all()
    flat = block_queries.reshape(-1)
    assert (flat >= 0).sum() == nq * n_probes
    # each pair's slot holds its query, in a block of its list
    q_of = np.repeat(np.arange(nq), n_probes).reshape(nq, n_probes)
    np.testing.assert_array_equal(flat[pair_at], q_of)
    np.testing.assert_array_equal(block_list[pair_at // t], probes)
    assert len(np.unique(pair_at)) == nq * n_probes


@pytest.mark.parametrize("platform,nq,list_pad,rot,want", [
    ("cpu", 10_000, 1456, 128, "tpu_absent"),
    ("tpu", 8, 1456, 128, "list_kernel"),
    ("tpu", 10_000, 120, 128, "short_lists"),
    ("tpu", 10_000, 8192, 512, "list_vmem"),
    ("tpu", 32, 1456, 128, "list_kernel"),
    ("tpu", 10_000, 1456, 128, "list_kernel"),
])
def test_plan_list_scan_routes_on_the_shape(platform, nq, list_pad, rot,
                                            want):
    """The list-major core engages on a TPU at any batch size, where lists
    hold at least one group and a slab fits the kernel's VMEM; at the
    benchmark's IVF-PQ shape it takes 128-row blocks in one super-tile,
    and 8-row blocks for 8 queries (256 pairs over 1024 lists)."""
    plan, why = ivf_pq.plan_list_scan(platform, nq, 32, 1024, list_pad, rot,
                                      4, 10, 2360, 16_909_336_064 // 4)
    assert why == want
    assert (plan is None) == (want != "list_kernel")
    if nq == 10_000 and plan is not None:
        assert plan == ivf_pq.ListScan(128, 3516, 10_000, 1, False)
    elif plan is not None:
        assert plan.block_rows == 8 and plan.n_super == 1


def test_search_routes_small_buckets_to_the_list_major_core(pq_index, data,
                                                            monkeypatch):
    """Even a bucket of fewer pairs than lists takes the list-major core
    where it runs; elsewhere ``search`` keeps the query-major core and
    says why."""
    _, q = data
    params = ivf_pq.SearchParams(n_probes=2, scan_mode="cache")
    assert 8 * 2 < pq_index.n_lists
    monkeypatch.setattr(ivf_pq, "_LIST_MAJOR_PLATFORMS", ())
    _, _, rec = ivf_pq.search(pq_index, q[:8], 10, params, explain=True)
    assert (rec.engine, rec.reason) == ("cache", "tpu_absent")
    monkeypatch.setattr(ivf_pq, "_LIST_MAJOR_PLATFORMS", ("tpu", "cpu"))
    _, _, rec = ivf_pq.search(pq_index, q[:8], 10, params, explain=True)
    assert (rec.engine, rec.reason) == ("cache_lists", "list_kernel")
    assert {"block_rows", "n_blocks", "super_tiles",
            "padded_row_share"} <= set(rec.plan)
