"""On-chip recall / numerics gates. Every test here exists because the CPU
suite provably cannot see its failure mode (XLA:CPU upcasts bf16 matmuls,
emulates approx_min_k, and has no fp8 hardware path). Shapes are kept
small enough that the whole file is minutes, compile-dominated.

Reference floors pattern: cpp/test/neighbors/ann_ivf_pq.cuh:510-525.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests_tpu.conftest import recall


# --------------------------------------------------------------- numerics


def test_bf16_collapse_is_real_and_refine_recovers(clustered, gt):
    """The r3 find, as a permanent gate: an UNREFINED bf16 expanded-L2
    screen on clustered data collapses on real bf16 hardware (0.9997 →
    0.57 measured on v5e) while the refined path holds. If the gap ever
    disappears, either the backend started upcasting (CPU does — this
    test intentionally fails under RAFT_TPU_FORCE_ONCHIP_TESTS there) or
    the refine stopped being load-bearing; both are worth knowing."""
    from raft_tpu.neighbors import brute_force

    base, queries = clustered
    _, i_refined = brute_force.knn(queries, base, k=10,
                                   metric="sqeuclidean",
                                   scan_dtype="bfloat16")
    # refine_ratio=1 makes the re-rank a no-op: pure bf16 screen order
    _, i_raw = brute_force.knn(queries, base, k=10, metric="sqeuclidean",
                               scan_dtype="bfloat16", refine_ratio=1)
    r_ref, r_raw = recall(i_refined, gt), recall(i_raw, gt)
    assert r_ref >= r_raw + 0.03, (
        f"no bf16 collapse on this backend (raw {r_raw:.4f} vs refined "
        f"{r_ref:.4f}) - upcasting backend or refine not load-bearing")


def test_fused_l2_argmin_matches_oracle(clustered):
    """Index-exactness is the wrong gate in fp32 (near-ties flip vs the
    fp64 oracle); the contract is that the chosen row's distance equals
    the true minimum."""
    from raft_tpu.ops.fused_l2_nn import fused_l2_nn_argmin

    base, queries = clustered
    _, idx = fused_l2_nn_argmin(queries[:128], base[:8192])
    idx = np.asarray(idx)
    d = ((queries[:128, None, :].astype(np.float64)
          - base[None, :8192, :]) ** 2).sum(-1)
    chosen = d[np.arange(128), idx]
    np.testing.assert_allclose(chosen, d.min(1), rtol=1e-4)


# --------------------------------------------------------------- select_k


@pytest.mark.parametrize("nq,n,dim,k,metric", [
    (64, 40_000, 32, 10, "sqeuclidean"),
    (1000, 420_000, 96, 100, "sqeuclidean"),
    (1000, 300_000, 96, 100, "inner_product"),
    (64, 300_000, 128, 10, "sqeuclidean"),
    (1000, 420_000, 128, 100, "sqeuclidean"),
    (1000, 300_000, 128, 100, "inner_product"),
    (1000, 900_000, 96, 100, "sqeuclidean_tied"),
], ids=["small", "two_tiles", "inner_product", "sift_width_small",
        "sift_width_two_tiles", "sift_width_inner_product",
        "many_tiles_tied"])
def test_group_scan_exact_on_chip(rng, nq, n, dim, k, metric):
    """The exact brute-force scan's group minima (brute_force._group_topk),
    over the tiles and minima of the group kernel (pk.group_scan_tile),
    answer as one DIRECT top-k over the whole row: the same ids, ties to
    the lower row, and distances within 2 ulp of ‖q‖² + ‖x‖², the size of
    the terms the expanded distance cancels (the kernel's dot and XLA's
    may round apart; on a v5e they were bit-equal, PERF.md). The chip
    keeps a 32- or 96-wide collection with its rows on the lanes, and the
    kernel reads its [dim, rows] view; a 128-wide one rows-major, read in
    [rows, dim] blocks. ``_tied`` repeats a block of ten groups over many
    tiles, so every merge weighs tile groups against carried ones of
    equal minimum."""
    from raft_tpu.neighbors import brute_force
    from raft_tpu.obs import explain as obs_explain
    from raft_tpu.ops.distance import (inner_product, l2_expanded,
                                       row_norms_sq)
    from raft_tpu.ops.select_k import SelectAlgo, select_k

    centers = rng.standard_normal((64, dim)).astype(np.float32) * 3.0
    db = centers[rng.integers(0, 64, n)] + rng.standard_normal(
        (n, dim)).astype(np.float32)
    q = centers[rng.integers(0, 64, nq)] + rng.standard_normal(
        (nq, dim)).astype(np.float32)
    if metric.endswith("_tied"):
        metric = metric[:-len("_tied")]
        db = np.tile(db[:1_280], (n // 1_280 + 1, 1))[:n]
    with obs_explain.capture() as cap:
        v, i = brute_force.search(brute_force.build(db, metric=metric), q, k)
    scan = [r for r in cap.records if r.family == "brute_force_group_scan"]
    assert [r.engine for r in scan] == ["pallas"], cap.briefs()
    assert scan[0].plan["rows_on_lanes"] == (dim < 128)
    if metric == "inner_product":
        row = inner_product(jnp.asarray(q), jnp.asarray(db))
    else:
        row = l2_expanded(q, db, False, y_norms=row_norms_sq(db))
    want_v, want_i = select_k(row, k, select_min=metric != "inner_product",
                              algo=SelectAlgo.DIRECT)
    scale = float((q * q).sum(1).max() + (db * db).sum(1).max())
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(v), np.asarray(want_v), rtol=0,
                               atol=2 * np.spacing(np.float32(scale)))


def test_approx_select_recall_on_chip(rng):
    """The opt-in APPROX engine must hold its recall target on the real
    PartialReduce (CPU emulation is exact, so this gate only bites here)."""
    from raft_tpu.ops.select_k import SelectAlgo, select_k

    x = rng.standard_normal((1024, 32768)).astype(np.float32)
    _, ia = select_k(x, 10, algo=SelectAlgo.APPROX, recall_target=0.95)
    gt_i = np.argsort(x, 1)[:, :10]
    hits = np.mean([len(set(r) & set(g)) / 10.0
                    for r, g in zip(np.asarray(ia), gt_i)])
    assert hits >= 0.90, f"approx recall {hits:.3f} < 0.90 at target 0.95"


# ------------------------------------------------------------- bf16 scans


def test_brute_force_bf16_refine_recall(clustered, gt):
    from raft_tpu.neighbors import brute_force

    base, queries = clustered
    _, idx = brute_force.knn(queries, base, k=10, metric="sqeuclidean",
                             scan_dtype="bfloat16")
    r = recall(idx, gt)
    assert r >= 0.93, f"bf16+refine brute force recall {r:.4f}"


def test_ivf_flat_bf16_refine_recall(clustered, gt):
    """The r3 collapse class: bf16 expanded-L2 screen on clustered data
    MUST be recovered by the fp32 re-rank."""
    from raft_tpu.neighbors import ivf_flat

    base, queries = clustered
    idx = ivf_flat.build(base, ivf_flat.IndexParams(n_lists=256))
    _, ids32 = ivf_flat.search(idx, queries, 10,
                               ivf_flat.SearchParams(n_probes=32))
    _, ids16 = ivf_flat.search(
        idx, queries, 10,
        ivf_flat.SearchParams(n_probes=32, scan_dtype="bfloat16"))
    r32, r16 = recall(ids32, gt), recall(ids16, gt)
    assert r16 >= r32 - 0.05, f"bf16+refine {r16:.4f} vs fp32 {r32:.4f}"
    assert r16 >= 0.90, f"bf16+refine recall {r16:.4f}"


def test_ivf_flat_uint8_storage_recall(clustered, gt):
    """Narrow-dtype storage (4x fewer scan bytes): int values are
    bf16-exact and the MXU accumulates fp32, so recall must track the
    fp32 build on quantized data."""
    from raft_tpu.neighbors import brute_force, ivf_flat

    base, queries = clustered
    lo, hi = base.min(), base.max()
    base_u8 = np.clip((base - lo) * 255.0 / (hi - lo), 0, 255).astype(
        np.uint8)
    q_scaled = ((queries - lo) * 255.0 / (hi - lo)).astype(np.float32)
    _, gt_u8 = brute_force.knn(q_scaled, base_u8.astype(np.float32), k=10,
                               metric="sqeuclidean")
    idx = ivf_flat.build(base_u8, ivf_flat.IndexParams(n_lists=256))
    _, ids = ivf_flat.search(idx, q_scaled, 10,
                             ivf_flat.SearchParams(n_probes=32))
    r = recall(ids, np.asarray(gt_u8))
    assert r >= 0.90, f"uint8 ivf_flat recall {r:.4f}"


# ---------------------------------------------------------------- ivf_pq


@pytest.fixture(scope="module")
def pq_index(clustered):
    from raft_tpu.neighbors import ivf_pq

    base, _ = clustered
    return ivf_pq.build(
        base, ivf_pq.IndexParams(n_lists=256, pq_dim=48, pq_bits=8))


def test_ivf_pq_fp32_lut_recall(pq_index, clustered, gt):
    from raft_tpu.neighbors import ivf_pq

    _, queries = clustered
    _, ids = ivf_pq.search(pq_index, queries, 10,
                           ivf_pq.SearchParams(n_probes=32,
                                               scan_mode="lut"))
    r = recall(ids, gt)
    assert r >= 0.85, f"fp32 LUT recall {r:.4f}"


def test_ivf_pq_fp8_lut_recall(pq_index, clustered, gt):
    """fp8 max-abs-scaled LUTs (the fp_8bit analog,
    detail/ivf_pq_fp_8bit.cuh) must stay within 0.05 of the fp32 LUT on
    REAL fp8 hardware."""
    from raft_tpu.neighbors import ivf_pq

    _, queries = clustered
    _, i32 = ivf_pq.search(pq_index, queries, 10,
                           ivf_pq.SearchParams(n_probes=32,
                                               scan_mode="lut"))
    _, i8 = ivf_pq.search(
        pq_index, queries, 10,
        ivf_pq.SearchParams(n_probes=32, scan_mode="lut",
                            lut_dtype=jnp.float8_e4m3fn))
    r32, r8 = recall(i32, gt), recall(i8, gt)
    assert r8 >= r32 - 0.05, f"fp8 LUT {r8:.4f} vs fp32 LUT {r32:.4f}"


def test_ivf_pq_cache_engine_recall(pq_index, clustered, gt):
    """Decoded-cache MXU engine (the ADC-as-matmul path the reference
    doesn't have) must agree with the LUT engine's recall."""
    from raft_tpu.neighbors import ivf_pq

    _, queries = clustered
    _, ic = ivf_pq.search(pq_index, queries, 10,
                          ivf_pq.SearchParams(n_probes=32,
                                              scan_mode="cache"))
    _, il = ivf_pq.search(pq_index, queries, 10,
                          ivf_pq.SearchParams(n_probes=32,
                                              scan_mode="lut"))
    rc, rl = recall(ic, gt), recall(il, gt)
    assert rc >= rl - 0.03, f"cache engine {rc:.4f} vs lut {rl:.4f}"


@pytest.fixture(scope="module")
def sift_width_pq():
    """100k × 128 clustered rows in an index of SIFT1M's cell's shape but
    256 lists (pq_dim 64, 8-bit codes), 512 queries, and the queries'
    exact k-th distances (float64)."""
    from raft_tpu.neighbors import ivf_pq

    base, queries = _sift_width_rows(512)
    index = ivf_pq.build(base, ivf_pq.IndexParams(n_lists=256, pq_dim=64,
                                                  pq_bits=8))
    return index, queries, _kth_distances(base, queries)


def _sift_width_rows(n_queries: int):
    """``sift_width_pq``'s 100k × 128 rows (the same for any
    ``n_queries``) and ``n_queries`` queries from its clusters."""
    rng = np.random.default_rng(25)
    centers = rng.standard_normal((256, 128)).astype(np.float32) * 4.0
    base = centers[rng.integers(0, 256, 100_000)] + rng.standard_normal(
        (100_000, 128)).astype(np.float32)
    queries = centers[rng.integers(0, 256, n_queries)] + rng.standard_normal(
        (n_queries, 128)).astype(np.float32)
    return base, queries


def _kth_distances(base, queries):
    """Each query's exact 10th squared distance, in float64."""
    b64 = base.astype(np.float64)
    bn = (b64 * b64).sum(1)
    return np.concatenate([np.partition(
        (q * q).sum(1)[:, None] + bn[None] - 2.0 * q @ b64.T, 9, 1)[:, 9]
        for q in np.array_split(queries.astype(np.float64),
                                max(len(queries) // 64, 1))])


def _adc_error(index, queries, kth, d, i):
    """The widest gap between a reported distance and the float64 ADC
    distance of its id (benchmark/references/ivf_pq_adc.py), over the
    query's exact k-th distance."""
    from benchmark import harness

    names = ("centers", "rotation", "codebooks", "list_codes",
             "list_indices", "list_sizes", "overflow_codes",
             "overflow_labels", "overflow_indices")
    view = dict(zip(names, jax.device_get(
        [getattr(index, n) for n in names])))
    view.update(n_rows=index.n_rows, pq_dim=index.pq_dim,
                pq_bits=index.pq_bits, per_cluster=False)
    adc = harness.load_module("references", "ivf_pq_adc").distances(
        view, queries, np.asarray(i))
    return float((np.abs(np.asarray(d, np.float64) - adc)
                  / kth[:, None]).max())


@pytest.mark.parametrize("engine,dtype,float_path", [
    ("cache", jnp.float32, True),
    ("lut", jnp.float32, True),
    ("cache", jnp.bfloat16, False),
], ids=["cache_f32", "lut_f32", "cache_bf16"])
def test_ivf_pq_adc_error_on_chip(sift_width_pq, engine, dtype, float_path):
    """Each reported distance against the float64 ADC distance of the same
    id's codes (benchmark/references/ivf_pq_adc.py), over the query's
    exact k-th distance — the number that decides the ivfpq-sift1m-batch
    cell's ``correct``. The float32 engines hold 1e-4 (they contract at
    HIGHEST); the bfloat16 cache, one bfloat16 pass, reads above 1e-3, so
    the limit separates the two."""
    import json

    from raft_tpu.neighbors import ivf_pq

    index, queries, kth = sift_width_pq
    params = ivf_pq.SearchParams(n_probes=32, scan_mode=engine,
                                 lut_dtype=dtype, scan_cache_dtype=dtype,
                                 internal_distance_dtype=dtype)
    d, i = ivf_pq.search(index, queries, 10, params)
    err = _adc_error(index, queries, kth, d, i)
    print(json.dumps({"test": "ivf_pq_adc_error", "engine": engine,
                      "dtype": jnp.dtype(dtype).name, "adc_error": err}))
    if float_path:
        assert err <= 1e-4, err
    else:
        assert err > 1e-3, err


def test_ivf_pq_list_major_core_on_chip(sift_width_pq, monkeypatch):
    """The list-major cache core, compiled, at 1,000 queries × nprobe 32
    over 256 lists: ``adc_error`` within the cell's 1e-4, the query-major
    core's ids except where two distances tie, and a program that holds
    the list kernel and no [t, P, pad, rot] slab gather."""
    import json
    import re

    from raft_tpu.neighbors import ivf_pq

    index, _, _ = sift_width_pq
    base, queries = _sift_width_rows(1000)
    kth = _kth_distances(base, queries)
    params = ivf_pq.SearchParams(n_probes=32, scan_mode="cache",
                                 scan_cache_dtype=jnp.float32)
    calls = []
    core = ivf_pq._search_cache_lists_jit

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return core(*args, **kwargs)

    monkeypatch.setattr(ivf_pq, "_search_cache_lists_jit", spy)
    d1, i1, rec = ivf_pq.search(index, queries, 10, params, explain=True)
    assert (rec.engine, rec.reason) == ("cache_lists", "list_kernel")
    assert not rec.plan["interpret"]
    monkeypatch.setattr(ivf_pq, "_LIST_MAJOR_PLATFORMS", ())
    d0, i0, rec0 = ivf_pq.search(index, queries, 11, params, explain=True)
    assert rec0.engine == "cache"
    err = _adc_error(index, queries, kth, d1, i1)
    d0, i0, d1, i1 = map(np.asarray, (d0, i0, d1, i1))
    tol = 1e-5 * np.abs(d0[:, 9:10])
    gap = np.abs(d1 - d0[:, :10])
    print(json.dumps({"test": "ivf_pq_list_major", "adc_error": err,
                      "ids_differ": int((i1 != i0[:, :10]).sum()),
                      "widest_gap": float((gap / tol).max()) * 1e-5,
                      "plan": rec.plan}))
    assert err <= 1e-4, err
    assert (gap <= tol).all()
    for r, j in zip(*np.nonzero(i1 != i0[:, :10])):
        assert np.abs(np.delete(d0[r], j) - d0[r, j]).min() <= tol[r, 0]
    args, kwargs = calls[0]
    hlo = core.lower(*args, **kwargs).compile().as_text()
    n_probes, (pad, rot) = 32, index.list_decoded.shape[1:]
    assert "tpu_custom_call" in hlo and "list_scan" in hlo
    assert not re.search(rf"\[\d+,{n_probes},{pad},{rot}\]", hlo)


def test_ivf_pq_approx_select_recall(pq_index, clustered, gt):
    """select_recall=0.95 (APPROX selection inside the search) on real
    PartialReduce hardware."""
    from raft_tpu.neighbors import ivf_pq

    _, queries = clustered
    _, ids = ivf_pq.search(
        pq_index, queries, 10,
        ivf_pq.SearchParams(n_probes=32, select_recall=0.95))
    _, ids_exact = ivf_pq.search(pq_index, queries, 10,
                                 ivf_pq.SearchParams(n_probes=32))
    ra, re = recall(ids, gt), recall(ids_exact, gt)
    assert ra >= re - 0.05, f"approx-select {ra:.4f} vs exact {re:.4f}"


# ----------------------------------------------------------------- cagra


def test_cagra_recall_on_chip(clustered, gt):
    """64 well-separated clusters need seed coverage: with only 64
    random seeds, P(a query's cluster is unseeded) ≈ (63/64)^64 ≈ 0.36
    and the walk can't cross components — num_random_samplings is the
    reference's lever for exactly this (search_plan.cuh random init)."""
    from raft_tpu.neighbors import cagra

    base, queries = clustered
    idx = cagra.build(base, cagra.IndexParams(graph_degree=32))
    _, ids = cagra.search(
        idx, queries, 10,
        cagra.SearchParams(itopk_size=64, num_random_samplings=4))
    r = recall(ids, gt)
    assert r >= 0.90, f"cagra recall {r:.4f}"


def test_topk_pad_exact_on_chip(rng):
    """k-pad rules (select_k's in-code "tpu" table, swapped here through
    set_pad_rules) rewrite DIRECT's requested k on the real top_k lowering; the padded prefix must equal
    the unpadded selection bit-for-bit, at the measured pathological cell
    (n=4096, k=10: 112-120 ms unpadded vs ~2 ms at k=32 on v5e)."""
    import importlib

    import jax

    sk = importlib.import_module("raft_tpu.ops.select_k")
    from raft_tpu.ops.select_k import SelectAlgo, select_k

    x = rng.standard_normal((512, 4096)).astype(np.float32)
    plat = sk._platform_key()
    prev = sk._load_pad_rules().get(plat)
    # baseline must be UNPADDED although the in-code table pads this
    # cell (else this compares padded to padded and proves nothing)
    sk.set_pad_rules(plat, None)
    v0, i0 = select_k(x, 10, algo=SelectAlgo.DIRECT)
    v0, i0 = np.asarray(v0), np.asarray(i0)
    sk.set_pad_rules(plat, [{"n": 4096, "k": 10, "k_pad": 32}])
    try:
        v1, i1 = select_k(x, 10, algo=SelectAlgo.DIRECT)
        np.testing.assert_array_equal(np.asarray(v1), v0)
        np.testing.assert_array_equal(np.asarray(i1), i0)
    finally:
        sk.set_pad_rules(plat, prev)
