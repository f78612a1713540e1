"""Brute-force kNN tests: exact agreement with a numpy oracle, tiling paths,
serialization round-trip (reference pattern: cpp/test/neighbors/
knn_brute_force.cu + ann fixtures' serialize round-trips)."""

import io

import numpy as np
import pytest

from raft_tpu import Resources
from raft_tpu.neighbors import brute_force
from raft_tpu.stats import neighborhood_recall


def _numpy_knn(queries, dataset, k, metric="sqeuclidean"):
    import scipy.spatial.distance as sd

    d = sd.cdist(queries, dataset, metric)
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, 1), idx


@pytest.mark.parametrize("metric,scipy_metric", [
    ("sqeuclidean", "sqeuclidean"),
    ("euclidean", "euclidean"),
    ("cosine", "cosine"),
])
def test_exact_recall(metric, scipy_metric, rng):
    db = rng.standard_normal((500, 32)).astype(np.float32)
    q = rng.standard_normal((40, 32)).astype(np.float32)
    dist, idx = brute_force.knn(q, db, k=10, metric=metric)
    want_dist, want_idx = _numpy_knn(q, db, 10, scipy_metric)
    # tie-tolerant recall: fp32 near-ties can flip ranks at the k boundary
    recall = float(
        neighborhood_recall(
            np.asarray(idx), want_idx, np.asarray(dist), want_dist, eps=1e-4
        )
    )
    assert recall >= 0.999


def test_inner_product_maximizes(rng):
    db = rng.standard_normal((200, 16)).astype(np.float32)
    q = rng.standard_normal((10, 16)).astype(np.float32)
    dist, idx = brute_force.knn(q, db, k=5, metric="inner_product")
    ip = q @ db.T
    want = np.argsort(-ip, axis=1)[:, :5]
    assert float(neighborhood_recall(np.asarray(idx), want)) >= 0.999
    # returned "distances" are the (descending) inner products
    assert np.all(np.diff(np.asarray(dist), axis=1) <= 1e-5)


def test_tiled_matches_untiled(rng):
    db = rng.standard_normal((1000, 24)).astype(np.float32)
    q = rng.standard_normal((30, 24)).astype(np.float32)
    small = Resources(workspace_limit_bytes=1_000_000)
    d1, i1 = brute_force.knn(q, db, k=7, res=small)
    d2, i2 = brute_force.knn(q, db, k=7)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-4, atol=1e-5)
    assert float(neighborhood_recall(np.asarray(i1), np.asarray(i2))) >= 0.999


def test_k_clamped_to_size(rng):
    db = rng.standard_normal((5, 8)).astype(np.float32)
    q = rng.standard_normal((3, 8)).astype(np.float32)
    d, i = brute_force.search(brute_force.build(db), q, k=10)
    assert d.shape == (3, 5)


def test_serialize_roundtrip(rng):
    db = rng.standard_normal((100, 16)).astype(np.float32)
    q = rng.standard_normal((10, 16)).astype(np.float32)
    idx = brute_force.build(db, metric="euclidean")
    buf = io.BytesIO()
    brute_force.serialize(idx, buf)
    buf.seek(0)
    idx2 = brute_force.deserialize(buf)
    d1, i1 = brute_force.search(idx, q, 5)
    d2, i2 = brute_force.search(idx2, q, 5)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_bitset_filter(rng):
    from raft_tpu.core.bitset import Bitset

    db = rng.standard_normal((200, 16)).astype(np.float32)
    q = rng.standard_normal((20, 16)).astype(np.float32)
    mask = rng.random(200) < 0.5
    bs = Bitset.from_mask(mask)
    idx = brute_force.build(db, metric="sqeuclidean")
    d, i = brute_force.search(idx, q, 10, filter=bs)
    i = np.asarray(i)
    assert mask[i].all()  # only allowed rows returned
    ref = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    ref = np.where(mask[None, :], ref, np.inf)
    np.testing.assert_array_equal(i[:, 0], ref.argmin(1))


@pytest.mark.parametrize("dt", ["int8", "uint8", "bfloat16"])
def test_narrow_dtypes(dt, rng):
    import jax.numpy as jnp

    if dt == "bfloat16":
        db = jnp.asarray(rng.standard_normal((500, 16)), jnp.bfloat16)
        q = jnp.asarray(rng.standard_normal((50, 16)), jnp.bfloat16)
        ref_db = np.asarray(db, np.float32)
        ref_q = np.asarray(q, np.float32)
    else:
        lo = -120 if dt == "int8" else 0
        db = rng.integers(lo, 120, (500, 16)).astype(dt)
        q = rng.integers(lo, 120, (50, 16)).astype(dt)
        ref_db = db.astype(np.float32)
        ref_q = q.astype(np.float32)
    _, i = brute_force.knn(q, db, 5, metric="sqeuclidean")
    ref = ((ref_q[:, None, :] - ref_db[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(i)[:, 0], ref.argmin(1))


@pytest.mark.parametrize("metric", ["sqeuclidean", "euclidean", "cosine",
                                    "inner_product"])
def test_fast_scan_bf16_refined(metric, rng):
    """bf16 single-pass scan + exact fp32 re-rank: near-perfect recall and
    exact distances on the returned candidates."""
    from raft_tpu.stats import neighborhood_recall

    db = rng.standard_normal((3000, 64)).astype(np.float32)
    q = rng.standard_normal((100, 64)).astype(np.float32)
    idx = brute_force.build(db, metric=metric)
    d_f, i_f = brute_force.search(idx, q, 10, scan_dtype="bfloat16")
    d_e, i_e = brute_force.search(idx, q, 10)
    rec = float(neighborhood_recall(np.asarray(i_f), np.asarray(i_e)))
    assert rec >= 0.99
    # wherever the fast path picked the true neighbor, its distance is exact
    same = np.asarray(i_f) == np.asarray(i_e)
    np.testing.assert_allclose(np.asarray(d_f)[same], np.asarray(d_e)[same],
                               rtol=1e-5, atol=1e-5)


def test_fast_scan_tiled_and_filtered(rng):
    from raft_tpu.core.bitset import Bitset
    from raft_tpu.core.resources import Resources

    db = rng.standard_normal((2500, 32)).astype(np.float32)
    q = rng.standard_normal((64, 32)).astype(np.float32)
    mask = rng.random(2500) < 0.6
    bs = Bitset.from_mask(mask)
    # tiny workspace forces multiple db tiles through the merge path
    res = Resources(workspace_limit_bytes=2 << 20)
    idx = brute_force.build(db, metric="sqeuclidean", res=res)
    d, i = brute_force.search(idx, q, 8, filter=bs, res=res,
                              scan_dtype="bfloat16")
    i = np.asarray(i)
    assert mask[i].all()
    ref = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    ref = np.where(mask[None, :], ref, np.inf)
    np.testing.assert_array_equal(i[:, 0], ref.argmin(1))


def test_batch_k_query_iterator(rng):
    """Batched neighbor iteration: concatenated batches equal one wide
    search (reference: make_batch_k_query)."""
    db = rng.standard_normal((500, 16)).astype(np.float32)
    q = rng.standard_normal((20, 16)).astype(np.float32)
    idx = brute_force.build(db, metric="sqeuclidean")
    batches = []
    it = brute_force.make_batch_k_query(idx, q, batch_size=7)
    for _ in range(3):
        d, i = next(it)
        assert i.shape == (20, 7)
        batches.append(np.asarray(i))
    d_ref, i_ref = brute_force.search(idx, q, 21)
    np.testing.assert_array_equal(np.concatenate(batches, 1),
                                  np.asarray(i_ref))
    # exhausting the iterator covers the whole dataset exactly once
    total = 21 + sum(i.shape[1] for _, i in it)
    assert total == 500


def test_choose_tiles_balanced():
    """The tile grid splits the db evenly: rounding down to the lane
    multiple used to give n_db=10000 a second, 99.8%-padding tile
    (2x scan work on the headline shape)."""
    from raft_tpu.neighbors.brute_force import _choose_tiles
    from raft_tpu.utils.shape import cdiv

    for n_db in (999, 10_000, 131_073, 200_000, 1_000_000):
        _, db_tile = _choose_tiles(10_000, n_db, 128, 10, 2 << 30)
        n_tiles = cdiv(n_db, db_tile)
        assert n_tiles * db_tile - n_db < 128 * n_tiles + 8, \
            (n_db, db_tile, n_tiles)
        if n_tiles > 1:
            assert db_tile % 128 == 0


@pytest.mark.parametrize("n,nq,k,db_tile,q_tile,case", [
    (20_000, 16, 10, 2_048, 16, "plain"),
    (30_000, 16, 64, 8_192, 16, "plain"),
    (20_000, 16, 10, 2_048, 16, "sparse_filter"),
    (20_000, 16, 10, 2_048, 16, "starved_filter"),
    (20_000, 16, 32, 4_096, 16, "duplicate_rows"),
    (20_000, 16, 10, 2_048, 16, "inner_product"),
    (20_000, 20, 10, 2_048, 8, "query_tiles"),
    (60_000, 16, 100, 12_800, 16, "plain"),
    (20_000, 16, 10, 2_048, 16, "tied_carry"),
    (60_000, 16, 100, 12_800, 16, "tied_carry"),
], ids=["k10", "k64", "sparse_filter", "starved_filter", "duplicate_rows",
        "inner_product", "query_tiles", "many_tiles", "tied_carry_k10",
        "tied_carry_k100"])
def test_group_scan_matches_direct(monkeypatch, n, nq, k, db_tile, q_tile,
                                   case):
    """The group-minima scan (``_group_topk``) answers exactly as the
    per-tile DIRECT top-k it replaces: the same ids, ties to the lower row,
    the same float32 bits. Both run on the same tiles, so XLA:CPU rounds
    the distances alike. ``tied_carry`` repeats a block of ten groups, so
    every merge weighs tile groups against carried ones of equal
    minimum."""
    import jax

    from raft_tpu.core.bitset import Bitset

    rng = np.random.default_rng(n + k)
    db = rng.standard_normal((n, 8)).astype(np.float32)
    if case == "duplicate_rows":
        db = db[:300][rng.integers(0, 300, n)]
    if case == "tied_carry":
        db = np.tile(db[:1_280], (n // 1_280 + 1, 1))[:n]
    q = rng.standard_normal((nq, 8)).astype(np.float32)
    metric = "inner_product" if case == "inner_product" else "sqeuclidean"
    flt = None
    if case.endswith("filter"):
        keep = rng.random(n) < (0.05 if case == "sparse_filter" else 0.0)
        keep[[17, 4_000, 19_999]] = True  # 3 rows pass a starved filter
        flt = Bitset.from_mask(keep)
    index = brute_force.build(db, metric=metric)
    monkeypatch.setattr(brute_force, "_choose_tiles",
                        lambda *a: (q_tile, db_tile))
    traced = []
    group_topk = brute_force._group_topk
    monkeypatch.setattr(brute_force, "_group_topk",
                        lambda *a: traced.append(a[1:4]) or group_topk(*a))
    jax.clear_caches()
    d, i = brute_force.search(index, q, k, filter=flt)
    assert traced == [(n, db_tile, k)]
    monkeypatch.setattr(brute_force, "GROUP", 1 << 30)  # per-tile DIRECT
    jax.clear_caches()
    want_d, want_i = brute_force.search(index, q, k, filter=flt)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(want_d))


# ------------------------------------------- the group kernel, interpreted


def _ulp_tol(q, db):
    """4 ulp of ‖q‖² + ‖x‖², the size of the terms an expanded distance
    cancels: XLA:CPU rounds the interpreted kernel's [q, d]·[d, rows]
    block and XLA's [q, d]·[rows, d]ᵀ dot apart by a few of them (on a
    v5e the two were bit-equal, PERF.md)."""
    return 4 * np.spacing(np.float32((q * q).sum(1).max()
                                     + (db * db).sum(1).max()))


_TILE_CASES = [
    ("remainder", 4_096, 904, 4),
    ("n_valid", 0, 2_048, 2),
    ("sparse_filter", 2_048, 2_048, 4),
    ("starved_filter", 2_048, 2_048, 8),
    ("inner_product", 0, 2_048, 4),
    ("l2sqrt", 1_024, 1_024, 1),
    ("duplicate_rows", 0, 4_096, 8),
]


@pytest.mark.parametrize("case,start,width,gb", _TILE_CASES + [
    (c + "-rows_major", s, w, g) for c, s, w, g in _TILE_CASES])
def test_group_scan_tile_matches_xla_tile(case, start, width, gb):
    """``pk.group_scan_tile`` (Mosaic interpreter) against XLA's tile
    producer (``_tile_groups``, every group gathered) on the same rows,
    reading the collection's [dim, rows] view or, for ``-rows_major``, its
    [rows, dim] blocks: +inf at the same places (the last group's pad, rows
    past ``n_valid``, rows a filter clears), finite values within a few
    ulp, each minimum its group's least value, and the same groups ranked
    first."""
    import jax.numpy as jnp

    from raft_tpu.ops import pallas_kernels as pk
    from raft_tpu.ops.distance import inner_product, l2_expanded

    case, _, major = case.partition("-")
    rng = np.random.default_rng(width + gb)
    n = 5_000
    db = rng.standard_normal((n, 8)).astype(np.float32)
    if case == "duplicate_rows":
        db = db[:300][rng.integers(0, 300, n)]
    q = rng.standard_normal((16, 8)).astype(np.float32)
    qn, xn = (q * q).sum(1), (db * db).sum(1)
    limit = 1_500 if case == "n_valid" else start + width
    keep = np.ones(n, bool)
    if case.endswith("filter"):
        keep = rng.random(n) < (0.05 if case == "sparse_filter" else 0.0)
        keep[[2_100, 3_000]] = True
    l2 = case != "inner_product"
    terms = np.where(keep, xn if l2 else 0.0, np.inf).astype(np.float32)
    tile, mins = pk.group_scan_tile(
        q, qn, db, terms, start, limit, width=width, gb=gb,
        lanes_rows=not major, l2=l2, sqrt=case == "l2sqrt", negate=not l2,
        filtered=case.endswith("filter"), interpret=True)
    rows = np.swapaxes(np.asarray(tile), 0, 1)

    def tile_dist(s, w):
        x = jnp.asarray(db[s:s + w])
        d = (l2_expanded(q, x, case == "l2sqrt", y_norms=xn[s:s + w]) if l2
             else inner_product(jnp.asarray(q), x))
        ids = np.arange(s, s + w)
        return jnp.where((ids >= limit) | ~keep[ids], np.inf if l2
                         else -np.inf, d)

    take, want_mins = brute_force._tile_groups(tile_dist, l2)(start, width)
    n_g = want_mins.shape[1]
    want_rows = np.asarray(take(jnp.broadcast_to(jnp.arange(n_g),
                                                 (16, n_g))))
    assert rows.shape == want_rows.shape
    np.testing.assert_array_equal(np.isinf(rows), np.isinf(want_rows))
    fin = np.isfinite(want_rows)
    np.testing.assert_allclose(rows[fin], want_rows[fin], rtol=0,
                               atol=_ulp_tol(q, db))
    np.testing.assert_array_equal(np.asarray(mins), rows.min(-1))
    kk = min(10, mins.shape[1])
    np.testing.assert_array_equal(
        np.argsort(np.asarray(mins), 1, kind="stable")[:, :kk],
        np.argsort(np.asarray(want_mins), 1, kind="stable")[:, :kk])


@pytest.mark.parametrize("n,nq,k,db_tile,q_tile,case", [
    (20_000, 16, 10, 2_048, 16, "plain"),
    (30_000, 16, 64, 8_192, 16, "plain"),
    (20_000, 16, 10, 2_048, 16, "sparse_filter"),
    (20_000, 16, 10, 2_048, 16, "starved_filter"),
    (20_000, 16, 32, 4_096, 16, "duplicate_rows"),
    (20_000, 16, 10, 2_048, 16, "inner_product"),
    (20_000, 16, 10, 2_560, 16, "l2sqrt"),
    (20_000, 20, 10, 2_048, 8, "query_tiles"),
    (20_000, 16, 10, 2_048, 16, "plain-lanes_rows"),
    (20_000, 16, 10, 2_048, 16, "sparse_filter-lanes_rows"),
    (20_000, 16, 32, 4_096, 16, "duplicate_rows-lanes_rows"),
    (20_000, 16, 10, 2_048, 16, "inner_product-lanes_rows"),
], ids=["k10", "k64", "sparse_filter", "starved_filter", "duplicate_rows",
        "inner_product", "l2sqrt", "query_tiles", "k10_lanes_rows",
        "sparse_filter_lanes_rows", "duplicate_rows_lanes_rows",
        "inner_product_lanes_rows"])
def test_group_kernel_search_matches_xla(monkeypatch, n, nq, k, db_tile,
                                         q_tile, case):
    """The exact scan over the group kernel's tiles (interpreted) answers
    as over XLA's: the same ids, ties to the lower row, distances within a
    few ulp; the search records the producer and the planning counter
    counts it. The CPU keeps the collection rows-major, so the kernel
    reads [rows, dim] blocks; ``-lanes_rows`` cases take the layout of a
    narrow collection on a TPU, and the kernel reads the [dim, rows] view."""
    import jax

    from raft_tpu.core.bitset import Bitset
    from raft_tpu.obs import explain as obs_explain
    from raft_tpu.obs.metrics import REGISTRY
    from raft_tpu.ops import pallas_kernels as pk

    case, _, lanes = case.partition("-")
    rng = np.random.default_rng(n + k)
    db = rng.standard_normal((n, 8)).astype(np.float32)
    q = rng.standard_normal((nq, 8)).astype(np.float32)
    if case == "duplicate_rows":
        # small integers: every distance is exact whatever the dot's
        # shape, so the many ties must go to the lower row on both paths
        db = rng.integers(-3, 4, (300, 8)).astype(np.float32)[
            rng.integers(0, 300, n)]
        q = rng.integers(-3, 4, (nq, 8)).astype(np.float32)
    metric = {"inner_product": "inner_product",
              "l2sqrt": "euclidean"}.get(case, "sqeuclidean")
    flt = None
    if case.endswith("filter"):
        keep = rng.random(n) < (0.05 if case == "sparse_filter" else 0.0)
        keep[[17, 4_000, 19_999]] = True
        flt = Bitset.from_mask(keep)
    index = brute_force.build(db, metric=metric)
    monkeypatch.setattr(brute_force, "_choose_tiles",
                        lambda *a: (q_tile, db_tile))
    jax.clear_caches()
    want_d, want_i = brute_force.search(index, q, k, filter=flt)
    monkeypatch.setattr(brute_force, "_GROUP_KERNEL_PLATFORMS",
                        ("tpu", "cpu"))
    monkeypatch.setattr(pk, "rows_on_lanes", lambda *a: bool(lanes))
    plans = REGISTRY.get("raft_tpu_group_scan_plans_total")
    before = dict((key, c.value) for key, c in plans.collect())
    jax.clear_caches()
    with obs_explain.capture() as cap:
        d, i = brute_force.search(index, q, k, filter=flt)
    scan = [r for r in cap.records if r.family == "brute_force_group_scan"]
    assert [(r.engine, r.reason) for r in scan] == [("pallas", "group_kernel")]
    assert scan[0].plan["rows_on_lanes"] == bool(lanes)
    assert scan[0].plan["interpret"]
    after = dict((key, c.value) for key, c in plans.collect())
    assert after[("pallas",)] - before.get(("pallas",), 0) == 1
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(d), np.asarray(want_d), rtol=0,
                               atol=_ulp_tol(q, db))


@pytest.mark.parametrize("metric,dtype,fast,dim,want", [
    ("sqeuclidean", np.float32, False, 96, ("pallas", "group_kernel")),
    ("inner_product", np.float32, False, 96, ("pallas", "group_kernel")),
    ("sqeuclidean", np.float32, True, 96, ("xla", "fast_scan")),
    ("cosine", np.float32, False, 96, ("xla", "unsupported_metric")),
    ("sqeuclidean", "bfloat16", False, 96, ("xla", "not_float32")),
    ("sqeuclidean", np.float32, False, 8_192, ("xla", "query_tile_vmem")),
], ids=["l2", "inner_product", "fast_scan", "cosine", "bfloat16",
        "wide_rows"])
def test_group_scan_producer_reasons(monkeypatch, metric, dtype, fast, dim,
                                     want):
    """Where the group kernel engages, and the reason code of each place
    it does not; off the kernel's platforms the XLA tile stays."""
    import jax

    from raft_tpu.ops.distance import resolve_metric

    m = resolve_metric(metric)
    args = (m, dtype, jax.devices("cpu")[0], 1_000, 3_125_000, 208_384, dim,
            100, fast, 4)
    assert brute_force.plan_group_scan(*args)[:2] == ("xla", "tpu_absent")
    monkeypatch.setattr(brute_force, "_GROUP_KERNEL_PLATFORMS",
                        ("tpu", "cpu"))
    plan = brute_force.plan_group_scan(*args)
    assert plan[:2] == want
    assert (plan.gb > 0) == (plan.producer == "pallas")
    assert plan.interpret == (plan.producer == "pallas")
    # a scan that never takes the group minima has no plan
    assert brute_force.plan_group_scan(*args[:7], 2_000, fast, 4) is None


def test_plan_group_scan_steps_fit_and_align():
    """A step is a power of two of groups that fits the VMEM budget with
    the whole query tile, by the terms the planner gives the solver, and
    divides the tile so each tile starts on a step; the deep cell's
    208,384-row tiles (1,628 groups) take 4."""
    from raft_tpu.ops import pallas_kernels as pk

    assert pk.plan_group_scan(1_000, 208_384, 96, True,
                              aligned_to=208_384) == 4
    assert pk.plan_group_scan(1_000, 210_048, 96, True,
                              aligned_to=210_048) == 1
    for q_tile, width, dim, lanes_rows in [
            (8, 1_000_000, 96, True), (64, 1_000_000, 96, True),
            (1_024, 3_000, 768, False), (200, 300_000, 128, False),
            (64, 1_000_000, 128, False), (1_000, 208_384, 96, True)]:
        gb = pk.plan_group_scan(q_tile, width, dim, lanes_rows)
        assert gb and gb & (gb - 1) == 0 and 128 % gb == 0
        assert gb * 128 <= max(width, 128) + 127
        t = pk.group_scan_vmem_terms(q_tile, dim, lanes_rows)
        rows, q = gb * 128, pk._sublanes(q_tile)
        assert (t["outer_bytes"] * rows + t["inner_bytes"] * q
                + t["cell_bytes"] * rows * q) <= pk.DEFAULT_VMEM_BUDGET
    assert pk.plan_group_scan(1_024, 100_000, 8_192, True) == 0
    assert pk.plan_group_scan(1_024, 100_000, 8_192, False) == 0
