"""Distributed-layer tests on the 8-device virtual CPU mesh — the simulated
backend seam the reference lacks (SURVEY.md §4: raft-dask test_comms.py runs
collectives on a LocalCUDACluster; here the mesh is the cluster)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from raft_tpu.parallel import comms as comms_mod
from raft_tpu.parallel import sharded
from raft_tpu.neighbors import brute_force
from raft_tpu.stats import neighborhood_recall


@pytest.fixture(scope="module")
def comms():
    assert len(jax.devices()) == 8, "conftest should provide 8 CPU devices"
    return comms_mod.init_comms(axis="data")


def test_comms_size_and_selftests(comms):
    assert comms.size == 8
    assert comms_mod.test_collective_allreduce(comms)
    assert comms_mod.test_collective_allgather(comms)
    assert comms_mod.test_collective_reducescatter(comms)
    assert comms_mod.test_pointToPoint_simple_send_recv(comms)


def test_comm_split():
    devs = jax.devices()
    c = comms_mod.init_comms(devs, axis="rows", mesh_shape=(4, 2),
                             axis_names=("rows", "cols"))
    assert c.size == 4
    c2 = c.comm_split("cols")
    assert c2.size == 2
    with pytest.raises(ValueError, match="not in mesh"):
        c.comm_split("nope")


def test_reduce_ops(comms):
    import jax.numpy as jnp

    x = comms.shard(jnp.arange(8, dtype=jnp.float32)[:, None], P("data"))

    def body(xs):
        v = xs[0, 0]
        return (comms.allreduce(v, "sum"), comms.allreduce(v, "max"),
                comms.allreduce(v, "min"))

    s, mx, mn = jax.jit(comms.run(body, P("data"), (P(), P(), P())))(x)
    assert float(s) == sum(range(8))
    assert float(mx) == 7.0
    assert float(mn) == 0.0


def test_sharded_knn_matches_single_device(comms):
    rng = np.random.default_rng(0)
    db = rng.standard_normal((1000, 16)).astype(np.float32)
    q = rng.standard_normal((32, 16)).astype(np.float32)
    d_ref, i_ref = brute_force.knn(q, db, k=10, metric="sqeuclidean")
    d, i = sharded.knn(comms, q, db, k=10, metric="sqeuclidean")
    assert float(neighborhood_recall(np.asarray(i), np.asarray(i_ref))) >= 0.999
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref), rtol=1e-3,
                               atol=1e-3)


def test_sharded_knn_unpadded_rows(comms):
    # n not divisible by 8 exercises the padding mask
    rng = np.random.default_rng(1)
    db = rng.standard_normal((1003, 16)).astype(np.float32)
    q = rng.standard_normal((16, 16)).astype(np.float32)
    d_ref, i_ref = brute_force.knn(q, db, k=5, metric="sqeuclidean")
    d, i = sharded.knn(comms, q, db, k=5)
    assert float(neighborhood_recall(np.asarray(i), np.asarray(i_ref))) >= 0.999


@pytest.fixture(scope="module")
def comms4():
    return comms_mod.init_comms(jax.devices()[:4], axis="data")


def _direct_reference(q, x, n_shards, k, db_tile, metric):
    """The full-row DIRECT answer over the row-sharded collection: each
    shard's distances in the scan's own tiles, from the shard's norms
    (XLA:CPU rounds a dot by its shape, so the same tiles give the same
    bits), padding rows masked, one ``lax.top_k`` over the whole row."""
    from raft_tpu.ops.distance import (l2_expanded, pairwise_core,
                                       resolve_metric, row_norms_sq)
    from raft_tpu.ops.select_k import SelectAlgo, select_k
    from raft_tpu.utils.shape import cdiv

    m = resolve_metric(metric)
    minimize = m != sharded.DistanceType.InnerProduct
    n = x.shape[0]
    shard = cdiv(n, n_shards)
    xp = np.concatenate([x, np.zeros((shard * n_shards - n, x.shape[1]),
                                     x.dtype)])
    if minimize:
        norms = [row_norms_sq(xp[r * shard:(r + 1) * shard])
                 for r in range(n_shards)]
        dist = jax.jit(lambda a, b, bn: l2_expanded(
            a, b, False, x_norms=row_norms_sq(a), y_norms=bn))
    else:
        norms = [np.zeros(shard, np.float32)] * n_shards
        dist = jax.jit(lambda a, b, bn: pairwise_core(a, b, m, 2.0, 1 << 30))
    row = np.concatenate([
        np.asarray(dist(q, xp[r * shard + s:r * shard + min(s + db_tile,
                                                             shard)],
                        norms[r][s:s + db_tile]))
        for r in range(n_shards) for s in range(0, shard, db_tile)], axis=1)
    row[:, n:] = np.inf if minimize else -np.inf
    v, i = select_k(row, k, select_min=minimize, algo=SelectAlgo.DIRECT)
    return np.asarray(v), np.asarray(i)


@pytest.mark.parametrize("n,k,db_tile,metric,dup", [
    (80_000, 10, 6_016, "sqeuclidean", False),
    (79_990, 10, 6_016, "sqeuclidean", False),
    (12_000, 300, 256, "sqeuclidean", False),
    (120_000, 100, 12_928, "sqeuclidean", False),
    (80_000, 50, 6_400, "sqeuclidean", True),
    (80_000, 10, 6_016, "inner_product", False),
], ids=["partial_last_tile", "padded_last_shard", "k_over_tile",
        "k100_groups", "duplicate_rows", "inner_product"])
def test_sharded_knn_local_scan_matches_direct(comms4, monkeypatch, n, k,
                                               db_tile, metric, dup):
    """The tiled local scan answers exactly as a DIRECT top-k over the
    whole row: the same ids (ties to the lower row) and the same float32
    bits, on four virtual devices."""
    rng = np.random.default_rng(n + k)
    if dup:
        x = rng.standard_normal((300, 8)).astype(np.float32)[
            rng.integers(0, 300, n)]
    else:
        x = rng.standard_normal((n, 8)).astype(np.float32)
    q = rng.standard_normal((16, 8)).astype(np.float32)
    monkeypatch.setattr(brute_force, "choose_tiles",
                        lambda nq, *a: (nq, db_tile))
    d, i = sharded.knn(comms4, q, x, k, metric=metric)
    want_d, want_i = _direct_reference(q, x, 4, k, db_tile, metric)
    np.testing.assert_array_equal(np.asarray(i), want_i)
    np.testing.assert_array_equal(np.asarray(d), want_d)


@pytest.mark.parametrize("n,k,db_tile,metric", [
    (79_990, 10, 6_016, "sqeuclidean"),
    (120_000, 100, 12_928, "sqeuclidean"),
    (80_000, 10, 6_400, "inner_product"),
], ids=["padded_last_shard", "k100_groups", "inner_product"])
def test_sharded_knn_group_kernel_matches_xla(comms4, monkeypatch, n, k,
                                              db_tile, metric):
    """The local scans over the group kernel's tiles (interpreted, inside
    the shard_map) answer as over XLA's: the last shard's padding rows
    never answer, the same ids, distances within 4 ulp of ‖q‖² + ‖x‖²
    (XLA:CPU rounds the two dots apart by a few; bit-equal on a v5e)."""
    from raft_tpu.obs import explain as obs_explain
    from raft_tpu.obs.metrics import REGISTRY

    rng = np.random.default_rng(n + k)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    q = rng.standard_normal((16, 8)).astype(np.float32)
    monkeypatch.setattr(brute_force, "choose_tiles",
                        lambda nq, *a: (nq, db_tile))
    want_d, want_i = sharded.knn(comms4, q, x, k, metric=metric)
    monkeypatch.setattr(brute_force, "_GROUP_KERNEL_PLATFORMS",
                        ("tpu", "cpu"))
    sharded.plan_cache_clear()
    jax.clear_caches()
    plans = REGISTRY.get("raft_tpu_group_scan_plans_total")
    before = dict((key, c.value) for key, c in plans.collect())
    with obs_explain.capture() as cap:
        d, i = sharded.knn(comms4, q, x, k, metric=metric)
    assert [r.engine for r in cap.records
            if r.family == "brute_force_group_scan"] == ["pallas"]
    after = dict((key, c.value) for key, c in plans.collect())
    assert after[("pallas",)] - before.get(("pallas",), 0) == 1
    assert int(np.asarray(i).max()) < n
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
    tol = 4 * np.spacing(np.float32((q * q).sum(1).max()
                                    + (x * x).sum(1).max()))
    np.testing.assert_allclose(np.asarray(d), np.asarray(want_d), rtol=0,
                               atol=tol)


def test_sharded_knn_compiles_once_per_shape(comms4):
    from raft_tpu.obs import device as obs_device

    rng = np.random.default_rng(5)
    # placed first, so that only the search itself can compile
    x = comms4.shard(rng.standard_normal((4_008, 8)).astype(np.float32),
                     P("data", None))
    q = comms4.shard(rng.standard_normal((24, 8)).astype(np.float32),
                     P(None, None))
    sharded.ensure_resources(None)  # the default resources' own compiles
    before = obs_device.compile_count()
    outs = [sharded.knn(comms4, q, x, 7) for _ in range(3)]
    jax.block_until_ready(outs)
    assert obs_device.compile_count() - before == 1
    np.testing.assert_array_equal(np.asarray(outs[0][1]),
                                  np.asarray(outs[2][1]))


def test_sharded_kmeans(comms):
    rng = np.random.default_rng(2)
    centers = rng.standard_normal((8, 16)) * 10
    labels = rng.integers(0, 8, 2000)
    x = (centers[labels] + rng.standard_normal((2000, 16))).astype(np.float32)
    c, got = sharded.kmeans_fit(comms, x, 8, n_iters=15,
                                key=jax.random.key(12))
    assert c.shape == (8, 16)
    got = np.asarray(got)
    # cluster purity: every true cluster maps to one dominant found label
    purity = 0
    for t in range(8):
        members = got[labels == t]
        purity += np.bincount(members, minlength=8).max()
    # plain Lloyd with random init occasionally merges two blobs; the gate
    # checks the distributed EM works, not init quality
    assert purity / len(x) >= 0.9


@pytest.mark.slow
def test_sharded_ivf_flat(comms):
    from raft_tpu.neighbors import ivf_flat

    rng = np.random.default_rng(3)
    db = rng.standard_normal((4000, 24)).astype(np.float32)
    q = rng.standard_normal((50, 24)).astype(np.float32)
    _, gt = brute_force.knn(q, db, k=10, metric="sqeuclidean")
    idx = sharded.build_ivf_flat(comms, db, ivf_flat.IndexParams(n_lists=8))
    d, i = sharded.search_ivf_flat(idx, q, 10,
                                   ivf_flat.SearchParams(n_probes=8))
    recall = float(neighborhood_recall(np.asarray(i), np.asarray(gt)))
    assert recall >= 0.999, f"sharded ivf_flat recall {recall}"
    # sharded search honors the bf16 fast scan too
    d, i = sharded.search_ivf_flat(
        idx, q, 10, ivf_flat.SearchParams(n_probes=8, scan_dtype="bfloat16"))
    recall = float(neighborhood_recall(np.asarray(i), np.asarray(gt)))
    assert recall >= 0.99, f"sharded bf16 ivf_flat recall {recall}"


@pytest.mark.slow
def test_sharded_ivf_pq(comms):
    from raft_tpu.neighbors import ivf_pq

    rng = np.random.default_rng(4)
    db = rng.standard_normal((4000, 32)).astype(np.float32)
    q = rng.standard_normal((50, 32)).astype(np.float32)
    _, gt = brute_force.knn(q, db, k=10, metric="sqeuclidean")
    idx = sharded.build_ivf_pq(
        comms, db, ivf_pq.IndexParams(n_lists=8, pq_dim=16, pq_bits=8,
                                      kmeans_n_iters=5))
    d, i = sharded.search_ivf_pq(idx, q, 10, ivf_pq.SearchParams(n_probes=8))
    i = np.asarray(i)
    assert i.shape == (50, 10)
    recall = float(neighborhood_recall(i, np.asarray(gt)))
    # full-probe PQ scan: recall limited only by quantization
    assert recall >= 0.7, f"sharded ivf_pq recall {recall}"


@pytest.mark.slow
def test_sharded_ivf_pq_lut_matches_cache(comms):
    """The memory-lean LUT engine under sharding must agree with the decoded
    cache engine (VERDICT r1 #7 gate). fp32 cache dtype → bit-exact ADC on
    both paths → identical neighbor sets."""
    from raft_tpu.neighbors import ivf_pq

    rng = np.random.default_rng(6)
    db = rng.standard_normal((2400, 32)).astype(np.float32)
    q = rng.standard_normal((40, 32)).astype(np.float32)
    from raft_tpu import Resources

    params = ivf_pq.IndexParams(n_lists=8, pq_dim=16, pq_bits=8,
                                kmeans_n_iters=4)
    # identical seeds → identical per-shard indexes; fp32 cache → both
    # engines evaluate the exact same ADC quantity
    cache_idx = sharded.build_ivf_pq(comms, db, params, res=Resources(seed=9),
                                     scan_mode="cache",
                                     scan_cache_dtype=jnp.float32)
    # scan_cache_dtype also governs the overflow-block decode for lut
    # builds: leaving it bf16 here would let spilled rows' distances drift
    # past rtol while the probed-list scans agree bit-for-bit
    lut_idx = sharded.build_ivf_pq(comms, db, params, res=Resources(seed=9),
                                   scan_mode="lut",
                                   scan_cache_dtype=jnp.float32)
    assert lut_idx.list_decoded is None  # memory-lean: no decoded cache
    assert lut_idx.list_codes is not None

    d_c, i_c = sharded.search_ivf_pq(cache_idx, q, 10,
                                     ivf_pq.SearchParams(n_probes=8))
    d_l, i_l = sharded.search_ivf_pq(
        lut_idx, q, 10, ivf_pq.SearchParams(n_probes=8, scan_mode="lut"))
    # same build seeds → same per-shard indexes; engines must agree
    np.testing.assert_allclose(np.asarray(d_l), np.asarray(d_c),
                               rtol=1e-4, atol=1e-4)
    overlap = np.mean([
        len(set(a) & set(b)) / 10.0
        for a, b in zip(np.asarray(i_l), np.asarray(i_c))])
    assert overlap >= 0.95, f"lut/cache neighbor overlap {overlap}"
    # engine-mismatch guards
    with pytest.raises(ValueError, match="no decoded cache"):
        sharded.search_ivf_pq(lut_idx, q, 10,
                              ivf_pq.SearchParams(scan_mode="cache"))
    with pytest.raises(ValueError, match="no packed codes"):
        sharded.search_ivf_pq(cache_idx, q, 10,
                              ivf_pq.SearchParams(scan_mode="lut"))


def test_ring_pairwise_distance_matches_single_device(comms):
    """Ring-scheduled MNMG pairwise (x stationary, y rotating via
    ppermute) must equal the single-device engine bit-for-bit."""
    from raft_tpu.ops.distance import pairwise_distance as pd_single

    rng = np.random.default_rng(12)
    x = rng.standard_normal((130, 24)).astype(np.float32)
    y = rng.standard_normal((75, 24)).astype(np.float32)
    for metric in ("sqeuclidean", "cosine", "inner_product"):
        got = np.asarray(sharded.pairwise_distance(comms, x, y, metric))
        want = np.asarray(pd_single(x, y, metric))
        assert got.shape == want.shape == (130, 75)
        np.testing.assert_allclose(got, want, atol=1e-4, err_msg=metric)


def test_allgatherv_gatherv(comms):
    counts = [(r % 3) + 1 for r in range(comms.size)]
    cap = max(counts)
    x = np.zeros((comms.size, cap, 2), np.float32)
    for r in range(comms.size):
        x[r, :counts[r]] = r + 1
    xs = comms.shard(jnp.asarray(x), P(comms.axis))

    def body(v):
        return comms.allgatherv(v[0], counts)

    out = np.asarray(jax.jit(comms.run(body, P(comms.axis), P()))(xs))
    want = np.concatenate([np.full((counts[r], 2), r + 1, np.float32)
                           for r in range(comms.size)])
    np.testing.assert_allclose(out, want)


def test_device_send_recv_and_multicast(comms):
    n = comms.size
    x = jnp.arange(n, dtype=jnp.float32)[:, None]
    xs = comms.shard(x, P(comms.axis))

    # reversal permutation
    table = list(reversed(range(n)))

    def body(v):
        return comms.device_send_recv(v, table)

    out = np.asarray(jax.jit(comms.run(body, P(comms.axis),
                                       P(comms.axis)))(xs))
    want = np.zeros(n)
    for r, d in enumerate(table):
        want[d] = r
    np.testing.assert_allclose(out.ravel(), want)

    # multicast root 0 → ranks {1, 2}
    def body2(v):
        return comms.device_multicast_sendrecv(v[0], 0, [1, 2])

    out2 = np.asarray(jax.jit(comms.run(body2, P(comms.axis),
                                        P(comms.axis)))(xs))
    want2 = np.arange(n, dtype=np.float32)
    want2[1] = 0
    want2[2] = 0
    np.testing.assert_allclose(out2.ravel(), want2)


@pytest.mark.slow
def test_sharded_cagra(tmp_path):
    """Runs in a fresh subprocess: compiling the nn_descent build program
    ~300 tests into a long-lived process intermittently segfaults this
    image's XLA:CPU (LLVM JIT; see ROUND_NOTES "Known flake") — the same
    compile is reliable in a fresh process, which is also how real
    deployments encounter it."""
    import pathlib
    import subprocess
    import sys

    body = pathlib.Path(__file__).with_name("_sharded_cagra_body.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1])
    r = subprocess.run([sys.executable, str(body)], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "SHARDED_CAGRA_OK" in r.stdout, r.stdout[-3000:]


# ------------------------------------------- per-shard search trace spans


class TestShardedSearchSpans:
    """``set_span_sink()`` flips every search entrypoint onto the two-phase
    dispatch (local scan sharded, per-shard fence, host-side
    ``_elastic_merge``) — results must stay bit-identical to the fused
    single-program path, and the tape must carry one ``shard_search``
    child per rank under the parent's trace id."""

    def _run_instrumented(self, fn):
        from raft_tpu.obs import spans as obs_spans

        sink = obs_spans.ListSink()
        prev = sharded.set_span_sink(sink)
        try:
            out = fn()
        finally:
            sharded.set_span_sink(prev)
        return out, sink.records

    def _check_spans(self, records, family, size=8):
        children = [r for r in records if r["kind"] == "shard_search"]
        parents = [r for r in records if r["kind"] == "sharded_search"]
        assert len(parents) == 1
        parent = parents[0]
        assert parent["family"] == family
        assert parent["n_shards"] == size
        assert sorted(c["rank"] for c in children) == list(range(size))
        assert all(c["trace_id"] == parent["trace_id"] for c in children)
        assert all(c["family"] == family for c in children)
        # one distinct device per shard; timing fields present
        assert len({c["device"] for c in children}) == size
        for key in ("launch_ms", "merge_ms", "total_ms"):
            assert parent[key] >= 0.0
        assert all(c["device_ms"] >= 0.0 for c in children)

    def test_set_span_sink_returns_previous(self):
        marker = object()
        assert sharded.set_span_sink(marker) is None
        assert sharded.set_span_sink(None) is marker
        assert sharded._span_sink() is None

    def test_knn_spans_and_parity(self, comms, rng):
        data = rng.standard_normal((1000, 32)).astype(np.float32)
        q = rng.standard_normal((20, 32)).astype(np.float32)
        v0, i0 = sharded.knn(comms, q, data, k=10)
        (v1, i1), records = self._run_instrumented(
            lambda: sharded.knn(comms, q, data, k=10))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        self._check_spans(records, "brute_force")

    @pytest.mark.slow
    def test_ivf_flat_spans_and_parity(self, comms, rng):
        from raft_tpu.neighbors import ivf_flat

        data = rng.standard_normal((800, 32)).astype(np.float32)
        q = rng.standard_normal((16, 32)).astype(np.float32)
        idx = sharded.build_ivf_flat(comms, data,
                                     ivf_flat.IndexParams(n_lists=8))
        params = ivf_flat.SearchParams(n_probes=4)
        v0, i0 = sharded.search_ivf_flat(idx, q, 10, params)
        (v1, i1), records = self._run_instrumented(
            lambda: sharded.search_ivf_flat(idx, q, 10, params))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        self._check_spans(records, "ivf_flat")

    @pytest.mark.slow
    def test_ivf_pq_spans_and_parity(self, comms, rng):
        from raft_tpu.neighbors import ivf_pq

        data = rng.standard_normal((800, 32)).astype(np.float32)
        q = rng.standard_normal((16, 32)).astype(np.float32)
        idx = sharded.build_ivf_pq(comms, data,
                                   ivf_pq.IndexParams(n_lists=8, pq_dim=8))
        params = ivf_pq.SearchParams(n_probes=4)
        v0, i0 = sharded.search_ivf_pq(idx, q, 8, params)
        (v1, i1), records = self._run_instrumented(
            lambda: sharded.search_ivf_pq(idx, q, 8, params))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        self._check_spans(records, "ivf_pq")

    def test_no_sink_emits_nothing(self, comms, rng):
        """Default path: no sink, no spans — the zero-overhead guarantee."""
        from raft_tpu.obs import spans as obs_spans

        data = rng.standard_normal((256, 16)).astype(np.float32)
        q = rng.standard_normal((4, 16)).astype(np.float32)
        sink = obs_spans.ListSink()
        # sink NOT installed
        sharded.knn(comms, q, data, k=4)
        assert sink.records == []
