"""Fused Pallas scan+select (``scan_mode="pallas"``) — interpret-mode
parity, VMEM planner properties, and engine dispatch.

Every kernel test forces TINY tiles so the running top-k carry crosses
the merge boundary (several inner grid steps revisit the output block)
and uses ragged extents so the padded tails exercise the +inf/-1
sentinel path. References are plain numpy. Dispatch tests drive the
public search APIs: on CPU ``scan_mode="pallas"`` must silently fall
back to XLA; with RAFT_TPU_PALLAS_INTERPRET=1 it must route through the
Mosaic interpreter and epsilon-match the XLA engines end to end.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq
from raft_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module", autouse=True)
def _drop_interpret_executables():
    """Interpret-mode pallas_call lowers to very large XLA:CPU programs;
    keeping their executables cached for the rest of the session pushes
    the LLVM JIT into its known environment-level segfault a few hundred
    tests later. Drop them (and everything else — later modules recompile
    their own shapes anyway) when this module is done."""
    yield
    jax.clear_caches()


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


def _np_topk(d, k):
    """Ascending (values, ids) per row; +inf / -1 past the row's extent."""
    m, n = d.shape
    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(d, order, axis=1)
    if k > n:
        pad = np.full((m, k - n), np.inf, d.dtype)
        vals = np.concatenate([vals, pad], axis=1)
        order = np.concatenate(
            [order, np.full((m, k - n), -1, order.dtype)], axis=1)
    return vals, order


def _assert_topk_match(v, i, ref_d, k, atol=1e-4):
    """Sorted-value parity + id consistency (ties at the k boundary may
    reorder ids between engines, so id equality is checked through the
    distance each id maps back to, not positionally)."""
    v = np.asarray(v)
    i = np.asarray(i)
    ref_v, _ = _np_topk(ref_d, k)
    np.testing.assert_allclose(v, ref_v, rtol=1e-4, atol=atol)
    valid = i >= 0
    rows, cols = np.nonzero(valid)
    picked = ref_d[rows, i[rows, cols]]
    np.testing.assert_allclose(v[valid], picked, rtol=1e-4, atol=atol)
    assert np.all(v[~valid] == np.inf)


# ------------------------------------------------------------ VMEM planner

def test_solve_vmem_tiles_respects_budget():
    from raft_tpu.core.resources import solve_vmem_tiles

    budget = 12 << 20
    for cell, ob, ib, imax in [(12, 600, 516, 1024), (4, 4096, 8, 131072),
                               (12, 33000, 516, 256)]:
        outer, inner = solve_vmem_tiles(budget, cell, ob, ib, imax)
        assert outer % 8 == 0 and inner % 128 == 0
        if (outer, inner) != (8, 128):  # degraded floor is best-effort
            assert outer * ob + inner * ib + outer * inner * cell <= budget


@pytest.mark.parametrize("m,n,dim,k", [
    (10_000, 1_000_000, 128, 100), (100, 300, 16, 10), (8, 128, 8, 1)])
def test_plan_fused_topk_tiles_fit_vmem(m, n, dim, k):
    tm, tn = pk.plan_fused_topk_tiles(m, n, dim, k)
    assert tm % 8 == 0 and tn % 128 == 0
    assert pk.fused_topk_tile_bytes(tm, tn, dim, k) <= pk.DEFAULT_VMEM_BUDGET
    assert pk.fused_topk_tile_bytes(tm, tn, dim, k) <= pk.VMEM_LIMIT_BYTES


@pytest.mark.parametrize("list_pad", [7, 24, 1000, 1464])
def test_plan_fused_ivf_tile_divides_layout(list_pad):
    for itemsize in (2, 4):
        pt = pk.plan_fused_ivf_tile(list_pad, 128, 100, itemsize)
        assert list_pad % pt == 0
        assert (pk.fused_ivf_vmem_bytes(pt, 128, 100, itemsize)
                <= pk.DEFAULT_VMEM_BUDGET or pt == 1)
    # the sift-1M slab fits whole: one DMA per probe, no inner axis
    assert pk.plan_fused_ivf_tile(1464, 128, 100, 4) == 1464


@pytest.mark.parametrize("list_pad", [16, 24, 1464])
def test_plan_fused_pq_tile_divides_layout(list_pad):
    pt = pk.plan_fused_pq_tile(list_pad, 64, 256, 2, 100)
    assert list_pad % pt == 0
    assert (pk.fused_pq_vmem_bytes(pt, 64, 256, 2, 100)
            <= pk.DEFAULT_VMEM_BUDGET or pt == 1)


def test_fused_workspace_accounting_positive():
    assert pk.fused_topk_workspace_bytes(100, 1000, 32, 10) > 0
    assert pk.fused_ivf_workspace_bytes(16, 4, 32, 8, 24, 10) > 0
    assert pk.fused_pq_workspace_bytes(16, 4, 32, 8, 24, 8, 256, 4, 10) > 0


# --------------------------------------------- fused_l2_topk (brute force)

@pytest.mark.parametrize("k", [1, 10, 64])
def test_fused_l2_topk_parity(rng, k):
    # tn=128 over n=300 → three db tiles: the carry merges twice
    x = rng.standard_normal((23, 16)).astype(np.float32)
    y = rng.standard_normal((300, 16)).astype(np.float32)
    v, i = pk.fused_l2_topk(x, y, k, tm=8, tn=128, interpret=True)
    d = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    _assert_topk_match(v, i, d, k)


def test_fused_l2_topk_k_exceeds_rows(rng):
    # k > n: the tail of the carry stays at the +inf / -1 sentinels
    x = rng.standard_normal((9, 8)).astype(np.float32)
    y = rng.standard_normal((20, 8)).astype(np.float32)
    v, i = pk.fused_l2_topk(x, y, 64, tm=8, tn=128, interpret=True)
    d = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    _assert_topk_match(v, i, d, 64)
    assert np.all(np.asarray(i)[:, 20:] == -1)


def test_fused_l2_topk_rejects_large_k(rng):
    with pytest.raises(ValueError, match="small-k"):
        pk.fused_l2_topk(np.zeros((8, 8), np.float32),
                         np.zeros((8, 8), np.float32), 2000)


# ------------------------------------------------ fused_ivf_topk (flat/pq)

def _ivf_ref(probes, qres, list_data, row_norms, ids, clamp):
    """Per-query candidate distances over probed slabs, -1 slots → +inf."""
    nq, P = probes.shape
    pad = list_data.shape[1]
    d = np.full((nq, P * pad), np.inf, np.float32)
    gid = np.full((nq, P * pad), -1, np.int64)
    for qi in range(nq):
        for pj in range(P):
            sl = probes[qi, pj]
            qn = (qres[qi, pj].astype(np.float32) ** 2).sum()
            dots = list_data[sl].astype(np.float32) @ qres[qi, pj]
            dist = qn + row_norms[sl] - 2.0 * dots
            if clamp:
                dist = np.maximum(dist, 0.0)
            dist = np.where(ids[sl] < 0, np.inf, dist)
            d[qi, pj * pad:(pj + 1) * pad] = dist
            gid[qi, pj * pad:(pj + 1) * pad] = ids[sl]
    return d, gid


def _assert_ivf_match(v, i, ref_d, ref_gid, k, atol=1e-4):
    v, i = np.asarray(v), np.asarray(i)
    order = np.argsort(ref_d, axis=1, kind="stable")[:, :k]
    ref_v = np.take_along_axis(ref_d, order, axis=1)
    np.testing.assert_allclose(np.where(v == np.inf, np.inf, v), ref_v,
                               rtol=1e-4, atol=atol)
    # ids map back to a distance the candidate set actually holds for
    # them (a slab probed twice contributes the same id at DIFFERENT
    # residual distances — any of its copies is a valid pairing)
    for qi in range(v.shape[0]):
        lut = {}
        for dist, g in zip(ref_d[qi], ref_gid[qi]):
            if g >= 0:
                lut.setdefault(g, []).append(dist)
        for dist, g in zip(v[qi], i[qi]):
            if g < 0:
                assert dist == np.inf
            else:
                assert any(abs(c - dist) <= atol + 1e-4 * abs(dist)
                           for c in lut[g])


@pytest.mark.parametrize("k", [1, 10])
def test_fused_ivf_topk_parity_carry_boundary(rng, k):
    # pad_tile=8 over list_pad=24 → three slab tiles per probe
    L, pad, rot, nq, P = 6, 24, 16, 5, 3
    data = rng.standard_normal((L, pad, rot)).astype(np.float32)
    ids = np.arange(L * pad, dtype=np.int32).reshape(L, pad)
    ids[:, -5:] = -1  # ragged tails: unfilled slots
    norms = (data.astype(np.float32) ** 2).sum(-1)
    probes = rng.integers(0, L, (nq, P)).astype(np.int32)
    qres = rng.standard_normal((nq, P, rot)).astype(np.float32)
    qn = (qres ** 2).sum(-1)
    v, i = pk.fused_ivf_topk(probes, qres, qn, data, norms, ids, k,
                             pad_tile=8, clamp=True, interpret=True)
    ref_d, ref_gid = _ivf_ref(probes, qres, data, norms, ids, clamp=True)
    _assert_ivf_match(v, i, ref_d, ref_gid, k)


def test_fused_ivf_topk_bf16_cache_fp32_accum(rng):
    # bf16 slab upcast in-kernel, fp32 accumulation (the pq scan cache)
    L, pad, rot, nq, P, k = 4, 16, 8, 4, 2, 6
    data32 = rng.standard_normal((L, pad, rot)).astype(np.float32)
    data = data32.astype(jnp.bfloat16)
    ids = np.arange(L * pad, dtype=np.int32).reshape(L, pad)
    norms = (np.asarray(data, np.float32) ** 2).sum(-1)
    probes = rng.integers(0, L, (nq, P)).astype(np.int32)
    qres = rng.standard_normal((nq, P, rot)).astype(np.float32)
    qn = (qres ** 2).sum(-1)
    v, i = pk.fused_ivf_topk(probes, qres, qn, data, norms, ids, k,
                             pad_tile=8, clamp=False, interpret=True)
    ref_d, ref_gid = _ivf_ref(probes, np.asarray(qres),
                              np.asarray(data, np.float32), norms, ids,
                              clamp=False)
    _assert_ivf_match(v, i, ref_d, ref_gid, k, atol=5e-2)


def test_fused_ivf_topk_rejects_non_divisor_tile(rng):
    L, pad, rot = 2, 24, 8
    data = np.zeros((L, pad, rot), np.float32)
    with pytest.raises(ValueError, match="does not divide"):
        pk.fused_ivf_topk(np.zeros((1, 1), np.int32),
                          np.zeros((1, 1, rot), np.float32),
                          np.zeros((1, 1), np.float32), data,
                          np.zeros((L, pad), np.float32),
                          np.zeros((L, pad), np.int32), 4, pad_tile=7,
                          interpret=True)


# ------------------------------------------------- fused_pq_topk (lut)

def test_fused_pq_topk_parity(rng):
    L, pad, pq_dim, book, pq_len, nq, P, k = 4, 16, 4, 16, 2, 3, 2, 5
    rot = pq_dim * pq_len
    centers = rng.standard_normal((L, rot)).astype(np.float32)
    q_rot = rng.standard_normal((nq, rot)).astype(np.float32)
    cb = rng.standard_normal((pq_dim, book, pq_len)).astype(np.float32)
    cbn = (cb ** 2).sum(-1)
    codes = rng.integers(0, book, (L, pad, pq_dim)).astype(np.uint8)
    ids = np.arange(L * pad, dtype=np.int32).reshape(L, pad)
    ids[:, -3:] = -1
    probes = rng.integers(0, L, (nq, P)).astype(np.int32)
    v, i = pk.fused_pq_topk(probes, q_rot, centers, cb, cbn, codes, ids, k,
                            pad_tile=8, interpret=True)
    # numpy ADC reference: residual LUT per (query, probe, subspace)
    nq_, P_ = probes.shape
    ref_d = np.full((nq_, P_ * pad), np.inf, np.float32)
    ref_g = np.full((nq_, P_ * pad), -1, np.int64)
    for qi in range(nq_):
        for pj in range(P_):
            sl = probes[qi, pj]
            res = (q_rot[qi] - centers[sl]).reshape(pq_dim, pq_len)
            lut = ((res[:, None, :] - cb) ** 2).sum(-1)  # [pq_dim, book]
            dist = lut[np.arange(pq_dim)[None, :],
                       codes[sl].astype(np.int64)].sum(-1)
            dist = np.where(ids[sl] < 0, np.inf, dist)
            ref_d[qi, pj * pad:(pj + 1) * pad] = dist
            ref_g[qi, pj * pad:(pj + 1) * pad] = ids[sl]
    _assert_ivf_match(v, i, ref_d, ref_g, k, atol=1e-3)


def test_fused_pq_topk_rejects_packed_codes():
    # pq_bits<8 packs several codes per byte: n_code_bytes != pq_dim
    with pytest.raises(ValueError, match="pq_bits=8"):
        pk.fused_pq_topk(np.zeros((1, 1), np.int32),
                         np.zeros((1, 8), np.float32),
                         np.zeros((2, 8), np.float32),
                         np.zeros((4, 16, 2), np.float32),
                         np.zeros((4, 16), np.float32),
                         np.zeros((2, 8, 2), np.uint8),
                         np.zeros((2, 8), np.int32), 4, interpret=True)


# -------------------------------------------------------- engine dispatch

@pytest.fixture(scope="module")
def small_db():
    rng = np.random.default_rng(3)
    db = rng.standard_normal((600, 32)).astype(np.float32)
    q = rng.standard_normal((17, 32)).astype(np.float32)
    return db, q


def test_brute_force_pallas_mode_cpu_fallback(small_db):
    # no interpret opt-in: "pallas" on CPU must fall back bit-exactly
    db, q = small_db
    bf = brute_force.build(db, metric="sqeuclidean")
    vx, ix = brute_force.search(bf, q, 10, scan_mode="xla")
    vp, ip = brute_force.search(bf, q, 10, scan_mode="pallas")
    np.testing.assert_array_equal(np.asarray(ix), np.asarray(ip))
    np.testing.assert_array_equal(np.asarray(vx), np.asarray(vp))
    with pytest.raises(ValueError, match="scan_mode"):
        brute_force.search(bf, q, 10, scan_mode="mosaic")


@pytest.mark.parametrize("metric", ["sqeuclidean", "euclidean"])
def test_brute_force_pallas_interpret_parity(small_db, monkeypatch, metric):
    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    db, q = small_db
    bf = brute_force.build(db, metric=metric)
    vx, ix = brute_force.search(bf, q, 10, scan_mode="xla")
    vp, ip = brute_force.search(bf, q, 10, scan_mode="pallas")
    np.testing.assert_allclose(np.asarray(vp), np.asarray(vx),
                               rtol=1e-4, atol=1e-4)
    assert np.mean(np.asarray(ip) == np.asarray(ix)) > 0.99


def test_ivf_flat_pallas_interpret_parity_with_overflow(monkeypatch):
    # tight pad budget forces spill: the fused path must merge the
    # XLA-scanned overflow block into the in-kernel carry's results
    rng = np.random.default_rng(5)
    db = np.concatenate([
        rng.standard_normal((500, 16)).astype(np.float32),
        rng.standard_normal((150, 16)).astype(np.float32) * 0.05 + 2.0])
    q = rng.standard_normal((9, 16)).astype(np.float32)
    idx = ivf_flat.build(db, ivf_flat.IndexParams(
        n_lists=8, list_pad_expansion=1.01))
    assert idx.overflow_data.shape[0] > 0
    vx, ix = ivf_flat.search(idx, q, 10, ivf_flat.SearchParams(
        n_probes=4, scan_mode="xla"))
    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    vp, ip = ivf_flat.search(idx, q, 10, ivf_flat.SearchParams(
        n_probes=4, scan_mode="pallas"))
    np.testing.assert_allclose(np.asarray(vp), np.asarray(vx),
                               rtol=1e-4, atol=1e-4)
    assert np.mean(np.asarray(ip) == np.asarray(ix)) > 0.99
    # and without the opt-in the same params fall back cleanly on CPU
    monkeypatch.delenv("RAFT_TPU_PALLAS_INTERPRET")
    vf, if_ = ivf_flat.search(idx, q, 10, ivf_flat.SearchParams(
        n_probes=4, scan_mode="pallas"))
    np.testing.assert_array_equal(np.asarray(if_), np.asarray(ix))


def test_ivf_flat_fused_metric_fallback(small_db, monkeypatch):
    # inner-product is outside the fused fallback matrix: "pallas" must
    # quietly use the XLA engine even with the interpret opt-in
    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    db, q = small_db
    idx = ivf_flat.build(db, ivf_flat.IndexParams(
        n_lists=8, metric="inner_product"))
    vx, ix = ivf_flat.search(idx, q, 5, ivf_flat.SearchParams(
        n_probes=4, scan_mode="xla"))
    vp, ip = ivf_flat.search(idx, q, 5, ivf_flat.SearchParams(
        n_probes=4, scan_mode="pallas"))
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ix))


def test_ivf_pq_pallas_interpret_parity(small_db, monkeypatch):
    db, q = small_db
    idx = ivf_pq.build(db, ivf_pq.IndexParams(
        n_lists=8, pq_dim=8, pq_bits=8))
    sp = dict(n_probes=4)
    vx, ix = ivf_pq.search(idx, q, 10, ivf_pq.SearchParams(
        scan_mode="cache", scan_cache_dtype=jnp.float32, **sp))
    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    vp, ip = ivf_pq.search(idx, q, 10, ivf_pq.SearchParams(
        scan_mode="pallas", scan_cache_dtype=jnp.float32, **sp))
    np.testing.assert_allclose(np.asarray(vp), np.asarray(vx),
                               rtol=1e-4, atol=1e-4)
    assert np.mean(np.asarray(ip) == np.asarray(ix)) > 0.99
    monkeypatch.delenv("RAFT_TPU_PALLAS_INTERPRET")
    vf, if_ = ivf_pq.search(idx, q, 10, ivf_pq.SearchParams(
        scan_mode="pallas", scan_cache_dtype=jnp.float32, **sp))
    np.testing.assert_array_equal(np.asarray(if_), np.asarray(ix))


def test_fused_dispatch_cpu_defaults():
    # without the interpret hook, CPU never routes to the fused kernels
    assert pk.fused_dispatch("brute_force", "xla") == (False, False)
    assert pk.fused_dispatch("brute_force", "pallas") == (False, False)
    assert pk.fused_dispatch("brute_force", "auto") == (False, False)


# ------------------------------------------- k-pad rule exemption (no 2x pad)

def test_select_k_pad_rules_flag_controls_k_padding():
    import importlib

    import jax

    # the package re-exports the select_k FUNCTION under the same name;
    # the module itself holds the pad-rule hooks
    sk = importlib.import_module("raft_tpu.ops.select_k")

    key = sk._platform_key()
    try:
        sk.set_pad_rules(key, [{"n": 256, "k": 10, "k_pad": 64}])
        v = jnp.zeros((4, 256), jnp.float32)
        padded = str(jax.make_jaxpr(
            lambda x: sk.select_k(x, 10, algo=sk.SelectAlgo.DIRECT))(v))
        exempt = str(jax.make_jaxpr(
            lambda x: sk.select_k(x, 10, algo=sk.SelectAlgo.DIRECT,
                                  pad_rules=False))(v))
        assert "k=64" in padded      # the measured pad rule applies...
        assert "k=64" not in exempt  # ...but never on the exempt path
        assert "k=10" in exempt
    finally:
        sk.set_pad_rules(key, None)


def test_fused_ivf_dispatch_merge_is_pad_exempt(monkeypatch):
    """The fused path's only select_k calls are the XLA coarse probe
    selection (a real slab — pad rules apply) and the overflow merge over
    the in-kernel carry (already selected — MUST be pad-exempt)."""
    rng = np.random.default_rng(7)
    db = np.concatenate([
        rng.standard_normal((400, 16)).astype(np.float32),
        rng.standard_normal((120, 16)).astype(np.float32) * 0.05 + 2.0])
    q = rng.standard_normal((5, 16)).astype(np.float32)
    idx = ivf_flat.build(db, ivf_flat.IndexParams(
        n_lists=8, list_pad_expansion=1.01))
    assert idx.overflow_data.shape[0] > 0

    calls = []
    real = ivf_flat.select_k

    def spy(values, k, *a, **kw):
        calls.append(kw.get("pad_rules", True))
        return real(values, k, *a, **kw)

    monkeypatch.setattr(ivf_flat, "select_k", spy)
    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    ivf_flat.search(idx, q, 10, ivf_flat.SearchParams(
        n_probes=4, scan_mode="pallas"))
    assert calls, "fused dispatch traced no select_k call"
    assert calls.count(False) >= 1, (
        "overflow merge over the in-kernel carry must pass pad_rules=False"
    )


# --------------------------------------- fused cagra beam search (VMEM beam)

def _np_beam_walk(q, db, graph, seeds, k, itopk, width, max_iter):
    """Greedy beam walk, one query, squared L2 — the kernel's documented
    semantics in plain numpy: first-occurrence seed/target dedup, ``width``
    cheapest-unexpanded parents per hop, stable ascending merges, fixed
    iteration budget. float64 scoring so the reference's tie/order
    decisions never depend on fp32 rounding."""
    def d2(ids):
        diff = db[ids].astype(np.float64) - q.astype(np.float64)
        return (diff * diff).sum(-1)

    seen = []
    for s in seeds:
        s = int(s)
        if s >= 0 and s not in seen:
            seen.append(s)
    buf_ids = np.array(seen, np.int64)
    buf_d = d2(buf_ids)
    order = np.argsort(buf_d, kind="stable")[:itopk]
    buf_ids, buf_d = buf_ids[order], buf_d[order]
    flags = np.zeros(len(buf_ids), bool)
    for _ in range(max_iter):
        unexp = np.nonzero(~flags)[0]
        if unexp.size == 0:
            break
        parents = unexp[:width]
        flags[parents] = True
        targets = []
        for p in parents:
            for t in graph[buf_ids[p]]:
                t = int(t)
                if t >= 0 and t not in targets and t not in buf_ids:
                    targets.append(t)
        if not targets:
            continue
        t_ids = np.array(targets, np.int64)
        all_ids = np.concatenate([buf_ids, t_ids])
        all_d = np.concatenate([buf_d, d2(t_ids)])
        all_f = np.concatenate([flags, np.zeros(len(t_ids), bool)])
        order = np.argsort(all_d, kind="stable")[:itopk]
        buf_ids, buf_d, flags = all_ids[order], all_d[order], all_f[order]
    out_d = np.full(k, np.inf)
    out_i = np.full(k, -1, np.int64)
    m = min(k, len(buf_ids))
    out_d[:m], out_i[:m] = buf_d[:m], buf_ids[:m]
    return out_d, out_i


# (seed, n, dim, degree, nq, k, itopk, width, n_seeds, ct) — spans the
# tile boundaries: width*degree below/at/above one ct chunk, a ragged
# last graph tile (wd=12 padded to 16), and multi-chunk seed streams.
_CAGRA_COMBOS = [
    (0, 500, 24, 8, 4, 5, 16, 1, 20, 16),
    (2, 300, 24, 6, 3, 4, 16, 2, 20, 16),   # ragged: wd=12 < chunk 16
    (1, 600, 32, 16, 2, 8, 64, 4, 64, 32),  # wd=64: two chunks per hop
]


def _cagra_case(seed, n, dim, degree, nq, n_seeds):
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((n, dim)).astype(np.float32)
    q = rng.standard_normal((nq, dim)).astype(np.float32)
    graph = rng.integers(0, n, (n, degree)).astype(np.int32)
    graph[5, :3] = -1  # invalid edges must be skipped, not scored
    seeds = rng.integers(0, n, (nq, n_seeds)).astype(np.int32)
    seeds[:, 1] = seeds[:, 0]  # duplicate seed ids dedup to one entry
    return data, q, graph, seeds


@pytest.mark.parametrize(
    "seed,n,dim,degree,nq,k,itopk,width,n_seeds,ct", _CAGRA_COMBOS)
def test_fused_cagra_matches_numpy_beam_walk(seed, n, dim, degree, nq, k,
                                             itopk, width, n_seeds, ct):
    data, q, graph, seeds = _cagra_case(seed, n, dim, degree, nq, n_seeds)
    fd, fi = pk.fused_cagra_topk(q, data, graph, seeds, k, itopk, width,
                                 max_iter=12, ct=ct, interpret=True)
    fd, fi = np.asarray(fd), np.asarray(fi)
    for r in range(nq):
        rd, ri = _np_beam_walk(q[r], data, graph, seeds[r], k, itopk,
                               width, 12)
        np.testing.assert_array_equal(fi[r], ri)
        finite = np.isfinite(rd)
        np.testing.assert_allclose(fd[r][finite], rd[finite],
                                   rtol=1e-5, atol=1e-5)
        assert np.all(fd[r][~finite] == np.inf)


@pytest.mark.parametrize(
    "seed,n,dim,degree,nq,k,itopk,width,n_seeds,ct",
    [_CAGRA_COMBOS[0], _CAGRA_COMBOS[2]])
def test_fused_cagra_bit_parity_vs_xla_core(seed, n, dim, degree, nq, k,
                                            itopk, width, n_seeds, ct):
    """Interpret-mode fused core vs ``_search_jit``: identical ids (same
    parent pick, same stable merge order, same done-freeze exit) and
    distances within a few ulp. Not bitwise: the XLA engine scores with a
    batched ``td,tcd->tc`` einsum and the kernel with one [1, dim] ×
    [ct, dim]ᵀ dot per chunk, and XLA:CPU blocks (hence rounds) the two
    contractions differently (measured: ≤ 2e-7 relative on jaxlib
    0.9.0). Sharing one order would take the MXU out of the kernel."""
    from raft_tpu.neighbors import cagra
    from raft_tpu.ops.distance import DistanceType

    data, q, graph, seeds = _cagra_case(seed, n, dim, degree, nq, n_seeds)
    fw = jnp.zeros((1,), jnp.uint32)
    xd, xi = cagra.search_core(
        q, data, data, jnp.asarray(graph), jnp.asarray(seeds), fw,
        DistanceType.L2Expanded, k, itopk, width, 12, False, False)
    fd, fi = pk.fused_cagra_topk(q, data, graph, seeds, k, itopk, width,
                                 max_iter=12, ct=ct, interpret=True)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(xi))
    np.testing.assert_allclose(np.asarray(fd), np.asarray(xd), rtol=1e-6)


def test_plan_fused_cagra_tile_budget_and_alignment():
    for budget in (256 << 10, 1 << 20, 4 << 20, 16 << 20):
        ct = pk.plan_fused_cagra_tile(64, 4, 32, 128, 128,
                                      vmem_budget=budget)
        assert ct >= 8 and ct % 8 == 0
        assert pk.fused_cagra_vmem_bytes(ct, 128, 64, 4, 32, 128) <= budget
    # monotone non-decreasing in budget
    cts = [pk.plan_fused_cagra_tile(64, 4, 32, 128, 128, vmem_budget=b)
           for b in (256 << 10, 1 << 20, 16 << 20)]
    assert cts == sorted(cts)


def test_plan_fused_cagra_tile_caps_at_widest_stream():
    # the widest stream the walk scores is max(width*degree, n_seeds):
    # a bigger scratch would sit empty, so the plan must not exceed its
    # 8-aligned round-up even under a huge budget
    ct = pk.plan_fused_cagra_tile(64, 1, 8, 32, 12, vmem_budget=1 << 30)
    assert ct == 16  # round_up(max(8, 12, 8), 8)
    assert pk.plan_fused_cagra_tile(
        64, 4, 64, 32, 8, vmem_budget=1 << 30) == 256


def test_fused_cagra_workspace_excludes_any_space_operands():
    # dataset/graph are ANY-space ARGUMENTS, not staged temps: workspace
    # must not scale with n (the design point of the fused walk)
    small = pk.fused_cagra_workspace_bytes(64, 10_000, 128, 32, 64, 1,
                                           64, 10)
    large = pk.fused_cagra_workspace_bytes(64, 10_000_000, 128, 32, 64, 1,
                                           64, 10)
    assert small == large > 0


def test_fused_cagra_rejects_large_itopk(rng):
    data, q, graph, seeds = _cagra_case(0, 300, 16, 8, 2, 16)
    with pytest.raises(ValueError, match="itopk"):
        pk.fused_cagra_topk(q, data, graph, seeds, 10, itopk=2048)


def test_cagra_dispatch_fallback_matrix(monkeypatch):
    """scan_mode="pallas" + interpret opt-in routes the fused engine only
    inside the eligibility envelope; everything else must fall back to
    XLA with the matrix's closed reason (docs/tuning.md)."""
    from raft_tpu.core.bitset import Bitset
    from raft_tpu.neighbors import cagra

    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(11)
    n, dim = 400, 16
    data = rng.standard_normal((n, dim)).astype(np.float32)
    q = rng.standard_normal((3, dim)).astype(np.float32)
    graph = jnp.asarray(rng.integers(0, n, (n, 8)).astype(np.int32))
    idx = cagra.Index(cagra.IndexParams(graph_degree=8),
                      jnp.asarray(data), graph)
    pal = dict(itopk_size=16, scan_mode="pallas")

    _, _, rec = cagra.search(idx, q, 5, cagra.SearchParams(**pal),
                             explain=True)
    assert (rec.engine, rec.reason) == ("pallas", "interpret")

    ip = cagra.Index(
        cagra.IndexParams(graph_degree=8,
                          metric=cagra.DistanceType.InnerProduct),
        jnp.asarray(data), graph)
    _, _, rec = cagra.search(ip, q, 5, cagra.SearchParams(**pal),
                             explain=True)
    assert (rec.engine, rec.reason) == ("xla", "non_l2")

    flt = Bitset.create(n)
    _, _, rec = cagra.search(idx, q, 5, cagra.SearchParams(**pal),
                             filter=flt, explain=True)
    assert (rec.engine, rec.reason) == ("xla", "filtered")

    # itopk beyond the kernel's 1024 buffer cap (dataset must be larger
    # than itopk or the XLA fallback's own seed top-k can't run either)
    big_n = 1200
    big = cagra.Index(
        cagra.IndexParams(graph_degree=8),
        jnp.asarray(rng.standard_normal((big_n, dim)).astype(np.float32)),
        jnp.asarray(rng.integers(0, big_n, (big_n, 8)).astype(np.int32)))
    _, _, rec = cagra.search(
        big, q, 5, cagra.SearchParams(itopk_size=1056, scan_mode="pallas"),
        explain=True)
    assert (rec.engine, rec.reason) == ("xla", "k_gt_1024")

    _, _, rec = cagra.search(
        idx, q, 5, cagra.SearchParams(itopk_size=16, scan_dtype="bfloat16",
                                      scan_mode="pallas"), explain=True)
    assert (rec.engine, rec.reason) == ("xla", "fast_scan")

    # TPU absent, no interpret opt-in: auto stays on XLA
    monkeypatch.delenv("RAFT_TPU_PALLAS_INTERPRET")
    _, _, rec = cagra.search(idx, q, 5,
                             cagra.SearchParams(itopk_size=16),
                             explain=True)
    assert (rec.engine, rec.reason) == ("xla", "tpu_absent")


def test_cagra_public_api_interpret_bit_parity(monkeypatch):
    # the whole public path — seed lattice, padding, epilogue — must give
    # identical ids between engines when the fused core runs interpret,
    # and distances within a few ulp (the engines' dots round in
    # different orders; see test_fused_cagra_bit_parity_vs_xla_core)
    from raft_tpu.neighbors import cagra

    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(3)
    n, dim = 800, 32
    data = rng.standard_normal((n, dim)).astype(np.float32)
    q = rng.standard_normal((5, dim)).astype(np.float32)
    idx = cagra.Index(cagra.IndexParams(graph_degree=8), jnp.asarray(data),
                      jnp.asarray(rng.integers(0, n, (n, 8)).astype(
                          np.int32)))
    for metric in (cagra.DistanceType.L2Expanded,
                   cagra.DistanceType.L2SqrtExpanded):
        mi = cagra.Index(cagra.IndexParams(graph_degree=8, metric=metric),
                         idx.dataset, idx.graph)
        vx, ix = cagra.search(mi, q, 5, cagra.SearchParams(
            itopk_size=32, search_width=2, scan_mode="xla"))
        vp, ip = cagra.search(mi, q, 5, cagra.SearchParams(
            itopk_size=32, search_width=2, scan_mode="pallas"))
        np.testing.assert_array_equal(np.asarray(ix), np.asarray(ip))
        np.testing.assert_allclose(np.asarray(vx), np.asarray(vp),
                                   rtol=1e-6)


def test_cagra_fused_recall_floor(monkeypatch):
    """Recall ≥0.95 through the fused engine on a real built graph — the
    walk must actually navigate, not just agree with itself."""
    from raft_tpu.neighbors import brute_force as bf
    from raft_tpu.neighbors import cagra
    from raft_tpu.stats import neighborhood_recall

    monkeypatch.setenv("RAFT_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(7)
    db = rng.standard_normal((3000, 32)).astype(np.float32)
    q = rng.standard_normal((32, 32)).astype(np.float32)
    _, gt = bf.knn(q, db, k=10, metric="sqeuclidean")
    idx = cagra.build(db, cagra.IndexParams(
        intermediate_graph_degree=48, graph_degree=24,
        build_algo=cagra.BuildAlgo.NN_DESCENT, nn_descent_niter=12))
    _, i = cagra.search(idx, q, 10, cagra.SearchParams(
        itopk_size=64, search_width=2, scan_mode="pallas"))
    recall = float(neighborhood_recall(np.asarray(i), np.asarray(gt)))
    assert recall >= 0.95, f"fused recall {recall}"


# ------------------------------------------------------------- heavy shapes

@pytest.mark.slow
def test_fused_l2_topk_heavy_parity(rng):
    x = rng.standard_normal((128, 64)).astype(np.float32)
    y = rng.standard_normal((5000, 64)).astype(np.float32)
    v, i = pk.fused_l2_topk(x, y, 100, tm=64, tn=512, interpret=True)
    d = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    _assert_topk_match(v, i, d, 100, atol=1e-3)


@pytest.mark.slow
def test_fused_ivf_topk_heavy_parity(rng):
    L, pad, rot, nq, P, k = 16, 128, 64, 32, 8, 64
    data = rng.standard_normal((L, pad, rot)).astype(np.float32)
    ids = np.arange(L * pad, dtype=np.int32).reshape(L, pad)
    norms = (data ** 2).sum(-1)
    probes = rng.integers(0, L, (nq, P)).astype(np.int32)
    qres = rng.standard_normal((nq, P, rot)).astype(np.float32)
    qn = (qres ** 2).sum(-1)
    v, i = pk.fused_ivf_topk(probes, qres, qn, data, norms, ids, k,
                             pad_tile=32, clamp=True, interpret=True)
    ref_d, ref_gid = _ivf_ref(probes, qres, data, norms, ids, clamp=True)
    _assert_ivf_match(v, i, ref_d, ref_gid, k, atol=1e-3)


def test_forced_pallas_on_tpu_raises_when_the_kernel_cannot_serve(
        small_db, monkeypatch):
    """On a TPU backend scan_mode="pallas" runs the compiled kernel or
    raises: an ineligible request (here a non-L2 metric) is never quietly
    served by the XLA engines. Off the chip the mode keeps falling back."""
    db, q = small_db
    idx = brute_force.build(db, metric="cityblock")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="cannot serve"):
        brute_force.search(idx, q, 5, scan_mode="pallas")
    pk.require_compiled_kernel("brute_force", "auto", "non_l2")  # auto: ok
    pk.require_compiled_kernel("brute_force", "pallas", None)  # eligible
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    pk.require_compiled_kernel("brute_force", "pallas", "non_l2")  # canary
