"""select_k tests — compared against a numpy reference across shapes/algos
(reference pattern: cpp/test/matrix/select_k.cu)."""

import numpy as np
import pytest

from raft_tpu.ops import SelectAlgo, select_k


def _ref_select(values, k, select_min):
    order = np.argsort(values if select_min else -values, axis=-1, kind="stable")
    idx = order[..., :k]
    return np.take_along_axis(values, idx, -1), idx


@pytest.mark.parametrize(
    "algo", [SelectAlgo.DIRECT, SelectAlgo.TWO_PHASE, SelectAlgo.AUTO])
@pytest.mark.parametrize(
    "shape,k",
    [((4, 100), 10), ((1, 17), 17), ((7, 2048), 256), ((3, 100000), 64)])
@pytest.mark.parametrize("select_min", [True, False])
def test_select_k(algo, shape, k, select_min, rng):
    if shape[1] < 100 and algo == SelectAlgo.TWO_PHASE:
        pytest.skip("two-phase needs wide rows")
    values = rng.standard_normal(shape).astype(np.float32)
    got_v, got_i = select_k(values, k, select_min=select_min, algo=algo)
    want_v, _ = _ref_select(values, k, select_min)
    np.testing.assert_allclose(np.sort(np.asarray(got_v), -1),
                               np.sort(want_v, -1), rtol=1e-6)
    # indices must gather the returned values
    np.testing.assert_allclose(
        np.take_along_axis(values, np.asarray(got_i), -1), np.asarray(got_v), rtol=1e-6
    )


def test_select_k_with_source_indices(rng):
    values = rng.standard_normal((3, 50)).astype(np.float32)
    src = rng.integers(0, 10_000, size=(3, 50))
    got_v, got_i = select_k(values, 5, indices=src)
    want_v, want_pos = _ref_select(values, 5, True)
    np.testing.assert_allclose(np.sort(np.asarray(got_v)), np.sort(want_v), rtol=1e-6)
    assert set(np.asarray(got_i)[0]) == set(src[0][want_pos[0]])


def test_select_k_1d(rng):
    values = rng.standard_normal(100).astype(np.float32)
    v, i = select_k(values, 3)
    assert v.shape == (3,)
    np.testing.assert_allclose(np.asarray(v), np.sort(values)[:3], rtol=1e-6)


def test_k_too_large():
    with pytest.raises(ValueError):
        select_k(np.zeros((2, 4), np.float32), 5)


def test_two_phase_wide_rows(rng):
    """SELECT_LARGE_TEST analog: wide rows force the two-phase path under
    AUTO and must agree with numpy."""
    from raft_tpu.ops.select_k import SelectAlgo, select_k

    x = rng.standard_normal((4, 1 << 17)).astype(np.float32)
    for algo in (SelectAlgo.AUTO, SelectAlgo.TWO_PHASE):
        v, i = select_k(x, 32, select_min=True, algo=algo)
        ref = np.sort(x, axis=1)[:, :32]
        np.testing.assert_allclose(np.sort(np.asarray(v), 1), ref, rtol=1e-6)
        np.testing.assert_allclose(
            np.take_along_axis(x, np.asarray(i), 1), np.asarray(v), rtol=1e-6)


def test_two_phase_matches_direct_largest(rng):
    from raft_tpu.ops.select_k import SelectAlgo, select_k

    x = rng.standard_normal((3, 70_000)).astype(np.float32)
    v1, _ = select_k(x, 7, select_min=False, algo=SelectAlgo.DIRECT)
    v2, _ = select_k(x, 7, select_min=False, algo=SelectAlgo.TWO_PHASE)
    np.testing.assert_allclose(np.sort(np.asarray(v1), 1),
                               np.sort(np.asarray(v2), 1), rtol=1e-6)


@pytest.mark.parametrize("shape,k", [((16, 1000), 5), ((64, 4096), 32),
                                     ((8, 300), 10)])
def test_pallas_algo_matches_direct(shape, k, rng):
    """Streaming Pallas k-extraction agrees with lax.top_k (values exactly;
    indices up to ties)."""
    x = rng.standard_normal(shape).astype(np.float32)
    for select_min in (True, False):
        v_p, i_p = select_k(x, k, select_min=select_min,
                            algo=SelectAlgo.PALLAS)
        v_d, _ = select_k(x, k, select_min=select_min,
                          algo=SelectAlgo.DIRECT)
        np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_d),
                                   rtol=1e-6)
        picked = np.take_along_axis(x, np.asarray(i_p), axis=1)
        np.testing.assert_allclose(picked, np.asarray(v_d), rtol=1e-6)


def test_pallas_inf_rows_and_wide_k(rng):
    """Rows with fewer than k finite entries emit -1 null indices (no
    duplicate picks); k wider than the column tile still selects exactly."""
    from raft_tpu.ops.pallas_kernels import pallas_select_k

    x = np.full((8, 256), np.inf, np.float32)
    x[:, 0] = 1.0
    x[:, 100] = 2.0
    v, i = pallas_select_k(x, 4, interpret=True)
    np.testing.assert_array_equal(np.asarray(i)[0], [0, 100, -1, -1])

    y = rng.standard_normal((8, 1024)).astype(np.float32)
    v, i = pallas_select_k(y, 200, tn=128, interpret=True)
    np.testing.assert_allclose(np.asarray(v), np.sort(y, 1)[:, :200],
                               rtol=1e-6)
    with pytest.raises(ValueError, match="small-k"):
        pallas_select_k(y, 1025, interpret=True)


def test_auto_uses_measured_table():
    """AUTO resolves DIRECT/TWO_PHASE from the per-platform measured
    crossover table (VERDICT r2 #6), overridable via set_auto_table."""
    import importlib

    # the ops package rebinds the name `select_k` to the function, so the
    # module must come from importlib
    sk = importlib.import_module("raft_tpu.ops.select_k")

    # cpu's measured table: DIRECT everywhere
    assert sk._resolve_auto(262144, 128) == sk.SelectAlgo.DIRECT
    # install a fake measured table and check band resolution
    sk.set_auto_table("cpu", {"32": 1024, "256": 4096, "inf": 16384})
    try:
        assert sk._resolve_auto(2048, 10) == sk.SelectAlgo.TWO_PHASE
        assert sk._resolve_auto(512, 10) == sk.SelectAlgo.DIRECT
        assert sk._resolve_auto(8192, 128) == sk.SelectAlgo.TWO_PHASE
        assert sk._resolve_auto(2048, 128) == sk.SelectAlgo.DIRECT
        assert sk._resolve_auto(32768, 1024) == sk.SelectAlgo.TWO_PHASE
        # k*4 > n guard: tiny rows always DIRECT
        assert sk._resolve_auto(2048, 1024) == sk.SelectAlgo.DIRECT
        # correctness is algo-independent: same results both ways
        rng = np.random.default_rng(0)
        x = rng.standard_normal((8, 8192)).astype(np.float32)
        vd, idd = select_k(x, 128, algo=SelectAlgo.DIRECT)
        vt, idt = select_k(x, 128, algo=SelectAlgo.TWO_PHASE)
        np.testing.assert_allclose(np.asarray(vd), np.asarray(vt))
        np.testing.assert_array_equal(np.asarray(idd), np.asarray(idt))
    finally:
        sk.set_auto_table("cpu", {"inf": sk._NEVER})


def test_auto_table_reads_no_json_file(tmp_path, monkeypatch):
    """AUTO's tables live in code: a SELECT_K_TABLE_*.json in the working
    directory or at the repo root switches nothing."""
    import importlib
    import json

    sk = importlib.import_module("raft_tpu.ops.select_k")
    monkeypatch.chdir(tmp_path)
    (tmp_path / "SELECT_K_TABLE_cpu.json").write_text(json.dumps(
        {"platform": "cpu", "crossovers": {"inf": 16}}))
    monkeypatch.setattr(sk, "_auto_table_cache", None)
    assert sk._resolve_auto(262144, 10) == sk.SelectAlgo.DIRECT
    assert sk._load_auto_table() == sk._BUILTIN_TABLES


def test_topk_pad_rules():
    """Measured k-pad rules rewrite DIRECT's requested k at trace time
    (exact: the prefix of a larger selection IS the smaller selection,
    ties included); rules match exact k within a x1.25 width window."""
    import importlib

    import jax

    sk = importlib.import_module("raft_tpu.ops.select_k")
    plat = jax.default_backend()
    # save/restore the platform's prior rules (may include the shipped
    # builtin on a tpu run) — set_pad_rules(plat, None) pops the
    # whole entry, which would leave later tests order-dependent
    prev = sk._load_pad_rules().get(plat)
    sk.set_pad_rules(plat, [{"n": 4096, "k": 10, "k_pad": 32}])
    try:
        assert sk._pad_k(4096, 10) == 32
        assert sk._pad_k(5000, 10) == 32      # within x1.25
        assert sk._pad_k(4096, 11) == 11      # k must match exactly
        assert sk._pad_k(16384, 10) == 10     # outside the window
        # nearest-width rule wins; k_pad clamps to the row width
        sk.set_pad_rules(plat, [{"n": 4096, "k": 10, "k_pad": 32},
                                {"n": 6144, "k": 10, "k_pad": 16},
                                {"n": 64, "k": 10, "k_pad": 4096}])
        assert sk._pad_k(5800, 10) == 16
        assert sk._pad_k(64, 10) == 64

        rng = np.random.default_rng(3)
        x = rng.standard_normal((8, 4100)).astype(np.float32)
        x[:, 50:60] = x[:, 40:50]  # duplicate values: tie behavior
        # the wiring, not just _pad_k: record the k DIRECT actually asks
        # lax.top_k for while tracing (k_pad is in the jit key, so this
        # trace is fresh even if (8, 4100) ran unpadded before)
        sk.set_pad_rules(plat, [{"n": 4096, "k": 10, "k_pad": 32}])
        asked = []
        real_top_k = jax.lax.top_k

        def recording_top_k(operand, kk):
            asked.append(kk)
            return real_top_k(operand, kk)

        jax.lax.top_k = recording_top_k
        try:
            v, i = select_k(x, 10, algo=SelectAlgo.DIRECT)
        finally:
            jax.lax.top_k = real_top_k
        assert 32 in asked, f"pad rule not applied (asked: {asked})"
        ref = np.argsort(x, 1, kind="stable")[:, :10]
        np.testing.assert_array_equal(np.asarray(i), ref)
        np.testing.assert_array_equal(
            np.asarray(v), np.take_along_axis(x, ref, 1))
    finally:
        sk.set_pad_rules(plat, prev)
    if prev is None:
        assert sk._pad_k(4096, 10) == 10


def test_platform_key_is_the_backend_name(monkeypatch):
    """Measured tables are keyed by the backend name alone: on a TPU
    backend the tpu tables arm, on CPU the cpu ones."""
    import importlib

    import jax

    sk = importlib.import_module("raft_tpu.ops.select_k")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sk._platform_key() == "tpu"
    # builtin tpu pad rule fires on the tpu backend — and
    # survives the shipped TOPK_PAD_tpu.json artifact, which measured
    # other widths but not the (4096, 10) cell (merge semantics:
    # artifact rules + builtins for unmeasured cells)
    assert sk._pad_k(4096, 10) == 32
    # a cell the artifact DID measure comes from the artifact
    assert sk._pad_k(8192, 10) == 16
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert sk._platform_key() == "cpu"
    assert sk._pad_k(4096, 10) == 10


def test_merge_pad_rules_builtin_survives_unmeasured_cells():
    """TOPK_PAD artifacts merge with the builtin pad table per (n, k)
    cell: a measured cell always wins (including k_pad == k "no pad"
    entries), a builtin survives when the artifact never measured its
    cell (ADVICE r5: wholesale replacement silently disarmed the n=4096
    builtin)."""
    import importlib

    sk = importlib.import_module("raft_tpu.ops.select_k")
    builtin = [{"n": 4096, "k": 10, "k_pad": 32},
               {"n": 2048, "k": 10, "k_pad": 32}]
    measured = [{"n": 2048, "k": 10, "k_pad": 10},   # measured: no pad
                {"n": 8192, "k": 10, "k_pad": 16}]
    merged = sk._merge_pad_rules(builtin, measured)
    cells = {(r["n"], r["k"]): r["k_pad"] for r in merged}
    assert cells[(2048, 10)] == 10   # measured overrides builtin
    assert cells[(8192, 10)] == 16   # measured-only cell kept
    assert cells[(4096, 10)] == 32   # unmeasured builtin survives
    assert len(merged) == 3
