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


def test_unknown_algo_name_raises(rng):
    """Only the enum's algorithms select: a name outside it (such as the
    removed "pallas") raises the enum's ValueError."""
    x = rng.standard_normal((4, 256)).astype(np.float32)
    with pytest.raises(ValueError, match="pallas"):
        select_k(x, 4, algo="pallas")
    v, _ = select_k(x, 4, algo="direct")
    np.testing.assert_allclose(np.asarray(v), np.sort(x, 1)[:, :4])


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
    """The in-code tables are keyed by the backend name alone: on a TPU
    backend the tpu pad rules arm, on CPU none do."""
    import importlib

    import jax

    sk = importlib.import_module("raft_tpu.ops.select_k")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sk._platform_key() == "tpu"
    assert sk._pad_k(4096, 10) == 32
    assert sk._pad_k(8192, 10) == 16
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert sk._platform_key() == "cpu"
    assert sk._pad_k(4096, 10) == 10


# (n, k, k_pad): every "tpu" pad rule, with the k the selection asked
# for at that width before the rules moved into code. Dropping or
# changing a row is a change of the program, made on purpose.
_TPU_PAD_CELLS = [
    (1024, 4, 64), (1024, 8, 64), (1024, 32, 64),
    (2048, 4, 32), (2048, 10, 32), (2048, 12, 32), (2048, 16, 32),
    (2048, 24, 32), (2048, 40, 48),
    (6144, 4, 24), (6144, 8, 24), (6144, 12, 24),
    (8192, 8, 16), (8192, 10, 16),
    (16384, 8, 40), (16384, 12, 40), (16384, 32, 40),
    (32768, 4, 16), (32768, 8, 16),
    (4096, 10, 32),
]


@pytest.mark.parametrize("n,k,k_pad", _TPU_PAD_CELLS,
                         ids=[f"{n}-{k}" for n, k, _ in _TPU_PAD_CELLS])
def test_tpu_pad_table_keeps_measured_cells(n, k, k_pad, monkeypatch):
    import importlib

    sk = importlib.import_module("raft_tpu.ops.select_k")
    monkeypatch.setattr(sk, "_platform_key", lambda: "tpu")
    assert sk._pad_k(n, k) == k_pad
    assert len(sk._BUILTIN_PAD_RULES["tpu"]) == len(_TPU_PAD_CELLS)


def _drop_table_caches(monkeypatch, *modules):
    """Drop every lazily loaded table of the dispatch modules, so the
    next lookup builds it anew."""
    for mod in modules:
        for name, value in vars(mod).items():
            if name.endswith("_cache") and isinstance(value, dict):
                monkeypatch.setattr(mod, name, None)


@pytest.mark.parametrize("prefix,via", [
    ("TOPK_PAD", "cwd"), ("TOPK_PAD", "env"),
    ("PALLAS_PROBE", "cwd"), ("PALLAS_PROBE", "env")])
def test_dispatch_reads_no_json_files(prefix, via, tmp_path, monkeypatch):
    """The choice of kernel and of k lives in code: a dispatch-shaped JSON
    file in the working directory, or named by an environment variable,
    switches nothing."""
    import importlib
    import json

    import jax

    from raft_tpu.ops import pallas_kernels as pk
    from raft_tpu.parallel import sharded

    sk = importlib.import_module("raft_tpu.ops.select_k")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def decisions():
        return ([sk._pad_k(n, k) for n, k, _ in _TPU_PAD_CELLS]
                + [sk._pad_k(4096, 16)],
                [pk.fused_dispatch_explained(f, "auto") for f in (
                    "brute_force", "ivf_flat", "ivf_pq", "ivf_scan",
                    "l2_argmin", "cagra")],
                sharded.merge_dispatch_explained("auto", 4))

    before = decisions()
    art = {"platform": "tpu",
           "pad_rules": [{"n": n, "k": k, "k_pad": k}
                         for n, k, _ in _TPU_PAD_CELLS]
           + [{"n": 4096, "k": 16, "k_pad": 64}],
           "fused": {f: {"fused_wins": True} for f in (
               "brute_force", "ivf_flat", "ivf_pq", "ivf_scan",
               "l2_argmin", "cagra", "merge_ring")}}
    path = tmp_path / f"{prefix}_tpu.json"
    path.write_text(json.dumps(art))
    if via == "cwd":
        monkeypatch.chdir(tmp_path)
    else:
        monkeypatch.setenv(f"RAFT_TPU_{prefix}", str(path))
    _drop_table_caches(monkeypatch, sk, pk)
    assert decisions() == before
