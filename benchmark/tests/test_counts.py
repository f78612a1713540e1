"""Count functions against shapes worked by hand."""

import pytest

from benchmark import harness


def test_brute_force_knn():
    bf = harness.load_module("counts", "brute_force")
    c = bf.knn(nq=10, n_rows=100, dim=4, k=3)
    assert c["flops"] == 2 * 10 * 100 * 4 + 2 * (10 + 100) * 4  # 8880
    assert c["bytes"] == 4 * (100 * 4 + 10 * 4) + 8 * 10 * 3  # 2000
    t, bound = bf.least_seconds(c, {"flops_per_s": 8880.0,
                                    "hbm_bytes_per_s": 1000.0})
    assert (t, bound) == (2.0, "bytes")


def test_ivf_pq_search():
    pq = harness.load_module("counts", "ivf_pq")
    # 2 queries, 64 rows in 4 lists of 16, dim = rot_dim = 8, 2 probes,
    # pq_dim 4 (pq_len 2), 8-bit codes, k 5
    c = pq.search(nq=2, n_rows=64, dim=8, rot_dim=8, n_lists=4, n_probes=2,
                  pq_dim=4, pq_bits=8, k=5)
    flops = (2 * 2 * 8 * 8          # rotation: 256
             + 2 * 2 * 4 * 8        # coarse: 128
             + 3 * 2 * 2 * 4 * 256 * 2  # LUTs: 24576
             + 2 * 32 * 4)          # lookup-adds: 256
    assert c["flops"] == pytest.approx(flops)
    distinct = 4 * (1 - (1 - 2 / 4) ** 2)  # 3 lists
    byt = (distinct * 16 * (4 + 4) + 4 * 8 * 4 + 8 * 8 * 4
           + 4 * 256 * 2 * 4 + 2 * 8 * 4 + 2 * 5 * 8)
    assert c["bytes"] == pytest.approx(byt)
    _, bound = pq.least_seconds(c, {"flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9})
    assert bound == "bytes"


def test_peaks_table():
    p = harness.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
