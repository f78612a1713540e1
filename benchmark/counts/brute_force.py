"""Operations and bytes of exact kNN by a brute-force scan (RAFT's
brute_force::knn): 2*q*n*d for the distance products (the norms are
q*d + n*d more), the collection and the queries read once, the k best
written. Comparisons of the top-k are left out, so the count is a floor.
"""

from __future__ import annotations


def knn(nq: int, n_rows: int, dim: int, k: int) -> dict:
    flops = 2.0 * nq * n_rows * dim + 2.0 * (nq + n_rows) * dim
    bytes_ = 4.0 * (n_rows * dim + nq * dim) + 8.0 * nq * k
    return {"flops": flops, "bytes": bytes_}


def least_seconds(counts: dict, peaks: dict) -> tuple:
    t_f = counts["flops"] / peaks["flops_per_s"]
    t_b = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
