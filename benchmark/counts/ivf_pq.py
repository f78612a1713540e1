"""Operations and bytes that RAFT's IVF-PQ search needs, whatever engine
runs it (raft ivf_pq_search.cuh): per query the coarse distances to every
list centre (after the rotation), one lookup table per (query, probe),
one lookup-add per code byte of every probed row, and a top-k; the codes
and ids of each distinct probed list read once per batch, the queries
read and the answers written once.

Counted as operations: multiply-adds as 2, the table's subtract, square
and add as 3 per entry, a lookup-add as 1. The top-k's comparisons are
left out (they are not arithmetic the MXU or VPU bounds), so the count is
a floor.
"""

from __future__ import annotations


def search(nq: int, n_rows: int, dim: int, rot_dim: int, n_lists: int,
           n_probes: int, pq_dim: int, pq_bits: int, k: int) -> dict:
    book = 1 << pq_bits
    pq_len = rot_dim // pq_dim
    rows_per_list = n_rows / n_lists
    probed_rows = n_probes * rows_per_list  # per query, on average
    flops = (2.0 * nq * dim * rot_dim            # rotate the queries
             + 2.0 * nq * n_lists * rot_dim      # coarse distances
             + 3.0 * nq * n_probes * pq_dim * book * pq_len  # the LUTs
             + 1.0 * nq * probed_rows * pq_dim)  # one lookup-add per code
    # distinct lists a batch probes, if its probes spread evenly
    distinct = n_lists * (1.0 - (1.0 - n_probes / n_lists) ** nq)
    code_bytes = pq_dim * pq_bits / 8.0
    bytes_ = (distinct * rows_per_list * (code_bytes + 4.0)  # codes + ids
              + n_lists * rot_dim * 4.0 + rot_dim * dim * 4.0  # centres, R
              + pq_dim * book * pq_len * 4.0     # codebooks
              + nq * dim * 4.0                   # queries
              + nq * k * 8.0)                    # answers
    return {"flops": flops, "bytes": bytes_}


def least_seconds(counts: dict, peaks: dict) -> tuple:
    """(seconds, bound): the larger of operations over the peak rate and
    bytes over the peak bandwidth, and which of the two it is."""
    t_f = counts["flops"] / peaks["flops_per_s"]
    t_b = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
