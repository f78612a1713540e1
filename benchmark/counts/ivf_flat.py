"""Operations and bytes that RAFT's IVF-Flat search needs, whatever engine
runs it (raft ivf_flat_search.cuh): per query the coarse distances to every
list centre, the distances to every row of its probed lists, and a
top-k; the float32 rows and ids of each distinct probed list read once per
batch, the centres and queries read and the answers written once.

Counted as operations: multiply-adds as 2. The norms and the top-k's
comparisons are left out (the first is one pass over data already
counted, the second is not arithmetic the MXU or VPU bounds), so the
count is a floor.

The floor's time puts the operations against ``peaks.json``'s
``flops_per_s``, the chip's bfloat16 MXU rate. The configuration states
float32 distances, which the program contracts at ``HIGHEST``: six
bfloat16 passes over each product. So a correct float32 program reaches
at most about a sixth of the roofline share this count gives.
"""

from __future__ import annotations


def search(nq: int, n_rows: int, dim: int, n_lists: int, n_probes: int,
           k: int) -> dict:
    rows_per_list = n_rows / n_lists
    probed_rows = n_probes * rows_per_list  # per query, on average
    flops = (2.0 * nq * n_lists * dim        # coarse distances
             + 2.0 * nq * probed_rows * dim)  # the probed rows
    # distinct lists a batch probes, if its probes spread evenly
    distinct = n_lists * (1.0 - (1.0 - n_probes / n_lists) ** nq)
    bytes_ = (distinct * rows_per_list * (dim * 4.0 + 4.0)  # rows + ids
              + n_lists * dim * 4.0              # centres
              + nq * dim * 4.0                   # queries
              + nq * k * 8.0)                    # answers
    return {"flops": flops, "bytes": bytes_}


def least_seconds(counts: dict, peaks: dict) -> tuple:
    """(seconds, bound): the larger of operations over the peak rate and
    bytes over the peak bandwidth, and which of the two it is."""
    t_f = counts["flops"] / peaks["flops_per_s"]
    t_b = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
