"""The distance IVF-PQ's search should report for an id, in float64.

Asymmetric distance computation (RAFT's IVF-PQ, L2): the query is rotated
and compared with the id's coarse centre plus its decoded residual,

    ||R q - R c[list(id)] - decode(codes(id))||^2,

where ``decode`` concatenates, subspace by subspace, the row of that
subspace's codebook each code names (per-subspace codebooks, the
default). The index's centres, rotation, codebooks and codes are read
back from the program (``System.adc_view``): this file holds only the
arithmetic, in float64, and imports nothing of the program. Comparing it
with the reported distance shows the precision the search computed in,
which the exact reference cannot see under the PQ approximation.
"""

from __future__ import annotations

import numpy as np


def unpack(code_bytes: np.ndarray, pq_dim: int, pq_bits: int) -> np.ndarray:
    """[..., n_bytes] uint8 -> [..., pq_dim] codes: code s holds bits
    [s * pq_bits, (s + 1) * pq_bits) of the bytes read as one
    little-endian bit string."""
    b = np.asarray(code_bytes).astype(np.int64)
    n_bytes = b.shape[-1]
    pos = np.arange(pq_dim) * pq_bits
    lo, sh = pos // 8, pos % 8
    hi = np.minimum(lo + 1, n_bytes - 1)
    word = b[..., lo] | (b[..., hi] << 8)
    return (word >> sh) & ((1 << pq_bits) - 1)


class Table:
    """Each id's coarse list and code bytes, from the view's lists and
    overflow block."""

    def __init__(self, view: dict):
        self.v = view
        sizes = np.asarray(view["list_sizes"])
        ids = np.asarray(view["list_indices"])
        valid = np.arange(ids.shape[1])[None, :] < sizes[:, None]
        lists, slots = np.nonzero(valid)
        all_ids = np.concatenate([ids[lists, slots],
                                  np.asarray(view["overflow_indices"])])
        n = int(view["n_rows"])
        self.label = np.full(n, -1, np.int64)
        self.where = np.full(n, -1, np.int64)
        ok = (all_ids >= 0) & (all_ids < n)
        self.label[all_ids[ok]] = np.concatenate(
            [lists, np.asarray(view["overflow_labels"])])[ok]
        self.where[all_ids[ok]] = np.arange(len(all_ids))[ok]
        self.n_listed = len(lists)
        self.slots = (lists, slots)

    def codes(self, rows: np.ndarray) -> np.ndarray:
        """Code bytes of the entries ``rows`` (positions in the table)."""
        codes = np.asarray(self.v["list_codes"])
        over = np.asarray(self.v["overflow_codes"])
        out = np.zeros((len(rows), codes.shape[-1]), np.uint8)
        inl = rows < self.n_listed
        r = rows[inl]
        out[inl] = codes[self.slots[0][r], self.slots[1][r]]
        out[~inl] = over[rows[~inl] - self.n_listed]
        return out


def distances(view: dict, queries: np.ndarray, ids: np.ndarray,
              block: int = 4096) -> np.ndarray:
    """float64 ADC distance [m, k] of each ``ids[i, j]`` to ``queries[i]``;
    inf where the id is in no list of the index."""
    table = Table(view)
    rot = np.asarray(view["rotation"], np.float64)
    centers = np.asarray(view["centers"], np.float64)
    books = np.asarray(view["codebooks"], np.float64)
    pq_dim, pq_bits = int(view["pq_dim"]), int(view["pq_bits"])
    if view["per_cluster"]:
        raise ValueError("per-cluster codebooks: no configuration uses them")
    q = np.asarray(queries, np.float64)
    ids = np.asarray(ids, np.int64)
    m, k = ids.shape
    out = np.full((m, k), np.inf)
    flat_q = np.repeat(np.arange(m), k)
    flat_i = ids.reshape(-1)
    for lo in range(0, len(flat_i), block):
        qi, ii = flat_q[lo:lo + block], flat_i[lo:lo + block]
        known = (ii >= 0) & (ii < len(table.label))
        known[known] = table.label[ii[known]] >= 0
        if not known.any():
            continue
        qi, ii = qi[known], ii[known]
        lab = table.label[ii]
        codes = unpack(table.codes(table.where[ii]), pq_dim, pq_bits)
        dec = books[np.arange(pq_dim)[None, :], codes].reshape(len(ii), -1)
        res = (q[qi] - centers[lab]) @ rot.T - dec
        d = np.einsum("bd,bd->b", res, res)
        pos = lo + np.nonzero(known)[0]
        out.reshape(-1)[pos] = d
    return out
