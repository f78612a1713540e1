"""IVF-PQ — inverted-file index with product-quantized residuals.

Reference: ``raft::neighbors::ivf_pq`` (neighbors/ivf_pq-inl.cuh:115-480;
types ivf_pq_types.hpp:48-146; build detail/ivf_pq_build.cuh:1732; search
detail/ivf_pq_search.cuh). Build: subsample trainset → balanced k-means
coarse clustering → random-orthonormal rotation (normal + QR,
detail/ivf_pq_build.cuh:121-137) → PQ codebooks per-subspace or per-cluster
(each trained by balanced k-means on residual sub-vectors,
detail/ivf_pq_build.cuh:394,471) → encode + bit-pack all vectors into
per-cluster lists (process_and_fill_codes, detail/ivf_pq_build.cuh:1185).
Search: coarse top-``n_probes`` via gemm + select_k (select_clusters,
detail/ivf_pq_search.cuh:69-155) → per query×probe look-up-table (LUT) scan
of packed codes with fp32/fp16/fp8 LUTs (detail/ivf_pq_compute_similarity)
→ final select_k → postprocess.

TPU-native design:
- **Storage**: padded dense ``[n_lists, list_pad, n_code_bytes]`` uint8 of
  bit-packed codes (pq_bits ∈ [4,8], invariant pq_dim·pq_bits ≡ 0 mod 8 —
  ivf_pq_types.hpp:538-545) + int32 row ids. Lane-aligned padding instead of
  the GPU's interleaved group-of-32 layout.
- **LUT build is a batched matmul** (MXU): for each query×probe the LUT is
  ``||q_sub − codebook||²`` expanded into norms + one einsum over
  [pq_dim, book_size, pq_len] — the analog of the shared-memory LUT fill.
- **Code scan**: static two-byte gathers unpack pq_bits codes from the byte
  stream (each code spans ≤ 2 bytes); scores come from a flat LUT gather and
  a sum over subspaces. ``lut_dtype``/``internal_distance_dtype`` map to
  fp32/bf16 (fp8 LUTs are emulated with bf16 — TPUs have no fp8 gather win).
- **Precision**: the float contractions of the scan, the LUT build and the
  encoder take their precision from the dtypes the caller states
  (:func:`contraction_precision`): ``HIGHEST`` where all are float32,
  ``DEFAULT`` (one bfloat16 pass on a TPU) where one is bfloat16 or fp8.
- **Codebook training**: one jitted Lloyd-EM body ``lax.map``-ed across
  subspaces (PER_SUBSPACE) or across clusters (PER_CLUSTER), trained on
  rotated residuals, weights masking ragged membership — one compile serves
  all groups.
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import serialize as ser
from raft_tpu.core import tracing
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.bitset import filter_mask as bitset_filter_mask
from raft_tpu.core.resources import (Resources, ensure_resources,
                                     solve_joint_tiles)
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
from raft_tpu.ops.distance import DistanceType, resolve_metric
from raft_tpu.ops.select_k import select_k, select_k_maybe_approx
from raft_tpu.neighbors import list_packing
from raft_tpu.neighbors.brute_force import fused_ineligible_reason
from raft_tpu.obs import explain as obs_explain
from raft_tpu.obs import metrics as obs_metrics
from raft_tpu.ops import pallas_kernels as pk
from raft_tpu.ops import rng as rrng
from raft_tpu.utils.shape import (as_query_array, balanced_tile, cdiv, pad_rows,
                                  query_bucket, round_up_to)

_SCAN_PLANS = obs_metrics.REGISTRY.counter(
    "raft_tpu_ivf_pq_scan_plans_total",
    "ivf_pq search dispatches by engine and the precision of its float "
    "contractions.",
    ("engine", "precision"))


def contraction_precision(*dtypes) -> jax.lax.Precision:
    """The precision of a float contraction of the scan, the LUT build or
    the encoder, from the dtypes the caller stated for it
    (``internal_distance_dtype`` and the cache's or the LUT's dtype, as
    RAFT's ``internalDistanceDtype``): ``HIGHEST`` where every one is
    float32, else ``DEFAULT``. A TPU computes a float32 contraction at
    ``DEFAULT`` in one bfloat16 pass, and the expanded ADC form
    ``‖q_res‖² − 2·q_res·dec + ‖dec‖²`` cancels, so that pass costs
    percents of the k-th distance; a bfloat16 or fp8 operand holds no
    more than the one pass keeps."""
    if all(jnp.dtype(d) == jnp.float32 for d in dtypes):
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


class CodebookGen(enum.IntEnum):
    """reference: ivf_pq_types.hpp codebook_gen."""

    PER_SUBSPACE = 0
    PER_CLUSTER = 1


@dataclasses.dataclass
class IndexParams:
    """reference: ivf_pq_types.hpp:48-108 index_params."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8
    pq_dim: int = 0  # 0 → heuristic (see _calc_pq_dim)
    codebook_kind: CodebookGen = CodebookGen.PER_SUBSPACE
    force_random_rotation: bool = False
    add_data_on_build: bool = True
    # Padded-storage budget (see ivf_flat.IndexParams.list_pad_expansion):
    # caps the dense list_pad; spilled rows live in a small overflow block
    # scanned brute-force per query (candidate superset, no recall loss).
    list_pad_expansion: float = 1.5

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)
        if not 4 <= self.pq_bits <= 8:
            raise ValueError(f"pq_bits must be in [4, 8], got {self.pq_bits}")
        if self.list_pad_expansion < 1.0:
            raise ValueError(
                f"list_pad_expansion must be >= 1.0, got "
                f"{self.list_pad_expansion}")
        if self.metric not in (
            DistanceType.L2Expanded,
            DistanceType.L2SqrtExpanded,
            DistanceType.InnerProduct,
        ):
            raise ValueError(
                f"ivf_pq supports L2Expanded/L2SqrtExpanded/InnerProduct, got "
                f"{self.metric.name}"
            )


@dataclasses.dataclass
class SearchParams:
    """reference: ivf_pq_types.hpp:110-146 search_params. ``lut_dtype``
    accepts jnp.float32, jnp.bfloat16, or jnp.float8_e4m3fn/e5m2 (fp8 LUTs
    are stored max-abs-scaled per subspace, the fp_8bit analog —
    detail/ivf_pq_fp_8bit.cuh); ``internal_distance_dtype`` accepts
    jnp.float32 or jnp.bfloat16."""

    n_probes: int = 20
    lut_dtype: object = jnp.float32
    internal_distance_dtype: object = jnp.float32
    # TPU-specific: how the ADC scan is evaluated.
    #   "auto"/"cache": scan decoded residuals with an MXU matmul (exactly
    #     the ADC distance, evaluated as ||q_res||² − 2·q_res·dec + ||dec||²
    #     instead of per-code LUT gathers, which XLA lowers to scalar loads).
    #     The decoded cache (bf16, rot_dim per row) is built lazily on the
    #     index and invalidated by extend().
    #   "lut": force the reference-shaped LUT gather path (lower memory —
    #     only the packed codes are resident).
    #   "pallas": fused Pallas scan+select — probed slabs (or packed codes
    #     + in-kernel LUT) are DMA'd to VMEM and the top-k is carried
    #     in-kernel, so no candidate slab touches HBM (docs/tuning.md).
    #     L2 metrics, no filter, k <= 1024; the LUT regime additionally
    #     needs pq_bits=8, PER_SUBSPACE, fp32 LUT dtypes. Unsupported
    #     combinations (and CPU without the interpret hook) fall back to
    #     the XLA engines silently; "auto" picks pallas on TPU only where
    #     the committed probe artifact shows it winning.
    scan_mode: str = "auto"
    # dtype of the decoded scan cache: bf16 (default; halves scan HBM
    # traffic, ~1e-3 recall cost — the reference's fp16/fp8-LUT trade) or
    # float32 (with a float32 internal_distance_dtype the scan contracts at
    # HIGHEST: the LUT engine's distances to float32 rounding).
    scan_cache_dtype: object = jnp.bfloat16
    # <1.0 routes internal top-k through the TPU PartialReduce engine
    # (ops.select_k APPROX) at this per-element recall target; exact by
    # default — the same recall/speed dial family as lut_dtype
    select_recall: float = 1.0


def _calc_pq_dim(dim: int) -> int:
    """Heuristic default pq_dim (analog of the reference's calculate_pq_dim:
    a power of two close to dim/2, at least 8)."""
    p = 1
    while p * 2 <= dim // 2 or p < 8:
        p *= 2
        if p >= 512:
            break
    return max(min(p, dim + (-dim) % 8), 8)


class Index:
    """IVF-PQ index (reference: ivf_pq_types.hpp:149-560 — coarse centers,
    rotation matrix, codebooks, packed per-list codes + ids)."""

    def __init__(self, params: IndexParams, pq_dim: int, centers, rotation,
                 codebooks, list_codes, list_indices, list_sizes, n_rows: int,
                 overflow_codes=None, overflow_labels=None,
                 overflow_indices=None):
        self.params = params
        self.pq_dim = int(pq_dim)
        self.centers = centers  # [n_lists, dim] fp32
        self.rotation = rotation  # [rot_dim, dim] fp32 (orthonormal columns)
        # codebooks: PER_SUBSPACE [pq_dim, book, pq_len]
        #            PER_CLUSTER  [n_lists, book, pq_len]
        self.codebooks = codebooks
        self.list_codes = list_codes  # [n_lists, list_pad, n_code_bytes] u8
        self.list_indices = list_indices  # [n_lists, list_pad] int32, -1 pad
        self.list_sizes = list_sizes  # [n_lists] int32
        self.n_rows = int(n_rows)
        # rows spilled past the capped list_pad (list_packing
        # .choose_list_pad): packed codes + their coarse list + ids. Their
        # decoded rotated vectors (lazy, below) are scanned brute-force by
        # every query and merged into the final select_k. Empty in the
        # balanced common case.
        n_bytes = (pq_dim * params.pq_bits) // 8
        self.overflow_codes = (overflow_codes if overflow_codes is not None
                               else jnp.zeros((0, n_bytes), jnp.uint8))
        self.overflow_labels = (
            overflow_labels if overflow_labels is not None
            else jnp.zeros((0,), jnp.int32))
        self.overflow_indices = (
            overflow_indices if overflow_indices is not None
            else jnp.zeros((0,), jnp.int32))
        # lazy decoded-residual scan cache (see SearchParams.scan_mode):
        # [n_lists, list_pad, rot_dim] bf16 + per-row ||dec||² f32
        self.list_decoded = None
        self.decoded_norms = None
        # lazy decoded overflow: FULL rotated vectors (center_rot + decoded
        # residual) [n_over, rot_dim] + ||v||² f32 — both engines share it
        self.overflow_decoded = None
        self.overflow_norms = None

    @property
    def metric(self) -> DistanceType:
        return self.params.metric

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def pq_bits(self) -> int:
        return self.params.pq_bits

    @property
    def pq_len(self) -> int:
        return self.rot_dim // self.pq_dim

    @property
    def pq_book_size(self) -> int:
        return 1 << self.pq_bits

    @property
    def size(self) -> int:
        return self.n_rows

    @property
    def centers_rot(self) -> jax.Array:
        return jnp.matmul(self.centers, self.rotation.T,
                          precision=jax.lax.Precision.HIGHEST)


# ------------------------------------------------------------- rotation matrix


def make_rotation_matrix(key, rot_dim: int, dim: int,
                         force_random: bool) -> jax.Array:
    """[rot_dim, dim] with orthonormal columns (reference:
    detail/ivf_pq_build.cuh:121-137 — random normal + in-place QR when
    force_random or rot_dim != dim, else identity)."""
    if not force_random and rot_dim == dim:
        return jnp.eye(dim, dtype=jnp.float32)
    if not force_random:
        # dim-padding only: identity embedding keeps exactness
        return jnp.eye(rot_dim, dim, dtype=jnp.float32)
    a = jax.random.normal(key, (rot_dim, rot_dim), jnp.float32)
    q, _ = jnp.linalg.qr(a)
    return q[:, :dim]


# --------------------------------------------------------- codebook training


def _codebook_em(subvecs, weights, book_size: int, n_iters: int, key):
    """Lloyd EM for one codebook: subvecs [n, l], weights [n] (0 = padding).
    Empty codes re-seed from a pseudo-random weighted row (the balancing
    analog of kmeans_balanced's adjust_centers for tiny codebook fits)."""
    n, l = subvecs.shape

    def m_step(labels):
        w = weights
        sums = jnp.zeros((book_size, l), jnp.float32).at[labels].add(
            subvecs * w[:, None])
        counts = jnp.zeros((book_size,), jnp.float32).at[labels].add(w)
        return sums, counts

    def body(i, state):
        centers, _ = state
        cn = jnp.sum(centers * centers, -1)
        d = cn[None, :] - 2.0 * jnp.matmul(
            subvecs, centers.T, precision=jax.lax.Precision.HIGHEST)
        # (+ ||x||², rank-invariant)
        labels = jnp.argmin(d, axis=1).astype(jnp.int32)
        sums, counts = m_step(labels)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # re-seed empty codes from the weighted seed pool (never padding)
        donor = seed_rows[jax.random.randint(
            jax.random.fold_in(key, i), (book_size,), 0, pool_size)]
        empty = counts < 0.5
        new = jnp.where(empty[:, None], subvecs[donor], new)
        return new, labels

    # init: ``book_size`` distinct (weight>0) data rows via Gumbel top-k —
    # the data-point seeding that keeps Lloyd from collapsing to the mean.
    # Trainsets smaller than the book reuse rows cyclically.
    g = jax.random.gumbel(jax.random.fold_in(key, n_iters + 1), (n,))
    g = jnp.where(weights > 0, g, -jnp.inf)
    _, seed_rows = jax.lax.top_k(g, min(book_size, n))
    if n < book_size:
        seed_rows = jnp.tile(seed_rows, cdiv(book_size, n))[:book_size]
    pool_size = seed_rows.shape[0]
    centers0 = subvecs[seed_rows]
    labels0 = jnp.zeros((n,), jnp.int32)
    centers, _ = jax.lax.fori_loop(
        0, n_iters, body, (centers0, labels0))
    return centers


@functools.partial(jax.jit, static_argnames=("book_size", "n_iters"))
def _train_codebooks_jit(keys, subvecs, weights, book_size: int, n_iters: int):
    """subvecs [G, n, l], weights [G, n] → codebooks [G, book, l]; sequential
    over groups (one compile), each EM internally vectorized."""

    def one(args):
        key, sv, w = args
        return _codebook_em(sv, w, book_size, n_iters, key)

    return jax.lax.map(one, (keys, subvecs, weights))


# ----------------------------------------------------------- code (un)packing


def _pack_codes_np(codes: np.ndarray, pq_bits: int) -> np.ndarray:
    """Bit-pack [n, pq_dim] uint8 codes → [n, pq_dim*pq_bits/8] bytes
    (little-endian bit order; analog of process_and_fill_codes' packing,
    detail/ivf_pq_build.cuh:1185-1351)."""
    n, pq_dim = codes.shape
    bits = (codes[:, :, None] >> np.arange(pq_bits, dtype=np.uint8)) & 1
    flat = bits.reshape(n, pq_dim * pq_bits)
    return np.packbits(flat, axis=1, bitorder="little")


@functools.lru_cache(maxsize=None)
def _pack_terms(pq_dim: int, pq_bits: int):
    """Static (code index, shift) terms per output byte for device-side
    bit-packing: byte j collects the codes whose [k·bits, (k+1)·bits) span
    intersects [8j, 8j+8) — at most 3 codes for pq_bits ∈ [4, 8].
    shift ≥ 0 means ``code << shift``, else ``code >> -shift``."""
    n_bytes = pq_dim * pq_bits // 8
    terms = []
    for j in range(n_bytes):
        lo_k = (8 * j) // pq_bits
        hi_k = min((8 * j + 7) // pq_bits, pq_dim - 1)
        terms.append([(k, k * pq_bits - 8 * j)
                      for k in range(lo_k, hi_k + 1)])
    width = max(len(t) for t in terms)
    ks = np.zeros((n_bytes, width), np.int32)
    shifts = np.zeros((n_bytes, width), np.int32)
    valid = np.zeros((n_bytes, width), bool)
    for j, t in enumerate(terms):
        for w, (k, s) in enumerate(t):
            ks[j, w], shifts[j, w], valid[j, w] = k, s, True
    # plain numpy (trace-safe constants): this cache may be populated
    # inside a jit trace, where a jnp array would memoize a leaked tracer
    return ks, shifts, valid


@functools.partial(jax.jit, static_argnames=("pq_dim", "pq_bits"))
def _pack_codes_jit(codes, pq_dim: int, pq_bits: int):
    """[..., pq_dim] int codes → [..., pq_dim·pq_bits/8] uint8, on device
    (bit-identical to ``_pack_codes_np``; the packing half of
    process_and_fill_codes, detail/ivf_pq_build.cuh:1185-1351)."""
    ks, shifts, valid = _pack_terms(pq_dim, pq_bits)
    c = jnp.take(codes.astype(jnp.int32), ks, axis=-1)  # [..., nb, w]
    up = jnp.where(shifts >= 0, c << jnp.maximum(shifts, 0),
                   c >> jnp.maximum(-shifts, 0))
    up = jnp.where(valid, up, 0)
    # in-byte bits of the terms are disjoint, so the mod-256 sum equals
    # the OR of the in-byte contributions (out-of-byte bits fall off in
    # the uint8 cast — they belong to neighboring bytes' own terms)
    return up.sum(-1).astype(jnp.uint8)


def _unpack_positions(pq_dim: int, pq_bits: int):
    """Static per-subspace (lo_byte, hi_byte, shift) for two-byte unpack."""
    pos = np.arange(pq_dim) * pq_bits
    lo = pos // 8
    sh = pos % 8
    n_bytes = pq_dim * pq_bits // 8
    hi = np.minimum(lo + 1, n_bytes - 1)
    return jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(sh)


def _unpack_codes(code_bytes: jax.Array, pq_dim: int, pq_bits: int) -> jax.Array:
    """[..., n_bytes] uint8 → [..., pq_dim] int32 codes. Each pq_bits field
    spans ≤ 2 bytes; static gathers keep this a pure vector op."""
    lo, hi, sh = _unpack_positions(pq_dim, pq_bits)
    b = code_bytes.astype(jnp.int32)
    lo_b = jnp.take(b, lo, axis=-1)
    hi_b = jnp.take(b, hi, axis=-1)
    word = lo_b | (hi_b << 8)
    return (word >> sh) & ((1 << pq_bits) - 1)


@functools.partial(jax.jit, static_argnames=("pq_dim", "pq_bits",
                                              "per_cluster", "list_tile",
                                              "cache_dtype"))
def _decode_lists_jit(codebooks, list_codes, pq_dim: int, pq_bits: int,
                      per_cluster: bool, list_tile: int,
                      cache_dtype=jnp.bfloat16):
    """Decode packed list codes → residual vectors [L, pad, rot_dim] bf16
    plus their squared norms [L, pad] f32 (the scan cache). The codebook
    gather runs once per build over list tiles (bounded HBM), not per query."""
    n_lists, list_pad, _ = list_codes.shape
    book = codebooks.shape[1]
    pq_len = codebooks.shape[2]

    n_tiles = cdiv(n_lists, list_tile)
    pad_l = n_tiles * list_tile - n_lists
    codes_p = jnp.pad(list_codes, ((0, pad_l), (0, 0), (0, 0)))
    cb_p = (jnp.pad(codebooks, ((0, pad_l), (0, 0), (0, 0)))
            if per_cluster else codebooks)

    def tile_body(args):
        ct, cbt = args
        codes = _unpack_codes(ct, pq_dim, pq_bits)  # [lt, pad, s]
        if per_cluster:
            # decoded[l,p,s,:] = cbt[l, codes[l,p,s], :]
            dec = jnp.take_along_axis(
                cbt[:, None, None, :, :],
                codes[:, :, :, None, None].astype(jnp.int32), axis=3,
            )[:, :, :, 0, :]
        else:
            # decoded[l,p,s,:] = codebooks[s, codes[l,p,s], :]
            flat = codebooks.reshape(pq_dim * book, pq_len)
            dec = jnp.take(flat, codes + jnp.arange(pq_dim) * book, axis=0)
        dec = dec.reshape(ct.shape[0], list_pad, pq_dim * pq_len)
        norms = jnp.sum(dec.astype(jnp.float32) ** 2, -1)
        return dec.astype(cache_dtype), norms

    if per_cluster:
        dec, norms = jax.lax.map(
            tile_body,
            (codes_p.reshape(n_tiles, list_tile, list_pad, -1),
             cb_p.reshape(n_tiles, list_tile, book, pq_len)))
    else:
        dec, norms = jax.lax.map(
            lambda ct: tile_body((ct, None)),
            codes_p.reshape(n_tiles, list_tile, list_pad, -1))
    dec = dec.reshape(n_tiles * list_tile, list_pad, -1)[:n_lists]
    norms = norms.reshape(n_tiles * list_tile, list_pad)[:n_lists]
    return dec, norms


def ensure_scan_cache(index: Index, dtype=jnp.bfloat16) -> None:
    """Build the decoded-residual scan cache if absent (idempotent).

    bf16 (default) halves scan HBM traffic for ~1e-3 recall — the same
    precision/bandwidth trade the reference's fp16/fp8 LUTs make, and the
    scan over it contracts at ``DEFAULT``. ``dtype=jnp.float32`` keeps the
    decoded residuals whole; with a float32 ``internal_distance_dtype``
    the scan then contracts at ``HIGHEST`` (:func:`contraction_precision`)
    and gives the LUT engine's distances to float32 rounding, on a TPU as
    on the CPU."""
    if index.list_codes is None:
        return
    if (index.list_decoded is not None
            and index.list_decoded.dtype == jnp.dtype(dtype)):
        return
    per_cluster = index.params.codebook_kind == CodebookGen.PER_CLUSTER
    # balanced grid: n_lists=130 with a flat 128 cap would pay a second,
    # 98%-padding tile (cf. shape.balanced_tile)
    list_tile = balanced_tile(index.n_lists, min(index.n_lists, 128), 8)
    # pad list count so tiles divide evenly inside the jit
    index.list_decoded, index.decoded_norms = _decode_lists_jit(
        index.codebooks, index.list_codes, index.pq_dim, index.pq_bits,
        per_cluster, list_tile, jnp.dtype(dtype).name)


@functools.partial(jax.jit, static_argnames=("pq_dim", "pq_bits",
                                             "per_cluster", "cache_dtype"))
def _decode_overflow_jit(codebooks, centers_rot, codes_bytes, labels,
                         pq_dim: int, pq_bits: int, per_cluster: bool,
                         cache_dtype=jnp.bfloat16):
    """Decode spilled code rows → FULL rotated vectors [O, rot_dim]
    (coarse center + decoded residual; unlike the list cache, overflow
    rows mix lists, so the center term must be baked in) + ||v||² f32."""
    book = codebooks.shape[1]
    pq_len = codebooks.shape[2]
    codes = _unpack_codes(codes_bytes, pq_dim, pq_bits)  # [O, s]
    if per_cluster:
        # dec[o, s, :] = codebooks[labels[o], codes[o, s], :]
        dec = codebooks[labels[:, None], codes]  # [O, s, l]
    else:
        flat = codebooks.reshape(pq_dim * book, pq_len)
        dec = jnp.take(flat, codes + jnp.arange(pq_dim) * book, axis=0)
    full = centers_rot[labels] + dec.reshape(codes.shape[0],
                                             pq_dim * pq_len)
    norms = jnp.sum(full.astype(jnp.float32) ** 2, -1)
    return full.astype(cache_dtype), norms


def ensure_overflow_decoded(index: Index, dtype=jnp.bfloat16) -> None:
    """Materialize the decoded overflow block (tiny: only spilled rows)."""
    if index.overflow_codes.shape[0] == 0:
        return
    if (index.overflow_decoded is not None
            and index.overflow_decoded.dtype == jnp.dtype(dtype)):
        return
    per_cluster = index.params.codebook_kind == CodebookGen.PER_CLUSTER
    index.overflow_decoded, index.overflow_norms = _decode_overflow_jit(
        index.codebooks, index.centers_rot, index.overflow_codes,
        index.overflow_labels, index.pq_dim, index.pq_bits, per_cluster,
        jnp.dtype(dtype).name)


# ----------------------------------------------------------------- encoding


@functools.partial(jax.jit, static_argnames=("per_cluster", "row_tile"))
def _encode_jit(x, labels, centers, rotation, codebooks, per_cluster: bool,
                row_tile: int):
    """Residual-encode rows → int32 codes [n, pq_dim]."""
    n, dim = x.shape
    pq_len = codebooks.shape[2]
    pq_dim = rotation.shape[0] // pq_len

    n_tiles = cdiv(n, row_tile)
    pad = n_tiles * row_tile - n
    xp = jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0)))
    lp = jnp.pad(labels, (0, pad))
    prec = contraction_precision(codebooks.dtype)

    def tile_body(args):
        xt, lt = args
        res = xt - centers[lt]
        rr = jax.lax.dot_general(
            res, rotation, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [t, rot_dim]
        sub = rr.reshape(-1, pq_dim, pq_len)  # [t, s, l]
        if per_cluster:
            cb = codebooks[lt]  # [t, book, l]
            dots = jnp.einsum("tsl,tcl->tsc", sub, cb,
                              preferred_element_type=jnp.float32,
                              precision=prec)
            cn = jnp.sum(cb * cb, -1)  # [t, book]
            d = cn[:, None, :] - 2.0 * dots
        else:
            dots = jnp.einsum("tsl,scl->tsc", sub, codebooks,
                              preferred_element_type=jnp.float32,
                              precision=prec)
            cn = jnp.sum(codebooks * codebooks, -1)  # [s, book]
            d = cn[None, :, :] - 2.0 * dots
        return jnp.argmin(d, axis=-1).astype(jnp.int32)  # [t, s]

    codes = jax.lax.map(
        tile_body,
        (xp.reshape(n_tiles, row_tile, dim), lp.reshape(n_tiles, row_tile)),
    )
    return codes.reshape(-1, pq_dim)[:n]


def _pack_lists_np(code_bytes: np.ndarray, labels: np.ndarray, n_lists: int,
                   ids: np.ndarray, max_expansion: float = 1.5):
    """Group packed code rows by cluster into padded list storage (native
    C++ packer; analog of process_and_fill_codes' list placement). ``pad``
    is budget-capped (list_packing.choose_list_pad); rows past a hot
    list's cap spill to the returned overflow block.

    Returns (codes, idxs, sizes, over_codes, over_labels, over_ids)."""
    from raft_tpu import native

    sizes = np.bincount(labels, minlength=n_lists).astype(np.int32)
    pad = list_packing.choose_list_pad(sizes, max_expansion)
    if int(sizes.max(initial=0)) <= pad:
        codes, idxs, sizes = native.pack_lists(code_bytes, labels, n_lists,
                                               pad, ids)
        return (codes, idxs, sizes, code_bytes[:0],
                np.zeros((0,), np.int32), np.zeros((0,), np.int32))
    keep = list_packing.fit_mask(labels, n_lists, pad)
    codes, idxs, sizes = native.pack_lists(
        np.ascontiguousarray(code_bytes[keep]), labels[keep], n_lists, pad,
        np.ascontiguousarray(np.asarray(ids, np.int32)[keep]))
    over_codes, over_ids = list_packing.pad_overflow_block(
        np.ascontiguousarray(code_bytes[~keep]),
        np.ascontiguousarray(np.asarray(ids, np.int32)[~keep]))
    over_labels = np.zeros((len(over_ids),), np.int32)
    spill_lab = labels[~keep]
    over_labels[:len(spill_lab)] = spill_lab
    return codes, idxs, sizes, over_codes, over_labels, over_ids


@functools.partial(jax.jit, static_argnames=("n_lists", "cap"))
def _group_rows_jit(rows, labels, n_lists: int, cap: int):
    """Group rows by label into padded [n_lists, cap, d] storage + 0/1
    weights, keeping each label's first ``cap`` rows in input order (device
    analog of the PER_CLUSTER trainset grouping loop)."""
    order, sl, slot = list_packing.label_slots(
        labels, jnp.zeros((n_lists,), jnp.int32), n_lists)
    grouped = jnp.zeros((n_lists, cap, rows.shape[1]), jnp.float32)
    grouped = grouped.at[sl, slot].set(
        rows[order].astype(jnp.float32), mode="drop")
    weights = jnp.zeros((n_lists, cap), jnp.float32).at[sl, slot].set(
        1.0, mode="drop")
    return grouped, weights


# --------------------------------------------------------------------- build


@tracing.range("ivf_pq.build")
def build(
    dataset,
    params: Optional[IndexParams] = None,
    res: Optional[Resources] = None,
    coarse_centers=None,
) -> Index:
    """Build the index (reference: ivf_pq::build, ivf_pq-inl.cuh:273 →
    detail/ivf_pq_build.cuh:1732).

    ``coarse_centers`` skips the coarse k-means and trains rotation +
    codebooks against the given ``[n_lists, dim]`` centers — the pod-scale
    build path (parallel/sharded.build_ivf_pq_from_file_pod) trains ONE
    mesh-wide quantizer and injects it into every shard's build."""
    params = params or IndexParams()
    res = ensure_resources(res)
    dataset = jnp.asarray(dataset)
    n_rows, dim = dataset.shape
    if params.n_lists > n_rows:
        raise ValueError(f"n_lists={params.n_lists} > n_rows={n_rows}")

    pq_dim = params.pq_dim or _calc_pq_dim(dim)
    if (pq_dim * params.pq_bits) % 8 != 0:
        raise ValueError(
            f"pq_dim*pq_bits must be a multiple of 8 "
            f"(got {pq_dim}*{params.pq_bits}); see ivf_pq_types.hpp:538-545"
        )
    pq_len = cdiv(dim, pq_dim)
    rot_dim = pq_len * pq_dim

    # trainset subsample (detail/ivf_pq_build.cuh:1759)
    n_train = max(int(n_rows * params.kmeans_trainset_fraction), params.n_lists)
    n_train = min(n_train, n_rows)
    trainset = rrng.subsample_rows(res.next_key(), dataset, n_train)
    trainset = trainset.astype(jnp.float32)

    # coarse quantizer
    km = KMeansBalancedParams(n_iters=params.kmeans_n_iters,
                              metric=params.metric)
    if coarse_centers is not None:
        centers = jnp.asarray(coarse_centers, jnp.float32)
        if centers.shape != (params.n_lists, dim):
            raise ValueError(
                f"coarse_centers shape {tuple(centers.shape)} != "
                f"(n_lists={params.n_lists}, dim={dim})")
    else:
        centers = kmeans_balanced.fit(res.next_key(), trainset,
                                      params.n_lists, km, res=res)

    rotation = make_rotation_matrix(res.next_key(), rot_dim, dim,
                                    params.force_random_rotation)

    # residuals of the trainset, rotated
    labels = kmeans_balanced.predict(centers, trainset, km, res=res)
    residuals = jnp.matmul(trainset - centers[labels], rotation.T,
                           precision=jax.lax.Precision.HIGHEST)

    book = 1 << params.pq_bits
    if params.codebook_kind == CodebookGen.PER_SUBSPACE:
        # [pq_dim groups] × (subvectors of every training row)
        sub = jnp.transpose(
            residuals.reshape(n_train, pq_dim, pq_len), (1, 0, 2)
        )  # [G=pq_dim, n_train, pq_len]
        w = jnp.ones((pq_dim, n_train), jnp.float32)
        keys = jax.random.split(res.next_key(), pq_dim)
        codebooks = _train_codebooks_jit(keys, sub, w, book,
                                         params.kmeans_n_iters)
    else:
        # group training residuals per coarse cluster (ragged → padded) —
        # a device segment-scatter, no host loop over lists
        sizes = np.bincount(np.asarray(labels), minlength=params.n_lists)
        cap = max(int(min(sizes.max(), max(2 * n_train // params.n_lists, book))), book)
        grouped, weights = _group_rows_jit(residuals, labels,
                                           params.n_lists, int(cap))
        # pool subspace positions: codebook shared across subspaces
        sub = grouped.reshape(params.n_lists, cap * pq_dim, pq_len)
        w = jnp.repeat(weights, pq_dim, axis=1)
        keys = jax.random.split(res.next_key(), params.n_lists)
        codebooks = _train_codebooks_jit(keys, sub, w, book,
                                         params.kmeans_n_iters)

    index = Index(params, pq_dim, centers, rotation, codebooks,
                  None, None, None, 0)
    if params.add_data_on_build:
        index = extend(index, dataset, res=res)
    return index


def encode_batch(index: Index, vectors, labels,
                 res: Optional[Resources] = None) -> jax.Array:
    """Residual-encode + bit-pack one batch of vectors against their coarse
    labels → packed code bytes [n, pq_dim*pq_bits/8], entirely on device
    (the per-batch body of process_and_fill_codes,
    detail/ivf_pq_build.cuh:1185-1351). Shared by ``extend`` and the
    streamed ``neighbors.ooc`` builder."""
    res = ensure_resources(res)
    per_cluster = index.params.codebook_kind == CodebookGen.PER_CLUSTER
    row_tile = int(np.clip(
        res.workspace_limit_bytes //
        max(index.pq_dim * index.pq_book_size * 4 * 4, 1), 8, 4096))
    row_tile = balanced_tile(len(vectors), row_tile, 8)
    codes = _encode_jit(jnp.asarray(vectors, jnp.float32),
                        jnp.asarray(labels), index.centers, index.rotation,
                        index.codebooks, per_cluster, max(row_tile, 8))
    return _pack_codes_jit(codes, index.pq_dim, index.pq_bits)


@tracing.range("ivf_pq.extend")
def extend(index: Index, new_vectors, new_indices=None,
           res: Optional[Resources] = None) -> Index:
    """Encode + add vectors (reference: ivf_pq::extend, ivf_pq-inl.cuh:355 →
    detail/ivf_pq_build.cuh:1653)."""
    res = ensure_resources(res)
    new_vectors = jnp.asarray(new_vectors).astype(jnp.float32)
    km = KMeansBalancedParams(metric=index.metric)
    labels = kmeans_balanced.predict(index.centers, new_vectors, km, res=res)

    code_bytes = encode_batch(index, new_vectors, labels, res)

    labels_np = np.asarray(labels)
    if new_indices is None:
        # past the row count and any user-supplied id, spilled ids included
        base = index.n_rows
        if index.list_indices is not None:
            base = max(base, int(np.asarray(index.list_indices).max()) + 1)
        if index.overflow_indices.shape[0]:
            base = max(base,
                       int(np.asarray(index.overflow_indices).max()) + 1)
        new_ids = np.arange(base, base + len(code_bytes), dtype=np.int32)
    else:
        new_ids = np.asarray(new_indices, np.int32)

    code_bytes_np = np.asarray(code_bytes)
    if index.list_codes is None:
        # first fill goes through the native host packer (shared with the
        # out-of-core streamed builds, which pack from host RAM without a
        # device round-trip); test_extend_matches_single_shot_lists pins it
        # bit-for-bit to the device scatter below
        data, idxs, sizes, o_codes, o_labels, o_ids = _pack_lists_np(
            code_bytes_np, labels_np, index.n_lists, new_ids,
            index.params.list_pad_expansion)
        data, idxs, sizes = (jnp.asarray(data), jnp.asarray(idxs),
                             jnp.asarray(sizes))
        o_codes, o_labels, o_ids = (jnp.asarray(o_codes),
                                    jnp.asarray(o_labels),
                                    jnp.asarray(o_ids))
        n_rows = len(code_bytes_np)
    else:
        # device-side append: grow the pad (budget-capped) if needed, then
        # segment-scatter the new batch after each list's tail — existing
        # lists stay packed on device (VERDICT r1 #3; reference:
        # process_and_fill_codes). Rows past a hot list's cap spill to the
        # overflow block (the pad never shrinks — no repack on extend).
        old_sizes = np.asarray(index.list_sizes)
        counts = np.bincount(labels_np, minlength=index.n_lists)
        cap = max(list_packing.choose_list_pad(
            old_sizes + counts, index.params.list_pad_expansion),
            index.list_codes.shape[1])
        keep = list_packing.fit_mask(labels_np, index.n_lists, cap,
                                     sizes=old_sizes)
        data, idxs = list_packing.grow_pad(
            index.list_codes, index.list_indices,
            int((old_sizes + np.bincount(
                labels_np[keep], minlength=index.n_lists)).max()))
        data, idxs, sizes = list_packing.append_lists(
            data, idxs, index.list_sizes, jnp.asarray(code_bytes_np[keep]),
            jnp.asarray(new_ids[keep]), jnp.asarray(labels_np[keep]),
            index.n_lists)
        o_codes, o_labels, o_ids = _merge_pq_overflow(
            index, code_bytes_np[~keep], labels_np[~keep], new_ids[~keep])
        n_rows = index.n_rows + len(code_bytes_np)
    return Index(index.params, index.pq_dim, index.centers, index.rotation,
                 index.codebooks, data, idxs, sizes, n_rows,
                 o_codes, o_labels, o_ids)


def _merge_pq_overflow(index: Index, new_codes_np, new_labels_np,
                       new_ids_np):
    """Append spilled code rows to the overflow block (8-aligned; valid
    rows stay a prefix — padding ids are -1 at the tail only)."""
    if len(new_codes_np) == 0:
        return (index.overflow_codes, index.overflow_labels,
                index.overflow_indices)
    old_ids = np.asarray(index.overflow_indices)
    n_old = int((old_ids >= 0).sum())
    codes = np.concatenate(
        [np.asarray(index.overflow_codes)[:n_old], new_codes_np], axis=0)
    labels = np.concatenate(
        [np.asarray(index.overflow_labels)[:n_old],
         np.asarray(new_labels_np, np.int32)])
    ids = np.concatenate([old_ids[:n_old],
                          np.asarray(new_ids_np, np.int32)])
    codes_p, ids_p = list_packing.pad_overflow_block(codes, ids)
    labels_p = np.zeros((len(ids_p),), np.int32)
    labels_p[:len(labels)] = labels
    return jnp.asarray(codes_p), jnp.asarray(labels_p), jnp.asarray(ids_p)


# --------------------------------------------------------------------- search


def _pq_overflow_scan(q_rot, overflow_decoded, overflow_norms,
                      overflow_indices, filter_words,
                      metric: DistanceType, has_filter: bool, bad_fill,
                      precision: jax.lax.Precision):
    """Distances of one query tile against the decoded overflow block
    (FULL rotated vectors: center + residual — see ensure_overflow_decoded)
    in the same squared-L2 / IP space as the probed-list scan, contracted
    at the scan's ``precision``: [t, O] distances + broadcast ids, ready
    for the final select_k."""
    dots = jax.lax.dot_general(
        q_rot, overflow_decoded.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )  # [t, O]
    if metric == DistanceType.InnerProduct:
        od = dots  # q_rot·v = q·center + q_rot·dec (rotation orthonormal)
    else:
        qn = jnp.sum(q_rot * q_rot, -1)
        od = qn[:, None] - 2.0 * dots + overflow_norms[None, :]
    ok = overflow_indices >= 0
    if has_filter:
        ok = ok & bitset_filter_mask(overflow_indices, filter_words)
    od = jnp.where(ok[None, :], od, bad_fill)
    oi = jnp.broadcast_to(overflow_indices[None, :],
                          (q_rot.shape[0], overflow_indices.shape[0]))
    return od, oi


def _cache_probes(qt, rotation, centers_rot, metric: DistanceType,
                  n_probes: int, sel):
    """A query tile's rotation [t, rot], its dots with the rotated centres
    [t, L] and its ``n_probes`` lists (nearest, or of largest dot for
    inner product, by ``sel``): the decoded-cache cores' coarse step."""
    q_rot = jax.lax.dot_general(
        qt, rotation, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    dots_c = jax.lax.dot_general(
        q_rot, centers_rot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if metric == DistanceType.InnerProduct:
        _, probes = sel(dots_c, n_probes, False)
    else:
        cn = jnp.sum(centers_rot * centers_rot, -1)
        _, probes = sel(cn[None, :] - 2.0 * dots_c, n_probes, True)
    return q_rot, dots_c, probes


def _cache_answers(flat_d, flat_i, q_rot, overflow_decoded, overflow_norms,
                   overflow_indices, filter_words, metric: DistanceType,
                   k: int, has_filter: bool, has_overflow: bool, precision,
                   sel):
    """The k best of a query tile's candidates ``flat_d``/``flat_i`` [t, n]
    and the overflow block's, by ``sel``: padded past the candidates
    (the worst value, id -1), the sqrt taken for L2Sqrt."""
    minimize = metric != DistanceType.InnerProduct
    bad_fill = jnp.inf if minimize else -jnp.inf
    if has_overflow:
        od, oi = _pq_overflow_scan(q_rot, overflow_decoded, overflow_norms,
                                   overflow_indices, filter_words, metric,
                                   has_filter, bad_fill, precision)
        flat_d = jnp.concatenate([flat_d, od], axis=1)
        flat_i = jnp.concatenate([flat_i, oi], axis=1)
    kk = min(k, flat_d.shape[1])
    v, sel_i = sel(flat_d, kk, minimize)
    i_out = jnp.take_along_axis(flat_i, sel_i, axis=1)
    if kk < k:
        v = jnp.pad(v, ((0, 0), (0, k - kk)), constant_values=bad_fill)
        i_out = jnp.pad(i_out, ((0, 0), (0, k - kk)), constant_values=-1)
    if metric == DistanceType.L2SqrtExpanded:
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i_out


def _search_cache_core(queries, centers, rotation, list_decoded,
                       decoded_norms, list_indices, list_sizes, filter_words,
                       metric: DistanceType, k: int, n_probes: int,
                       q_tile: int, has_filter: bool,
                       overflow_decoded=None, overflow_norms=None,
                       overflow_indices=None, has_overflow: bool = False,
                       select_recall: float = 1.0,
                       dist_dtype: str = "float32",
                       use_pallas: bool = False,
                       pallas_interpret: bool = False):
    """ADC scan over the decoded-residual cache: identical distances to the
    LUT formulation (||q_res − dec||² expands to ||q_res||² − 2 q_res·dec +
    ||dec||²), evaluated as one batched matvec per probe on the MXU, at the
    precision the cache's dtype and ``dist_dtype`` (the internal distance
    dtype) state (:func:`contraction_precision`).

    ``use_pallas``/``pallas_interpret`` are accepted only as False, the
    values ``benchmark/rehearse_compile.py`` passes: the unfused Pallas
    scan they once selected is gone."""
    if use_pallas or pallas_interpret:
        raise ValueError("the unfused Pallas scan was removed; "
                         "use_pallas must be False")
    nq, dim = queries.shape
    n_lists, list_pad, rot_dim = list_decoded.shape
    minimize = metric != DistanceType.InnerProduct
    prec = contraction_precision(list_decoded.dtype, dist_dtype)

    def _sel(vals, kk, sel_min):
        return select_k_maybe_approx(vals, kk, sel_min, select_recall)

    n_q_tiles = cdiv(nq, q_tile)
    pad_q = n_q_tiles * q_tile - nq
    qp = jnp.pad(queries.astype(jnp.float32), ((0, pad_q), (0, 0)))

    centers_rot = jax.lax.dot_general(
        centers, rotation, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    valid_slot = jnp.arange(list_pad)[None, :] < list_sizes[:, None]

    def q_body(qt):
        q_rot, dots_c, probes = _cache_probes(qt, rotation, centers_rot,
                                              metric, n_probes, _sel)
        g_idx = list_indices[probes]
        g_valid = valid_slot[probes]
        if metric == DistanceType.InnerProduct:
            g_dec = list_decoded[probes]  # [t, P, pad, rot] bf16
            # score = q·center + q_rot·dec
            dots = jnp.einsum("td,tpld->tpl", q_rot,
                              g_dec.astype(jnp.float32),
                              preferred_element_type=jnp.float32,
                              precision=prec)
            base = jnp.take_along_axis(dots_c, probes, axis=1)
            d = base[:, :, None] + dots
        else:
            g_dec = list_decoded[probes]  # [t, P, pad, rot] bf16
            g_n = decoded_norms[probes]  # [t, P, pad]
            qr_res = q_rot[:, None, :] - centers_rot[probes]  # [t, P, rot]
            dots = jnp.einsum("tpd,tpld->tpl", qr_res,
                              g_dec.astype(jnp.float32),
                              preferred_element_type=jnp.float32,
                              precision=prec)
            qn = jnp.sum(qr_res * qr_res, -1)  # [t, P]
            d = qn[:, :, None] - 2.0 * dots + g_n

        bad_fill = jnp.inf if minimize else -jnp.inf
        ok = g_valid
        if has_filter:
            ok = ok & bitset_filter_mask(g_idx, filter_words)
        d = jnp.where(ok, d, bad_fill)

        n_cand = n_probes * list_pad
        return _cache_answers(
            d.reshape(qt.shape[0], n_cand), g_idx.reshape(qt.shape[0], n_cand),
            q_rot, overflow_decoded, overflow_norms, overflow_indices,
            filter_words, metric, k, has_filter, has_overflow, prec, _sel)

    if n_q_tiles == 1:
        vals, idxs = q_body(qp)
    else:
        vals, idxs = jax.lax.map(q_body, qp.reshape(n_q_tiles, q_tile, dim))
        vals = vals.reshape(-1, k)
        idxs = idxs.reshape(-1, k)
    return vals[:nq], idxs[:nq]


_search_cache_jit = jax.jit(
    _search_cache_core,
    static_argnames=("metric", "k", "n_probes", "q_tile", "has_filter",
                     "use_pallas", "pallas_interpret", "has_overflow",
                     "select_recall", "dist_dtype"),
)

#: public traceable-core names — the cross-package contract for the
#: sharded engines (parallel/sharded.py shard_maps these bodies) and the
#: graftcheck jaxpr audit; the underscore spellings stay package-private
#: (R004 layering, docs/analysis.md)
search_cache_core = _search_cache_core
encode_core = _encode_jit


# ------------------------------------------- decoded cache, list-major
#
# ``_search_cache_core`` is query-major: each query copies its probed
# slabs out of the cache, so a list probed by many queries of a batch is
# read once for each of them. The list-major core orders the batch's
# (query, list) pairs by list and contracts each probed list once against
# every query that probes it (``pk.list_scan``), then finds each query's
# exact top-k by the minima of 128-slot groups (the ``brute_force
# ._group_topk`` scheme). It serves the single-chip cache engine on a TPU
# (``plan_list_scan``); the query-major core serves other platforms, the
# sharded engines and the tiered arena.

#: platforms the list-major core runs on: compiled on a TPU, under the
#: Mosaic interpreter on any other listed here (parity tests add "cpu")
_LIST_MAJOR_PLATFORMS = ("tpu",)


class ListScan(NamedTuple):
    """A list-major scan's plan (``plan_list_scan``): ``block_rows`` query
    rows a block, ``n_blocks`` blocks and ``super_tile`` queries a
    super-tile, ``n_super`` super-tiles, under the interpreter when
    ``interpret``."""
    block_rows: int
    n_blocks: int
    super_tile: int
    n_super: int
    interpret: bool


def list_scan_blocks(n_pairs: int, n_lists: int, block_rows: int) -> int:
    """Blocks that hold ``n_pairs`` (query, list) pairs, each list's run
    padded to whole blocks of ``block_rows``, whatever the skew: a list's
    ``c`` pairs take ⌈c / T⌉ ≤ (c + T − 1) / T blocks, and at most
    ``min(n_lists, n_pairs)`` lists are probed."""
    m = min(n_lists, n_pairs)
    return (n_pairs + m * (block_rows - 1)) // block_rows


def list_scan_bytes(super_tile: int, n_probes: int, n_lists: int,
                    list_pad: int, rot_dim: int, block_rows: int, k: int,
                    n_overflow: int) -> int:
    """Live set of one super-tile of the list-major core: the blocks'
    distances, minima and query rows, each pair's gathered minima row and
    plan, and the kept groups' and the overflow block's candidates."""
    nb = list_scan_blocks(super_tile * n_probes, n_lists, block_rows)
    n_g = pk.list_scan_groups(list_pad)
    kg = min(k, n_probes * n_g)
    return (nb * block_rows * (pk.SCAN_GROUP * 4 * (n_g + 1) + rot_dim * 4)
            + super_tile * n_probes * (pk.SCAN_GROUP * 4 + 32)
            + super_tile * (kg * pk.SCAN_GROUP + n_overflow) * 12)


def _block_rows_for(n_pairs: int, n_lists: int) -> int:
    """Query rows a block: the power of two in [8, 128] that holds a
    list's mean run of pairs."""
    t = 8
    while t < 128 and t * n_lists < n_pairs:
        t *= 2
    return t


def plan_list_scan(platform: str, nq: int, n_probes: int, n_lists: int,
                   list_pad: int, rot_dim: int, cache_itemsize: int, k: int,
                   n_overflow: int, workspace_limit_bytes: int):
    """Whether the decoded-cache engine scans list-major:
    ``(ListScan, "list_kernel")`` on the listed platforms at any batch
    size (on a v5e it was faster at every size measured, 8–10,000
    queries, docs/tuning.md), else ``(None, reason)``: ``tpu_absent``,
    ``short_lists`` (a list shorter than one 128-slot group) or
    ``list_vmem`` (a list's slab and one 8-row block overflow the
    kernel's VMEM). Super-tiles are as many as the workspace's live set
    (``list_scan_bytes``) and the prefetched block table need."""
    if platform not in _LIST_MAJOR_PLATFORMS:
        return None, "tpu_absent"
    if list_pad < pk.SCAN_GROUP:
        return None, "short_lists"

    def fits_vmem(t):
        return pk.list_scan_vmem_bytes(t, list_pad, rot_dim,
                                       cache_itemsize) \
            <= pk.DEFAULT_VMEM_BUDGET

    n_super = 1
    while True:
        st = nq if n_super == 1 else round_up_to(cdiv(nq, n_super), 8)
        t = _block_rows_for(st * n_probes, n_lists)
        while t > 8 and not fits_vmem(t):
            t //= 2
        if not fits_vmem(t):
            return None, "list_vmem"
        nb = list_scan_blocks(st * n_probes, n_lists, t)
        if st <= 8 or (
                nb * 4 <= pk.SMEM_PREFETCH_BYTES
                and list_scan_bytes(st, n_probes, n_lists, list_pad, rot_dim,
                                    t, k, n_overflow)
                <= workspace_limit_bytes):
            return ListScan(t, nb, st, cdiv(nq, st),
                            platform != "tpu"), "list_kernel"
        n_super += 1


def _list_scan_slots(list_pad: int):
    """The slot of each lane of each group of ``pk.list_scan`` [n_g, 128],
    and whether the lane is the slot's own: the last group ends at
    ``list_pad``, so its lanes that repeat the group before are not."""
    starts = pk.list_scan_group_starts(list_pad)
    slot = starts[:, None] + np.arange(pk.SCAN_GROUP)[None, :]
    return slot, slot >= np.arange(len(starts))[:, None] * pk.SCAN_GROUP


def _list_major_plan(probes, n_lists: int, block_rows: int, n_blocks: int):
    """The blocks of a super-tile's probes [nq, P]: its pairs ordered by
    list (a stable sort, so by query within a list), each list's run
    padded to whole blocks of ``block_rows``. Returns ``block_list``
    [NB] (a block's list; blocks past the last used repeat its list, so
    no slab is fetched for them), ``n_used`` [1], ``block_queries`` [NB,
    T] (a slot's query, -1 where it is padding) and ``pair_at`` [nq, P]
    (each pair's slot, ``block · T + row``)."""
    nq, n_probes = probes.shape
    n_pairs = nq * n_probes
    t = block_rows
    lists = probes.reshape(-1).astype(jnp.int32)
    pair = jnp.arange(n_pairs, dtype=jnp.int32)
    s_list, s_pair = jax.lax.sort((lists, pair), num_keys=1,
                                  is_stable=True)
    counts = jnp.zeros((n_lists,), jnp.int32).at[lists].add(1)
    n_blk = (counts + t - 1) // t
    first_pair = jnp.cumsum(counts) - counts
    first_blk = jnp.cumsum(n_blk) - n_blk
    rank = pair - first_pair[s_list]
    at = (first_blk[s_list] + rank // t) * t + rank % t
    block_queries = jnp.full((n_blocks * t,), -1, jnp.int32).at[at].set(
        s_pair // n_probes, unique_indices=True).reshape(n_blocks, t)
    pair_at = jnp.zeros((n_pairs,), jnp.int32).at[s_pair].set(
        at, unique_indices=True).reshape(nq, n_probes)
    heads = jnp.zeros((n_blocks,), jnp.int32).at[
        jnp.where(n_blk > 0, first_blk, n_blocks)].max(
        jnp.arange(n_lists, dtype=jnp.int32), mode="drop")
    block_list = jax.lax.cummax(heads)
    return block_list, jnp.sum(n_blk)[None], block_queries, pair_at


def _search_cache_lists_core(queries, centers, rotation, list_decoded,
                             decoded_norms, list_indices, list_sizes,
                             filter_words, metric: DistanceType, k: int,
                             n_probes: int, block_rows: int, super_tile: int,
                             has_filter: bool, overflow_decoded=None,
                             overflow_norms=None, overflow_indices=None,
                             has_overflow: bool = False,
                             select_recall: float = 1.0,
                             dist_dtype: str = "float32",
                             interpret: bool = False):
    """The decoded-cache ADC scan, list-major: the query-major core's
    probes, distances and answers (up to float rounding, ties to the lower
    probe and slot as there), with each probed list read once a
    super-tile of ``super_tile`` queries. Per super-tile: the coarse
    probe; the plan (``_list_major_plan``); one ``pk.list_scan`` over the
    blocks at the precision the cache's dtype and ``dist_dtype`` state
    (:func:`contraction_precision`); the k groups of least minimum among
    each query's ``n_probes`` lists, which hold its k best slots; the
    overflow block; one final select. Ids are -1 where fewer than k
    candidates pass."""
    nq, dim = queries.shape
    n_lists, list_pad, _ = list_decoded.shape
    minimize = metric != DistanceType.InnerProduct
    prec = contraction_precision(list_decoded.dtype, dist_dtype)
    bad_fill = jnp.inf if minimize else -jnp.inf
    g_w = pk.SCAN_GROUP
    n_g = pk.list_scan_groups(list_pad)
    t = block_rows
    n_blocks = list_scan_blocks(super_tile * n_probes, n_lists, t)
    kg = min(k, n_probes * n_g)

    def _sel(vals, kk, sel_min):
        return select_k_maybe_approx(vals, kk, sel_min, select_recall)

    centers_rot = jax.lax.dot_general(
        centers, rotation, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    # each group's slots, ids and row terms
    slot, fresh = _list_scan_slots(list_pad)
    ok = (slot[None] < list_sizes[:, None, None]) & fresh[None]
    ids_g = list_indices[:, slot]  # [L, n_g, 128]
    if has_filter:
        ok = ok & bitset_filter_mask(ids_g, filter_words)
    row_terms = jnp.where(ok, decoded_norms[:, slot] if minimize else 0.0,
                          jnp.inf)
    ids_g = ids_g.reshape(n_lists * n_g, g_w)

    def s_body(qs):
        q_rot, _, probes = _cache_probes(qs, rotation, centers_rot, metric,
                                         n_probes, _sel)
        block_list, n_used, block_queries, pair_at = _list_major_plan(
            probes, n_lists, t, n_blocks)
        rows = q_rot[jnp.maximum(block_queries, 0)]  # [NB, T, rot]
        dist, mins = pk.list_scan(block_list, n_used, rows, centers_rot,
                                  list_decoded, row_terms, l2=minimize,
                                  precision=prec, interpret=interpret)
        # the kg groups of least minimum of each query, in (probe, group)
        # order, so that ties go to the lower probe and slot
        q_mins = mins.reshape(n_blocks * t, g_w)[pair_at][..., :n_g]
        sel = jnp.sort(jax.lax.top_k(
            -q_mins.reshape(qs.shape[0], n_probes * n_g), kg)[1], axis=1)
        p, g = sel // n_g, sel % n_g
        at = jnp.take_along_axis(pair_at, p, axis=1)
        vals = dist.reshape(n_blocks * n_g * t, g_w)[
            ((at // t) * n_g + g) * t + at % t]
        ids = ids_g[jnp.take_along_axis(probes, p, axis=1) * n_g + g]
        flat_d = vals.reshape(qs.shape[0], kg * g_w)
        v, i_out = _cache_answers(
            flat_d if minimize else -flat_d,
            ids.reshape(qs.shape[0], kg * g_w), q_rot, overflow_decoded,
            overflow_norms, overflow_indices, filter_words, metric, k,
            has_filter, has_overflow, prec, _sel)
        return v, jnp.where(v == bad_fill, -1, i_out)

    n_super = cdiv(nq, super_tile)
    qp = jnp.pad(queries.astype(jnp.float32),
                 ((0, n_super * super_tile - nq), (0, 0)))
    if n_super == 1:
        vals, idxs = s_body(qp)
    else:
        vals, idxs = jax.lax.map(s_body,
                                 qp.reshape(n_super, super_tile, dim))
        vals = vals.reshape(-1, k)
        idxs = idxs.reshape(-1, k)
    return vals[:nq], idxs[:nq]


_search_cache_lists_jit = jax.jit(
    _search_cache_lists_core,
    static_argnames=("metric", "k", "n_probes", "block_rows", "super_tile",
                     "has_filter", "has_overflow", "select_recall",
                     "dist_dtype", "interpret"),
)


def _search_lut_core(queries, centers, rotation, codebooks, list_codes,
                     list_indices, list_sizes, filter_words,
                     metric: DistanceType, k: int, n_probes: int, q_tile: int,
                     per_cluster: bool, pq_dim: int, pq_bits: int,
                     has_filter: bool, lut_dtype, dist_dtype,
                     overflow_decoded=None, overflow_norms=None,
                     overflow_indices=None, has_overflow: bool = False,
                 select_recall: float = 1.0, probe_tile: int = 0):
    """LUT-engine scan over packed codes (traceable core — also runs inside
    ``shard_map`` for the memory-lean sharded search, parallel/sharded.py).

    ``probe_tile`` bounds the peak scan intermediate: 0 or >= ``n_probes``
    scans all probed lists of a query tile in one pass (the original
    shape, peak [q_tile, n_probes, list_pad, …]); otherwise probes are
    processed in ``probe_tile``-wide chunks under ``lax.scan`` with a
    running top-k carry merged through the existing ``select_k`` machinery
    (the TPU analog of the GPU kernel's per-CTA probe loop), so the peak
    is [q_tile, probe_tile, list_pad, …] regardless of n_probes. Distance
    VALUES are bit-identical to the single-pass shape (each candidate's
    contraction is elementwise the same); only tie ORDER among equal
    distances can differ, because the running merge re-ranks ties by
    carry position rather than global flat index. The LUT build and the
    overflow block contract at the precision ``lut_dtype`` and
    ``dist_dtype`` state (:func:`contraction_precision`)."""
    nq, dim = queries.shape
    n_lists, list_pad, _ = list_codes.shape
    pq_len = codebooks.shape[2]
    book = codebooks.shape[1]
    minimize = metric != DistanceType.InnerProduct
    p_tile = probe_tile if 0 < probe_tile < n_probes else n_probes
    prec = contraction_precision(lut_dtype, dist_dtype)

    def _sel(vals, kk, sel_min):
        return select_k_maybe_approx(vals, kk, sel_min, select_recall)

    n_q_tiles = cdiv(nq, q_tile)
    pad_q = n_q_tiles * q_tile - nq
    qp = jnp.pad(queries.astype(jnp.float32), ((0, pad_q), (0, 0)))

    centers_rot = jax.lax.dot_general(
        centers, rotation, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [n_lists, rot_dim]
    cb_norms = jnp.sum(codebooks.astype(jnp.float32) ** 2, -1)  # [G, book]
    valid_slot = jnp.arange(list_pad)[None, :] < list_sizes[:, None]

    def q_body(qt):
        # ---- coarse cluster selection (select_clusters,
        # detail/ivf_pq_search.cuh:69-155)
        q_rot = jax.lax.dot_general(
            qt, rotation, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [t, rot_dim]
        dots_c = jax.lax.dot_general(
            q_rot, centers_rot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        if metric == DistanceType.InnerProduct:
            coarse = dots_c
            _, probes = _sel(coarse, n_probes, False)
        else:
            cn = jnp.sum(centers_rot * centers_rot, -1)
            coarse = cn[None, :] - 2.0 * dots_c  # + ||q||² (rank-invariant)
            _, probes = _sel(coarse, n_probes, True)
        # [t, P]
        bad_fill = jnp.inf if minimize else -jnp.inf

        def probe_block(probes_blk, probe_ok):
            """LUT build + code scan of one probe chunk ``probes_blk``
            [t, pt] → (distances [t, pt, pad], ids [t, pt, pad]).
            ``probe_ok`` masks the scan-padding probes of the last chunk
            (None when every probe is real)."""
            pt = probes_blk.shape[1]
            # ---- LUT per (query, probe): [t, pt, pq_dim, book]
            qr_res = q_rot[:, None, :] - centers_rot[probes_blk]
            if metric == DistanceType.InnerProduct:
                qr_res = jnp.broadcast_to(q_rot[:, None, :], qr_res.shape)
            sub = qr_res.reshape(qt.shape[0], pt, pq_dim, pq_len)
            if per_cluster:
                cb_p = codebooks[probes_blk]  # [t, pt, book, l]
                dots = jnp.einsum("tpsl,tpcl->tpsc", sub, cb_p,
                                  preferred_element_type=jnp.float32,
                                  precision=prec)
                cbn = cb_norms[probes_blk][:, :, None, :]
            else:
                dots = jnp.einsum("tpsl,scl->tpsc", sub, codebooks,
                                  preferred_element_type=jnp.float32,
                                  precision=prec)
                cbn = cb_norms[None, None, :, :]  # [1, 1, s, book]
            if metric == DistanceType.InnerProduct:
                # score = q·center + Σ_s q_sub·cb[code_s]
                lut = dots
                base = jnp.take_along_axis(
                    dots_c, probes_blk, axis=1)  # [t, pt] — q·center term
            else:
                # ||q−center−decode||² = ||q_res||² − 2 q_res·cb + ||cb||²
                qn = jnp.sum(qr_res * qr_res, -1)  # [t, pt]
                lut = cbn - 2.0 * dots
                base = qn
            if str(lut_dtype) in ("float8_e4m3fn", "float8_e5m2"):
                # fp8 LUT with per-subspace max-abs scaling (the
                # reference's fp_8bit offset/scale normalization,
                # detail/ivf_pq_fp_8bit.cuh)
                lut_scale = jnp.maximum(
                    jnp.max(jnp.abs(lut), axis=-1), 1e-30)  # [t, pt, s]
                lut = (lut / lut_scale[..., None]).astype(lut_dtype)
            else:
                lut_scale = None
                lut = lut.astype(lut_dtype)

            # ---- gather probed lists and scan codes
            g_codes = list_codes[probes_blk]  # [t, pt, pad, n_bytes] u8
            g_idx = list_indices[probes_blk]  # [t, pt, pad]
            g_valid = valid_slot[probes_blk]
            codes = _unpack_codes(g_codes, pq_dim, pq_bits)  # [t,pt,pad,s]
            # flat-LUT gather: score contribution LUT[t,pt,s,code]
            flat_lut = lut.reshape(qt.shape[0], pt, pq_dim * book)
            gidx = codes + (jnp.arange(pq_dim) * book)[None, None, None, :]
            gather_dtype = dist_dtype if lut_scale is None else flat_lut.dtype
            contrib = jnp.take_along_axis(
                flat_lut[:, :, None, :].astype(gather_dtype),
                gidx.reshape(qt.shape[0], pt, list_pad * pq_dim)[:, :, None, :],
                axis=-1,
            ).reshape(qt.shape[0], pt, list_pad, pq_dim)
            if lut_scale is not None:
                # de-scale fp8 contributions per subspace before
                # accumulating
                contrib = contrib.astype(dist_dtype) * lut_scale[
                    :, :, None, :].astype(dist_dtype)
            d = jnp.sum(contrib.astype(dist_dtype),
                        axis=-1).astype(jnp.float32)
            d = d + base[:, :, None]

            ok = g_valid
            if has_filter:
                ok = ok & bitset_filter_mask(g_idx, filter_words)
            if probe_ok is not None:
                ok = ok & probe_ok[None, :, None]
                g_idx = jnp.where(probe_ok[None, :, None], g_idx, -1)
            d = jnp.where(ok, d, bad_fill)
            return d, g_idx

        if p_tile == n_probes:
            d, g_idx = probe_block(probes, None)
            n_cand = n_probes * list_pad
            flat_d = d.reshape(qt.shape[0], n_cand)
            flat_i = g_idx.reshape(qt.shape[0], n_cand)
        else:
            # probe-tile loop: running top-kk merge keeps the peak live
            # set at [t, p_tile, pad, …] however many lists are probed
            n_pt = cdiv(n_probes, p_tile)
            pp = n_pt * p_tile
            probes_p = jnp.pad(probes, ((0, 0), (0, pp - n_probes)))
            ok_p = (jnp.arange(pp) < n_probes).reshape(n_pt, p_tile)
            blocks = jnp.moveaxis(
                probes_p.reshape(qt.shape[0], n_pt, p_tile), 1, 0)
            kk = min(k, n_probes * list_pad)

            def step(carry, xs):
                cv, ci = carry
                pr, okb = xs
                d, gi = probe_block(pr, okb)
                cand_v = jnp.concatenate(
                    [cv, d.reshape(d.shape[0], -1)], axis=1)
                cand_i = jnp.concatenate(
                    [ci, gi.reshape(gi.shape[0], -1)], axis=1)
                v, sel = _sel(cand_v, kk, minimize)
                return (v, jnp.take_along_axis(cand_i, sel, axis=1)), None

            init = (jnp.full((qt.shape[0], kk), bad_fill, jnp.float32),
                    jnp.full((qt.shape[0], kk), -1, jnp.int32))
            (flat_d, flat_i), _ = jax.lax.scan(step, init, (blocks, ok_p))
            n_cand = kk

        if has_overflow:
            od, oi = _pq_overflow_scan(q_rot, overflow_decoded,
                                       overflow_norms, overflow_indices,
                                       filter_words, metric, has_filter,
                                       bad_fill, prec)
            flat_d = jnp.concatenate([flat_d, od], axis=1)
            flat_i = jnp.concatenate([flat_i, oi], axis=1)
            n_cand += od.shape[1]
        kk = min(k, n_cand)
        v, sel = _sel(flat_d, kk, minimize)
        i_out = jnp.take_along_axis(flat_i, sel, axis=1)
        if kk < k:
            v = jnp.pad(v, ((0, 0), (0, k - kk)), constant_values=bad_fill)
            i_out = jnp.pad(i_out, ((0, 0), (0, k - kk)), constant_values=-1)
        if metric == DistanceType.L2SqrtExpanded:
            v = jnp.sqrt(jnp.maximum(v, 0.0))
        return v, i_out

    if n_q_tiles == 1:
        vals, idxs = q_body(qp)
    else:
        vals, idxs = jax.lax.map(q_body, qp.reshape(n_q_tiles, q_tile, dim))
        vals = vals.reshape(-1, k)
        idxs = idxs.reshape(-1, k)
    return vals[:nq], idxs[:nq]


_search_jit = jax.jit(
    _search_lut_core,
    static_argnames=("metric", "k", "n_probes", "q_tile", "per_cluster",
                     "pq_dim", "pq_bits", "has_filter", "lut_dtype",
                     "dist_dtype", "has_overflow", "select_recall",
                     "probe_tile"),
)


#: public traceable-core name (see search_cache_core above)
search_lut_core = _search_lut_core


def _coarse_probes_rot(queries, centers, rotation, n_probes: int):
    """Shared coarse step of the fused cores: rotate the queries and pick
    the top-n_probes clusters in rotated space — the same math (and the
    same tie behavior) as the XLA engines' q_body preamble."""
    q_rot = jax.lax.dot_general(
        queries.astype(jnp.float32), rotation, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    centers_rot = jax.lax.dot_general(
        centers, rotation, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    dots_c = jax.lax.dot_general(
        q_rot, centers_rot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    cn = jnp.sum(centers_rot * centers_rot, -1)
    _, probes = select_k(cn[None, :] - 2.0 * dots_c, n_probes,
                         select_min=True)
    return q_rot, centers_rot, probes


def _fused_merge_overflow(v, i, q_rot, overflow_decoded, overflow_norms,
                          overflow_indices, k: int):
    """Merge the kernel's VMEM-carry survivors with the XLA overflow scan
    (squared space on both sides; at ``HIGHEST``, as the kernels
    contract). Selection already happened in-kernel,
    so the merge select runs with ``pad_rules=False`` — the k-pad rules
    model an HBM slab select and must not re-pad the short candidate list
    (ISSUE 10)."""
    od, oi = _pq_overflow_scan(q_rot, overflow_decoded, overflow_norms,
                               overflow_indices,
                               jnp.zeros((0,), jnp.uint32),
                               DistanceType.L2Expanded, False, jnp.inf,
                               jax.lax.Precision.HIGHEST)
    return select_k(jnp.concatenate([v, od], axis=1), k, select_min=True,
                    indices=jnp.concatenate([i, oi], axis=1),
                    pad_rules=False)


def _search_fused_cache_core(queries, centers, rotation, list_decoded,
                             decoded_norms, list_indices, list_sizes,
                             overflow_decoded, overflow_norms,
                             overflow_indices, metric: DistanceType, k: int,
                             n_probes: int, pad_tile: int,
                             has_overflow: bool, interpret: bool = False):
    """Fused-Pallas ADC scan over the decoded-residual cache
    (``scan_mode="pallas"``, L2 metrics): coarse selection stays XLA, then
    ``ops.pallas_kernels.fused_ivf_topk`` DMAs each probed cache slab to
    VMEM and merges ``||q_res||² − 2·q_res·dec + ||dec||²`` partials into
    an in-kernel top-k carry — the [nq, P, pad] candidate slab never
    exists in HBM and no k-pad rule applies to the fine scan.
    Unclamped, exactly like the XLA cache engine (ADC space)."""
    list_pad = list_decoded.shape[1]
    q_rot, centers_rot, probes = _coarse_probes_rot(
        queries, centers, rotation, n_probes)
    valid_slot = jnp.arange(list_pad)[None, :] < list_sizes[:, None]
    safe_ids = jnp.where(valid_slot, list_indices, -1)
    qr_res = q_rot[:, None, :] - centers_rot[probes]  # [nq, P, rot]
    qn = jnp.sum(qr_res * qr_res, -1)  # [nq, P]
    v, i = pk.fused_ivf_topk(probes, qr_res, qn, list_decoded,
                             decoded_norms, safe_ids, k, pad_tile=pad_tile,
                             clamp=False, interpret=interpret)
    if has_overflow:
        v, i = _fused_merge_overflow(v, i, q_rot, overflow_decoded,
                                     overflow_norms, overflow_indices, k)
    if metric == DistanceType.L2SqrtExpanded:
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


_search_fused_cache_jit = jax.jit(
    _search_fused_cache_core,
    static_argnames=("metric", "k", "n_probes", "pad_tile", "has_overflow",
                     "interpret"),
)


def _search_fused_lut_core(queries, centers, rotation, codebooks,
                           list_codes, list_indices, list_sizes,
                           overflow_decoded, overflow_norms,
                           overflow_indices, metric: DistanceType, k: int,
                           n_probes: int, pad_tile: int, has_overflow: bool,
                           interpret: bool = False):
    """Fused-Pallas LUT engine (``scan_mode="pallas"`` at the LUT memory
    regime; pq_bits=8, PER_SUBSPACE, fp32 LUT only): the per-probe LUT is
    built from the resident codebooks INSIDE the kernel and consumed by
    the one-hot code accumulation feeding the same VMEM top-k carry —
    neither the [nq, P, s, book] LUT nor the [nq, P, pad] candidate slab
    ever materializes in HBM (``ops.pallas_kernels.fused_pq_topk``)."""
    list_pad = list_codes.shape[1]
    q_rot, centers_rot, probes = _coarse_probes_rot(
        queries, centers, rotation, n_probes)
    valid_slot = jnp.arange(list_pad)[None, :] < list_sizes[:, None]
    safe_ids = jnp.where(valid_slot, list_indices, -1)
    cb_norms = jnp.sum(codebooks.astype(jnp.float32) ** 2, -1)
    v, i = pk.fused_pq_topk(probes, q_rot, centers_rot, codebooks,
                            cb_norms, list_codes, safe_ids, k,
                            pad_tile=pad_tile, interpret=interpret)
    if has_overflow:
        v, i = _fused_merge_overflow(v, i, q_rot, overflow_decoded,
                                     overflow_norms, overflow_indices, k)
    if metric == DistanceType.L2SqrtExpanded:
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


_search_fused_lut_jit = jax.jit(
    _search_fused_lut_core,
    static_argnames=("metric", "k", "n_probes", "pad_tile", "has_overflow",
                     "interpret"),
)

#: public traceable-core names for the fused paths (R004; audited by
#: graftcheck --jaxpr-audit at the VMEM-budget canonical shapes)
search_fused_cache_core = _search_fused_cache_core
search_fused_lut_core = _search_fused_lut_core


def lut_bytes_per_query_probe(list_pad: int, pq_dim: int, pq_bits: int,
                              lut_itemsize: int = 4,
                              dist_itemsize: int = 4) -> int:
    """TRUE peak live-set bytes of the LUT scan body per (query, probe).

    The pre-fix estimate counted only the LUT ``[t, P, s, book]`` and the
    packed-code gather — NOT the unpack intermediates (lo_b/hi_b/word
    int32, three ``[t, P, list_pad, pq_dim]`` arrays from the two-byte
    gather) or the score-gather temporaries (flat-LUT gather index +
    per-subspace contributions), which dominate as ``list_pad`` grows
    with n and are exactly what blew HBM at 1M rows (LUT_CRASH_tpu.json:
    q_tile solved from ~1/5 of the real footprint → a ~19 GB live set on
    a 16 GB chip). Itemized per (query, probe):

      LUT build   pq_dim·book·(4 + 4 + lut_itemsize)   dots + lut f32 + cast
      code gather list_pad·n_code_bytes                packed u8 rows
      unpack      list_pad·pq_dim·3·4                  lo_b, hi_b, word i32
      score       list_pad·pq_dim·(4 + dist_itemsize)  gather idx + contrib
      reduce      list_pad·(4 + 4 + 1)                 d f32, ids i32, valid
    """
    book = 1 << pq_bits
    n_code_bytes = pq_dim * pq_bits // 8
    return (pq_dim * book * (8 + lut_itemsize)
            + list_pad * n_code_bytes
            + list_pad * pq_dim * 12
            + list_pad * pq_dim * (4 + dist_itemsize)
            + list_pad * 9)


def plan_lut_tiles(n_probes: int, list_pad: int, pq_dim: int, pq_bits: int,
                   workspace_limit_bytes: int, lut_itemsize: int = 4,
                   dist_itemsize: int = 4) -> Tuple[int, int]:
    """Jointly solve (q_tile, probe_tile) for the LUT engine from the
    workspace budget so the scan is memory-bounded BY CONSTRUCTION: the
    peak intermediate is [q_tile, probe_tile, list_pad, …] and
    ``q_tile · probe_tile · lut_bytes_per_query_probe(...)`` fits the
    budget (full n_probes preferred; the probe-tile loop engages only
    when even an 8-query tile cannot hold all probes at once)."""
    per_qp = lut_bytes_per_query_probe(list_pad, pq_dim, pq_bits,
                                       lut_itemsize, dist_itemsize)
    q_tile, probe_tile = solve_joint_tiles(
        workspace_limit_bytes, per_qp, n_probes, outer_cap=256)
    if 1 < probe_tile < n_probes:
        # balance the probe grid (a 7-wide tile over 20 probes would pay
        # a 6/7-padding last chunk; cf. shape.balanced_tile)
        probe_tile = balanced_tile(n_probes, probe_tile, 1)
    return q_tile, probe_tile


def cache_bytes_per_query(n_probes: int, list_pad: int,
                          rot_dim: int) -> int:
    """TRUE peak live-set bytes of the decoded-cache scan per query: the
    gathered cache tile [P, pad, rot] bf16, its fp32 upcast feeding the
    MXU einsum, and the fp32 distance/id/mask temporaries. The itemized
    accounting ``plan_cache_tiles`` solves against — public so the
    obs.costs calibration audit can compare the planner's prediction to
    the compiled ``memory_analysis`` ground truth."""
    return n_probes * list_pad * (rot_dim * 6 + 24)


def plan_cache_tiles(n_probes: int, list_pad: int, rot_dim: int,
                     workspace_limit_bytes: int) -> int:
    """q_tile for the decoded-cache engine from the workspace budget: the
    peak per query is the gathered cache tile [P, pad, rot] bf16, its fp32
    upcast feeding the MXU einsum (the dominant term the old inline solve
    missed — a 3x undercount caught by the graftcheck jaxpr audit), and the
    fp32 distance/id/mask temporaries (shared by ``search`` and the audit,
    which certifies the solve statically)."""
    per_q = cache_bytes_per_query(n_probes, list_pad, rot_dim)
    q_tile = int(np.clip(workspace_limit_bytes // max(per_q, 1), 1, 1024))
    if q_tile >= 8:
        q_tile -= q_tile % 8
    return q_tile


def resolve_scan_mode(n_lists: int, list_pad: int, rot_dim: int,
                      n_code_bytes: int, cache_itemsize: int,
                      device_memory_bytes: Optional[int],
                      workspace_limit_bytes: int) -> str:
    """Memory-aware engine choice for ``scan_mode="auto"`` (VERDICT r2 #3;
    the reference's preferred_shmem_carveout / lut_dtype role,
    ivf_pq_types.hpp:110-146).

    HBM model (per chip):
      packed  = L·pad·(n_code_bytes + 4)          — always resident
      cache   = L·pad·(rot_dim·itemsize + 4)      — ON TOP of packed
      budget  = 50% of device HBM when the backend reports it (queries,
                per-tile gathers, XLA scratch and the rest of the program
                need the other half), else 4× workspace_limit (the CPU /
                unknown-backend fallback).
    Choose the decoded-cache engine only when packed + cache fit the
    budget; otherwise the LUT engine, which keeps only packed codes
    resident. The LUT engine is safe as the fallback at ANY index size:
    its scan workspace is bounded by construction — ``plan_lut_tiles``
    solves (q_tile, probe_tile) from the true peak live set
    (``lut_bytes_per_query_probe``), so the per-dispatch intermediate is
    [q_tile, probe_tile, list_pad, …] no matter how large n·n_probes
    grow (the 1M-row TPU-worker crash, LUT_CRASH_tpu.json, was the old
    one-axis q_tile solve under-counting that live set ~5×).

    DEEP-100M flagship shapes (deep-100M.json:252 — n=1e8, nlist=50000,
    pq_dim=96→rot_dim=96, pq_bits=8, bf16 cache): packed ≈ 1e8·(96+4)·1.5
    (1.5× pad budget) ≈ 15 GB total across 8 chips ≈ 1.9 GB/chip, while
    the decoded cache would ADD ≈ 1e8·(96·2+4)·1.5/8 ≈ 3.7 GB/chip and at
    nlist=50000 on ONE v5e chip (16 GB) the whole-index cache ≈ 29 GB —
    auto must (and does) pick LUT there; the test pins both regimes."""
    slots = n_lists * list_pad
    packed_bytes = slots * (n_code_bytes + 4)
    cache_bytes = slots * (rot_dim * cache_itemsize + 4)
    if device_memory_bytes is not None:
        budget = device_memory_bytes // 2
    else:
        budget = 4 * workspace_limit_bytes
    return "cache" if packed_bytes + cache_bytes <= budget else "lut"


def _record_scan(requested: str, engine: str, reason: str, params: dict,
                 plan: dict, precision: jax.lax.Precision) -> None:
    """A search dispatch's explain record, whose plan names the precision
    of the engine's float contractions, and its count in
    ``raft_tpu_ivf_pq_scan_plans_total``."""
    name = precision.name.lower()
    _SCAN_PLANS.labels(engine, name).inc()
    obs_explain.record_dispatch("ivf_pq", requested, engine, reason,
                                params=params,
                                plan={**plan, "precision": name})


@tracing.range("ivf_pq.search")
def search(
    index: Index,
    queries,
    k: int,
    params: Optional[SearchParams] = None,
    filter: Optional[Bitset] = None,
    res: Optional[Resources] = None,
    explain: bool = False,
):
    """Search (reference: ivf_pq::search, ivf_pq-inl.cuh:480). Distances for
    L2 metrics exclude nothing — they are the full ADC approximation; indices
    are source row ids, -1 where fewer than k candidates were probed. With
    ``explain=True`` a third element carries the
    :class:`raft_tpu.obs.explain.ExplainRecord` of the dispatch decision."""
    params = params or SearchParams()
    res = ensure_resources(res)
    if index.list_codes is None:
        raise ValueError("index has no data; call extend() first")
    queries = as_query_array(queries)  # host inputs stay host-side: the
    if queries.shape[1] != index.dim:  # jit call transfers the padded
        raise ValueError(              # batch in ONE dispatch
            f"query dim {queries.shape[1]} != index dim {index.dim}")
    nq = queries.shape[0]
    queries = pad_rows(queries, query_bucket(nq))  # serving batch bucket
    n_probes = int(min(params.n_probes, index.n_lists))
    list_pad = index.list_codes.shape[1]
    if params.scan_mode not in ("auto", "cache", "lut", "pallas"):
        raise ValueError(f"unknown scan_mode: {params.scan_mode}")
    scan_mode = params.scan_mode
    has_overflow = index.overflow_codes.shape[0] > 0
    if has_overflow:
        ensure_overflow_decoded(index, params.scan_cache_dtype)
    per_cluster = index.params.codebook_kind == CodebookGen.PER_CLUSTER
    # ---- fused Pallas scan+select (the VMEM top-k carry). Fallback
    # matrix (docs/tuning.md): L2 metrics, no filter, small k; the fused
    # LUT regime additionally needs byte codes (pq_bits=8), PER_SUBSPACE
    # codebooks and fp32 LUT/distance dtypes. Anything else falls through
    # to the XLA engines below — the mode is a performance hint, never a
    # correctness switch; each resolution records its reason code.
    requested = scan_mode
    use_fused = fused_interp = False
    dreason = "forced"  # explicit "cache"/"lut": honored as asked
    if scan_mode in ("auto", "pallas"):
        use_fused, fused_interp, dreason = pk.fused_dispatch_explained(
            "ivf_pq", scan_mode)
    ineligible = fused_ineligible_reason(
        index.metric, index.list_codes.dtype, int(k), filter is not None,
        False, require_float=False)
    pk.require_compiled_kernel("ivf_pq", scan_mode, ineligible)
    ex_params = {"k": int(k), "nq": nq, "bucket": queries.shape[0],
                 "n_probes": n_probes, "n_lists": index.n_lists,
                 "list_pad": list_pad, "pq_dim": index.pq_dim,
                 "pq_bits": index.pq_bits, "metric": index.metric.name}
    lut_unsupported = False
    with contextlib.ExitStack() as stack:
        cap = stack.enter_context(obs_explain.capture()) if explain else None
        v = i = None
        if use_fused and ineligible is None:
            # the same HBM model that splits cache/lut splits the fused
            # engines: the decoded cache is the faster scan when it fits
            engine = resolve_scan_mode(
                index.n_lists, list_pad, index.rot_dim,
                index.list_codes.shape[2],
                jnp.dtype(params.scan_cache_dtype).itemsize,
                device_memory_bytes=res.device_memory_bytes,
                workspace_limit_bytes=res.workspace_limit_bytes)
            if engine == "cache":
                ensure_scan_cache(index, params.scan_cache_dtype)
                pad_tile = pk.plan_fused_ivf_tile(
                    list_pad, index.rot_dim, int(k),
                    jnp.dtype(index.list_decoded.dtype).itemsize,
                    n_probes=n_probes)
                _record_scan(requested, "pallas_cache", dreason, ex_params,
                             {"memory_model": "cache", "pad_tile": pad_tile,
                              "interpret": fused_interp},
                             jax.lax.Precision.HIGHEST)
                v, i = _search_fused_cache_jit(
                    queries, index.centers, index.rotation,
                    index.list_decoded, index.decoded_norms,
                    index.list_indices, index.list_sizes,
                    index.overflow_decoded, index.overflow_norms,
                    index.overflow_indices, index.metric, int(k), n_probes,
                    pad_tile, has_overflow, fused_interp,
                )
            elif (not per_cluster and index.pq_bits == 8
                    and jnp.dtype(params.lut_dtype) == jnp.float32
                    and jnp.dtype(params.internal_distance_dtype)
                    == jnp.float32):
                pad_tile = pk.plan_fused_pq_tile(
                    list_pad, index.pq_dim, 1 << index.pq_bits,
                    index.codebooks.shape[2], int(k))
                _record_scan(requested, "pallas_lut", dreason, ex_params,
                             {"memory_model": "lut", "pad_tile": pad_tile,
                              "interpret": fused_interp},
                             jax.lax.Precision.HIGHEST)
                v, i = _search_fused_lut_jit(
                    queries, index.centers, index.rotation, index.codebooks,
                    index.list_codes, index.list_indices, index.list_sizes,
                    index.overflow_decoded, index.overflow_norms,
                    index.overflow_indices, index.metric, int(k), n_probes,
                    pad_tile, has_overflow, fused_interp,
                )
            else:
                # fused LUT regime unsupported at these params -> XLA engines
                lut_unsupported = True
                pk.require_compiled_kernel("ivf_pq", requested,
                                           "lut_params_unsupported")
        if v is None:
            memory_resolved = scan_mode in ("auto", "pallas")
            if memory_resolved:
                scan_mode = resolve_scan_mode(
                    index.n_lists, list_pad, index.rot_dim,
                    index.list_codes.shape[2],
                    jnp.dtype(params.scan_cache_dtype).itemsize,
                    device_memory_bytes=res.device_memory_bytes,
                    workspace_limit_bytes=res.workspace_limit_bytes)
            if requested not in ("auto", "pallas"):
                reason = "forced"
            elif lut_unsupported:
                reason = "lut_params_unsupported"
            elif use_fused and ineligible:
                reason = ineligible
            else:
                reason = dreason
            if scan_mode == "cache":  # resolve_scan_mode never says "auto"
                ensure_scan_cache(index, params.scan_cache_dtype)
                dist_dtype = jnp.dtype(params.internal_distance_dtype).name
                prec = contraction_precision(index.list_decoded.dtype,
                                             dist_dtype)
                words = (filter.words if filter is not None
                         else jnp.zeros((0,), jnp.uint32))
                bucket = queries.shape[0]
                lists, why = plan_list_scan(
                    res.device.platform, bucket, n_probes, index.n_lists,
                    list_pad, index.rot_dim,
                    jnp.dtype(index.list_decoded.dtype).itemsize, int(k),
                    index.overflow_indices.shape[0],
                    res.workspace_limit_bytes)
                if lists is not None:
                    rows = lists.n_blocks * lists.block_rows
                    _record_scan(
                        requested, "cache_lists", why, ex_params,
                        {"memory_model": "cache",
                         "memory_auto": memory_resolved,
                         "block_rows": lists.block_rows,
                         "n_blocks": lists.n_blocks,
                         "super_tiles": lists.n_super,
                         "padded_row_share": 1.0 - lists.super_tile
                         * n_probes / rows,
                         "interpret": lists.interpret,
                         "predicted_workspace_bytes": list_scan_bytes(
                             lists.super_tile, n_probes, index.n_lists,
                             list_pad, index.rot_dim, lists.block_rows,
                             int(k), index.overflow_indices.shape[0])},
                        prec)
                    v, i = _search_cache_lists_jit(
                        queries, index.centers, index.rotation,
                        index.list_decoded, index.decoded_norms,
                        index.list_indices, index.list_sizes, words,
                        index.metric, int(k), n_probes, lists.block_rows,
                        lists.super_tile, filter is not None,
                        index.overflow_decoded, index.overflow_norms,
                        index.overflow_indices, has_overflow,
                        select_recall=float(params.select_recall),
                        dist_dtype=dist_dtype, interpret=lists.interpret,
                    )
                else:
                    # workspace: gathered decoded cache [t,P,pad,rot] +
                    # dists
                    q_tile = plan_cache_tiles(n_probes, list_pad,
                                              index.rot_dim,
                                              res.workspace_limit_bytes)
                    _record_scan(requested, "cache", why, ex_params,
                                 {"memory_model": "cache",
                                  "memory_auto": memory_resolved,
                                  "q_tile": q_tile,
                                  "predicted_workspace_bytes": q_tile *
                                  cache_bytes_per_query(n_probes, list_pad,
                                                        index.rot_dim)},
                                 prec)
                    v, i = _search_cache_jit(
                        queries, index.centers, index.rotation,
                        index.list_decoded, index.decoded_norms,
                        index.list_indices, index.list_sizes, words,
                        index.metric, int(k), n_probes, q_tile,
                        filter is not None,
                        index.overflow_decoded, index.overflow_norms,
                        index.overflow_indices, has_overflow,
                        select_recall=float(params.select_recall),
                        dist_dtype=dist_dtype,
                    )
            else:
                # workspace: the TRUE peak live set of the scan body (LUT
                # build + code gather + unpack/score temporaries —
                # lut_bytes_per_query_probe), solved jointly into
                # (q_tile, probe_tile) so the engine never materializes more
                # than the budget however large n·n_probes grow
                lut_dtype = jnp.dtype(params.lut_dtype)
                dist_dtype = jnp.dtype(params.internal_distance_dtype)
                q_tile, probe_tile = plan_lut_tiles(
                    n_probes, list_pad, index.pq_dim, index.pq_bits,
                    res.workspace_limit_bytes, lut_dtype.itemsize,
                    dist_dtype.itemsize)
                _record_scan(requested, "lut", reason, ex_params,
                             {"memory_model": "lut",
                              "memory_auto": memory_resolved,
                              "q_tile": q_tile, "probe_tile": probe_tile,
                              "predicted_workspace_bytes": q_tile *
                              probe_tile * lut_bytes_per_query_probe(
                                  list_pad, index.pq_dim, index.pq_bits,
                                  lut_dtype.itemsize, dist_dtype.itemsize)},
                             contraction_precision(lut_dtype, dist_dtype))
                v, i = _search_jit(
                    queries, index.centers, index.rotation, index.codebooks,
                    index.list_codes, index.list_indices, index.list_sizes,
                    filter.words if filter is not None
                    else jnp.zeros((0,), jnp.uint32),
                    index.metric, int(k), n_probes, q_tile, per_cluster,
                    index.pq_dim, index.pq_bits, filter is not None,
                    lut_dtype.name, dist_dtype.name,
                    index.overflow_decoded, index.overflow_norms,
                    index.overflow_indices, has_overflow,
                    select_recall=float(params.select_recall),
                    probe_tile=probe_tile,
                )
    if explain:
        return v[:nq], i[:nq], cap.last
    return v[:nq], i[:nq]


_SERIAL_VERSION = 2  # v2: + list_pad_expansion, overflow block


def serialize(index: Index, file) -> None:
    """reference: detail/ivf_pq_serialize.cuh. Paths are written
    atomically (tmp + os.replace) with per-record crc framing."""
    if index.list_codes is None:
        raise ValueError("index has no data; call extend() before serialize()")
    with ser.writer_for(file) as stream:
        w = ser.IndexWriter(stream, "ivf_pq", _SERIAL_VERSION)
        w.scalar(int(index.metric), "<i4")
        w.scalar(index.params.n_lists, "<i8")
        w.scalar(index.params.kmeans_n_iters, "<i4")
        w.scalar(index.params.kmeans_trainset_fraction, "<f8")
        w.scalar(index.params.pq_bits, "<i4")
        w.scalar(index.pq_dim, "<i4")
        w.scalar(int(index.params.codebook_kind), "<i4")
        w.scalar(1 if index.params.force_random_rotation else 0, "<i4")
        w.scalar(index.params.list_pad_expansion, "<f8")
        w.scalar(index.n_rows, "<i8")
        w.array(index.centers)
        w.array(index.rotation)
        w.array(index.codebooks)
        w.array(index.list_codes)
        w.array(index.list_indices)
        w.array(index.list_sizes)
        w.array(index.overflow_codes)
        w.array(index.overflow_labels)
        w.array(index.overflow_indices)
        w.finish()


def deserialize(file, res: Optional[Resources] = None) -> Index:
    ensure_resources(res)
    with ser.reader_for(file) as stream:
        r = ser.IndexReader(stream, "ivf_pq", _SERIAL_VERSION)
        metric = DistanceType(r.scalar())
        n_lists = r.scalar()
        kmeans_n_iters = r.scalar()
        frac = r.scalar()
        pq_bits = r.scalar()
        pq_dim = r.scalar()
        kind = CodebookGen(r.scalar())
        force_rot = bool(r.scalar())
        # v1 files predate the capped pad: max-driven layout, no spill
        expansion = r.scalar() if r.version >= 2 else 1e30
        params = IndexParams(
            n_lists=n_lists, metric=metric, kmeans_n_iters=kmeans_n_iters,
            kmeans_trainset_fraction=frac, pq_bits=pq_bits, pq_dim=pq_dim,
            codebook_kind=kind, force_random_rotation=force_rot,
            list_pad_expansion=expansion,
        )
        n_rows = r.scalar()
        centers = jnp.asarray(r.array())
        rotation = jnp.asarray(r.array())
        codebooks = jnp.asarray(r.array())
        codes = jnp.asarray(r.array())
        idxs = jnp.asarray(r.array())
        sizes = jnp.asarray(r.array())
        o_codes = jnp.asarray(r.array()) if r.version >= 2 else None
        o_labels = jnp.asarray(r.array()) if r.version >= 2 else None
        o_ids = jnp.asarray(r.array()) if r.version >= 2 else None
        r.finish()
        return Index(params, pq_dim, centers, rotation, codebooks, codes,
                     idxs, sizes, n_rows, o_codes, o_labels, o_ids)


# ------------------------------------------------------------------ helpers


class helpers:
    """Code access utilities (reference: ivf_pq_helpers.cuh —
    ``helpers::codepacker::{pack,unpack}``, ``reconstruct_list_data``)."""

    @staticmethod
    def unpack_list_codes(index: "Index", label: int) -> np.ndarray:
        """Unpacked per-vector PQ codes of list ``label`` → [size, pq_dim]
        uint8 host array."""
        size = int(np.asarray(index.list_sizes)[label])
        packed = jnp.asarray(np.asarray(index.list_codes)[label, :size])
        return np.asarray(_unpack_codes(packed, index.pq_dim,
                                        index.pq_bits)).astype(np.uint8)

    @staticmethod
    def pack_list_codes(index: "Index", label: int, codes,
                        ids=None) -> "Index":
        """Overwrite list ``label`` with unpacked ``codes`` [n, pq_dim];
        returns a new Index."""
        codes = np.asarray(codes, np.uint8)
        packed = _pack_codes_np(codes, index.pq_bits)
        pad = index.list_codes.shape[1]
        if len(packed) > pad:
            raise ValueError(f"{len(packed)} codes exceed list capacity {pad}")
        data = np.asarray(index.list_codes).copy()
        idxs = np.asarray(index.list_indices).copy()
        sizes = np.asarray(index.list_sizes).copy()
        data[label, :len(packed)] = packed
        data[label, len(packed):] = 0
        if ids is not None:
            idxs[label, :len(packed)] = np.asarray(ids, np.int32)
        idxs[label, len(packed):] = -1
        old = int(sizes[label])
        sizes[label] = len(packed)
        out = Index(index.params, index.pq_dim, index.centers, index.rotation,
                    index.codebooks, jnp.asarray(data), jnp.asarray(idxs),
                    jnp.asarray(sizes), index.n_rows - old + len(packed))
        return out

    @staticmethod
    def reconstruct_list_data(index: "Index", label: int) -> np.ndarray:
        """Approximate original vectors of list ``label``
        (reference: helpers::reconstruct_list_data): center + rotationᵀ ·
        decoded residual."""
        codes = helpers.unpack_list_codes(index, label)  # [size, pq_dim]
        book = index.pq_book_size
        cbs = np.asarray(index.codebooks)
        if index.params.codebook_kind == CodebookGen.PER_CLUSTER:
            dec = cbs[label][codes.reshape(-1)]  # [size*s, l]
        else:
            flat = cbs.reshape(index.pq_dim * book, index.pq_len)
            offs = codes + np.arange(index.pq_dim)[None, :] * book
            dec = flat[offs.reshape(-1)]
        dec = dec.reshape(len(codes), index.rot_dim)
        center = np.asarray(index.centers)[label]
        rot = np.asarray(index.rotation)  # [rot_dim, dim]
        return center[None, :] + dec @ rot
