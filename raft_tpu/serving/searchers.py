"""Per-family searcher handles: one uniform, serving-shaped facade over
the four index families' public ``search()`` wrappers.

A handle owns (a) the index, pinned device-resident once at
:meth:`Searcher.place` (``jax.device_put`` per array attribute — never
per call; a per-call upload would be the single largest serving
cost), and (b) a closed-over search callable taking a
host batch ``[n, dim]`` and returning the public wrapper's
``(distances, indices)`` device arrays for exactly those ``n`` rows.

The handles deliberately call the PUBLIC wrappers, not the traced cores:
the wrappers own query bucketing, workspace tile solves, and scan-mode
resolution, so serving inherits every memory-budget guarantee the
wrappers certify (graftcheck jaxpr audit) instead of re-deriving static
arguments that could drift.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import numpy as np

__all__ = ["Searcher", "make_searcher", "brute_force_searcher",
           "ivf_flat_searcher", "ivf_pq_searcher", "cagra_searcher",
           "elastic_searcher", "tiered_ivf_pq_searcher",
           "mutable_ivf_searcher"]


@dataclasses.dataclass
class Searcher:
    """Uniform serving handle for one built index."""

    family: str
    dim: int
    index: object
    #: (queries_np [n, dim], k) -> (distances, indices) device arrays [n, k]
    search: Callable[[np.ndarray, int], Tuple[jax.Array, jax.Array]]
    query_dtype: np.dtype = np.dtype(np.float32)
    #: (queries, k, overrides) -> (distances, indices): ``search`` with
    #: per-call SearchParams overrides — the adaptive planner's hook
    #: (docs/tuning.md "Adaptive planning"). Overrides are applied onto
    #: the handle's base params via ``dataclasses.replace`` (unknown
    #: keys are a typed error, so a stale frontier artifact fails loud);
    #: the same public wrapper serves, so every exactness/memory-budget
    #: guarantee of ``search`` carries over. None for handles without
    #: adjustable knobs (elastic restores).
    search_with: Optional[
        Callable[[np.ndarray, int, dict],
                 Tuple[jax.Array, jax.Array]]] = None

    def place(self) -> int:
        """Pin every array attribute of the index on the default device
        (idempotent). Returns the number of arrays placed. Host numpy
        attributes become committed device arrays, so no search ever
        re-uploads index state."""
        n = 0
        attrs = getattr(self.index, "__dict__", {})
        for name, value in list(attrs.items()):
            if isinstance(value, (np.ndarray, jax.Array)):
                setattr(self.index, name, jax.device_put(value))
                n += 1
        return n

    @property
    def coverage(self) -> float:
        """Fraction of indexed rows this handle can actually search: 1.0
        for a normal index, < 1.0 for a degraded elastic restore
        (``allow_partial=True``, docs/robustness.md). The engine surfaces
        it in ``health()``/stats and records transitions across
        :meth:`Engine.swap_index`."""
        return float(getattr(self.index, "coverage", 1.0))


def brute_force_searcher(index, res=None, scan_dtype=None,
                         refine_ratio: float = 4.0,
                         select_recall: float = 1.0) -> Searcher:
    from raft_tpu.neighbors import brute_force

    base = {"scan_dtype": scan_dtype, "refine_ratio": refine_ratio,
            "select_recall": select_recall, "scan_mode": "auto"}

    def search_with(queries: np.ndarray, k: int, overrides: dict):
        kw = dict(base)
        for name, value in overrides.items():
            if name not in kw:
                raise TypeError(
                    f"brute_force operating point has no knob {name!r} "
                    f"(knobs: {sorted(kw)})")
            kw[name] = value
        return brute_force.search(index, queries, k, res=res, **kw)

    def search(queries: np.ndarray, k: int):
        return search_with(queries, k, {})

    return Searcher("brute_force", int(index.dim), index, search,
                    np.dtype(index.dataset.dtype), search_with=search_with)


def ivf_flat_searcher(index, params=None, res=None) -> Searcher:
    from raft_tpu.neighbors import ivf_flat

    params = params or ivf_flat.SearchParams()

    def search_with(queries: np.ndarray, k: int, overrides: dict):
        p = dataclasses.replace(params, **overrides) if overrides \
            else params
        return ivf_flat.search(index, queries, k, p, res=res)

    def search(queries: np.ndarray, k: int):
        return ivf_flat.search(index, queries, k, params, res=res)

    return Searcher("ivf_flat", int(index.dim), index, search,
                    search_with=search_with)


def ivf_pq_searcher(index, params=None, res=None) -> Searcher:
    from raft_tpu.neighbors import ivf_pq

    params = params or ivf_pq.SearchParams()

    def search_with(queries: np.ndarray, k: int, overrides: dict):
        p = dataclasses.replace(params, **overrides) if overrides \
            else params
        return ivf_pq.search(index, queries, k, p, res=res)

    def search(queries: np.ndarray, k: int):
        return ivf_pq.search(index, queries, k, params, res=res)

    return Searcher("ivf_pq", int(index.dim), index, search,
                    search_with=search_with)


def cagra_searcher(index, params=None, res=None) -> Searcher:
    from raft_tpu.neighbors import cagra

    params = params or cagra.SearchParams()

    def search_with(queries: np.ndarray, k: int, overrides: dict):
        p = dataclasses.replace(params, **overrides) if overrides \
            else params
        return cagra.search(index, queries, k, p, res=res)

    def search(queries: np.ndarray, k: int):
        return cagra.search(index, queries, k, params, res=res)

    return Searcher("cagra", int(index.dim), index, search,
                    search_with=search_with)


def elastic_searcher(index, params=None, res=None) -> Searcher:
    """Serving handle over an elastic restore (``ElasticIvfPq`` /
    ``ElasticIvfFlat``, parallel/sharded.py) — the degraded-serving path:
    a partial checkpoint restored with ``allow_partial=True`` serves its
    surviving shards here with ``searcher.coverage`` < 1.0, and a later
    full restore is promoted in-place via :meth:`Engine.swap_index`."""
    from raft_tpu.parallel import sharded

    if isinstance(index, sharded.ElasticIvfPq):
        family, dim = "elastic_ivf_pq", int(index.rotation.shape[2])
    elif isinstance(index, sharded.ElasticIvfFlat):
        family, dim = "elastic_ivf_flat", int(index.list_data.shape[3])
    else:
        raise TypeError(
            f"elastic_searcher wants ElasticIvfPq/ElasticIvfFlat, got "
            f"{type(index).__name__}")

    def search(queries: np.ndarray, k: int):
        r = index.search(queries, k, params, res=res)
        return r.distances, r.indices

    return Searcher(family, dim, index, search)


def tiered_ivf_pq_searcher(index, params=None, res=None) -> Searcher:
    """Serving handle over a ``TieredIvfPq`` (neighbors/tiered.py).

    The index object's host-tier arrays live inside non-array
    attributes (``tier``, ``arena``), so :meth:`Searcher.place`'s
    device upload sweep copies only the coarse structures — demoting
    the lists to host RAM survives engine placement by construction.
    """
    from raft_tpu.neighbors import ivf_pq, tiered

    if not isinstance(index, tiered.TieredIvfPq):
        raise TypeError(f"tiered_ivf_pq_searcher wants TieredIvfPq, got "
                        f"{type(index).__name__}")
    params = params or ivf_pq.SearchParams()

    def search_with(queries: np.ndarray, k: int, overrides: dict):
        p = dataclasses.replace(params, **overrides) if overrides \
            else params
        return index.search(queries, k, p, res=res)

    def search(queries: np.ndarray, k: int):
        return index.search(queries, k, params, res=res)

    return Searcher("tiered_ivf_pq", int(index.dim), index, search,
                    search_with=search_with)


def mutable_ivf_searcher(index, params=None, res=None) -> Searcher:
    """Serving handle over a ``MutableIvf`` (neighbors/mutable.py).

    The writer's host mirrors (WAL, delta rows, tombstones) live inside
    non-array attributes, so :meth:`Searcher.place`'s device upload
    sweep never pins mutable host state — only the immutable base the
    writer wraps. Search goes through the writer's merged base+delta
    path, so a handle published by the background compactor and a
    handle wrapping the live writer return bit-identical results for
    the same applied prefix.
    """
    from raft_tpu.neighbors import mutable

    if not isinstance(index, mutable.MutableIvf):
        raise TypeError(f"mutable_ivf_searcher wants MutableIvf, got "
                        f"{type(index).__name__}")
    params = params if params is not None else index.default_search_params()

    def search_with(queries: np.ndarray, k: int, overrides: dict):
        p = dataclasses.replace(params, **overrides) if overrides \
            else params
        return index.search(queries, k, p, res=res)

    def search(queries: np.ndarray, k: int):
        return index.search(queries, k, params, res=res)

    return Searcher("mutable_ivf", int(index.dim), index, search,
                    search_with=search_with)


_FACTORIES = {
    "brute_force": brute_force_searcher,
    "ivf_flat": ivf_flat_searcher,
    "ivf_pq": ivf_pq_searcher,
    "cagra": cagra_searcher,
    "elastic": elastic_searcher,
    "tiered_ivf_pq": tiered_ivf_pq_searcher,
    "mutable_ivf": mutable_ivf_searcher,
}


def make_searcher(family: str, index, **kwargs) -> Searcher:
    """Factory by family name (``brute_force``/``ivf_flat``/``ivf_pq``/
    ``cagra``); keyword arguments flow to the family constructor."""
    try:
        factory = _FACTORIES[family]
    except KeyError:
        raise ValueError(
            f"unknown family {family!r}; expected one of "
            f"{sorted(_FACTORIES)}") from None
    return factory(index, **kwargs)
