"""Persistent compilation cache.

Reference analog: the ``-ext``/``-inl`` explicit-instantiation split +
``libraft`` precompiled library (SURVEY.md §1, util/raft_explicit.hpp) —
RAFT pre-builds its expensive templates once so users don't pay nvcc time
per TU. The XLA analog is the persistent compilation cache: traced programs
compile once per (shape, dtype, flags) and later processes load the cached
executable instead of re-running XLA.

Where the cache lives is decided outside the program: JAX itself reads
``JAX_COMPILATION_CACHE_DIR``, and when that is set nothing here names
another directory. Unset, the cache sits at one fixed path inside the
checkout (:data:`DEFAULT_CACHE_DIR`, git-ignored) — the path is part of
the cache's key, so it must not move between processes.
"""

from __future__ import annotations

import os

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache(min_compile_time_secs: float = 1.0) -> str:
    """Turn on XLA's on-disk compilation cache (idempotent). Returns the
    cache directory: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    :data:`DEFAULT_CACHE_DIR`. Call once at program start; all
    subsequent jit compilations (ivf/cagra search kernels, pairwise
    engines, …) persist across processes — the runtime analog of
    shipping ``libraft``."""
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or DEFAULT_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    return cache_dir
