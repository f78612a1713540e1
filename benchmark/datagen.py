"""On-device synthetic vectors from ``--seed``.

A copy of ``raft_tpu.bench.datagen.low_rank_clusters`` rewritten with
``jax.random`` so that it runs on the chip: gaussian clusters in an
``intrinsic``-dimensional latent space (unit cluster std, centres drawn
N(0, spread^2)), embedded in ``dim`` ambient dimensions by one shared
random projection. Base rows and held-out queries share the centres and
the projection; each draws its latent points from its own key. A
configuration may fix its collection (``dataset.base_seed``), as a public
dataset's base is fixed: the centres, the projection and the base rows
then come from that seed, and only the queries from ``--seed``.

Rows are made in chunks of ``chunk`` rows with ``lax.map``, so the latent
draw of one chunk is the largest temporary beside the output, and chunk
``c`` of a set is the same whichever device makes it: a shard made on
device ``r`` holds exactly the rows a one-device run would hold there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**63: the low and the high 32
    bits are folded in one after the other."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def keys(seed: int) -> dict:
    """The named keys of one run: ``model`` (centres and projection),
    ``base`` and ``queries`` (latent draws)."""
    root = _seed_key(seed)
    return {name: jax.random.fold_in(root, i)
            for i, name in enumerate(("model", "base", "queries"))}


@functools.partial(jax.jit, static_argnames=(
    "first_chunk", "n_chunks", "chunk", "dim", "n_centers", "intrinsic",
    "spread"))
def _rows(model_key, set_key, first_chunk: int, n_chunks: int, chunk: int,
          dim: int, n_centers: int, intrinsic: int, spread: float):
    k_proj, k_cent = jax.random.split(model_key)
    proj = jax.random.normal(k_proj, (intrinsic, dim), jnp.float32)
    centers = jax.random.normal(k_cent, (n_centers, intrinsic),
                                jnp.float32) * spread

    def one(c):
        k_lab, k_z = jax.random.split(jax.random.fold_in(set_key, c))
        lab = jax.random.randint(k_lab, (chunk,), 0, n_centers)
        z = centers[lab] + jax.random.normal(k_z, (chunk, intrinsic),
                                             jnp.float32)
        return jnp.matmul(z, proj, precision=_HIGHEST)

    out = jax.lax.map(one, first_chunk + jnp.arange(n_chunks))
    return out.reshape(n_chunks * chunk, dim)


def make_rows(seed: int, which: str, first_row: int, n_rows: int, dim: int,
              gen: dict, chunk: int, device=None,
              model_seed=None) -> jax.Array:
    """Rows ``[first_row, first_row + n_rows)`` of set ``which`` ("base" or
    "queries") as a float32 array on ``device`` (the default device when
    None). ``gen`` holds the generator's parameters ``n_centers``,
    ``intrinsic`` and ``spread``; both ends must fall on boundaries of
    ``chunk`` rows. The centres and the projection come from
    ``model_seed`` (``seed`` when None), the latent draw from ``seed``."""
    chunk = int(chunk)
    if first_row % chunk or n_rows % chunk:
        raise ValueError(f"rows [{first_row}, +{n_rows}) are not whole "
                         f"chunks of {chunk}")
    model = keys(seed if model_seed is None else model_seed)["model"]
    args = (model, keys(seed)[which])
    kw = dict(first_chunk=first_row // chunk, n_chunks=n_rows // chunk,
              chunk=chunk, dim=int(dim), n_centers=int(gen["n_centers"]),
              intrinsic=int(gen["intrinsic"]), spread=float(gen["spread"]))
    if device is None:
        return _rows(*args, **kw)
    args = jax.device_put(args, device)
    with jax.default_device(device):
        return _rows(*args, **kw)
