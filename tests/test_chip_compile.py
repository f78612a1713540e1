"""Compile every fused kernel for a described TPU v5e — no chip needed.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (jax.experimental.topologies). Nothing runs, so
these say nothing about results or times; they catch what interpret-mode
tests cannot — the (8, 128) block rule, scoped-VMEM overflow, SMEM
overflow of the prefetched tables — at the shapes ``chip_smoke.py``
drives: raft-ann-bench's sift-128-euclidean (10k queries × 1M × 128,
k=10; IVF n_lists=1024, nprobe=32, list pad 1024; PQ pq_dim=64, 8 bits;
CAGRA graph degree 32, itopk 64) and the ring shift on a 2×2 mesh.

The topology is described only inside the module fixture below: one
process at a time may load the TPU library, so it must never happen at
import (every xdist worker imports every test file). The persistent
compile cache is off around these compiles — a described-device entry
cannot be read back without a chip.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.ops import pallas_kernels as pk

NQ, N, DIM, K = 10_000, 1_000_000, 128, 10
N_LISTS, LIST_PAD, N_PROBES = 1024, 1024, 32
PQ_DIM, PQ_LEN, BOOK = 64, 2, 256
DEGREE, ITOPK = 32, 64
#: device memory of one v5e chip
HBM_BYTES = 16 << 30
#: ``memory_stats()["bytes_limit"]`` of one v5e chip, of which
#: ``Resources`` plans tiles in a quarter
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total <= HBM_BYTES, f"{total} bytes do not fit one v5e"
    return compiled


def test_fused_l2_topk_compiles_at_sift1m(sds):
    tm, tn = pk.plan_fused_topk_tiles(NQ, N, DIM, K)
    assert pk.fused_topk_tile_bytes(tm, tn, DIM, K) <= pk.DEFAULT_VMEM_BUDGET
    _compile(lambda x, y, xn, yn: pk.fused_l2_topk(
        x, y, K, x_norms=xn, y_norms=yn),
        sds((NQ, DIM), jnp.float32), sds((N, DIM), jnp.float32),
        sds((NQ,), jnp.float32), sds((N,), jnp.float32))


@pytest.mark.parametrize("dtype,clamp", [(jnp.float32, True),
                                         (jnp.bfloat16, False)],
                         ids=["ivf_flat", "ivf_pq_cache"])
def test_fused_ivf_topk_compiles_at_sift1m(sds, dtype, clamp):
    _compile(lambda pr, qr, qn, ld, rn, li: pk.fused_ivf_topk(
        pr, qr, qn, ld, rn, li, K, clamp=clamp),
        sds((NQ, N_PROBES), jnp.int32), sds((NQ, N_PROBES, DIM), jnp.float32),
        sds((NQ, N_PROBES), jnp.float32),
        sds((N_LISTS, LIST_PAD, DIM), dtype),
        sds((N_LISTS, LIST_PAD), jnp.float32),
        sds((N_LISTS, LIST_PAD), jnp.int32))


def test_fused_pq_topk_compiles_at_sift1m(sds):
    _compile(lambda pr, q, c, cb, cbn, codes, li: pk.fused_pq_topk(
        pr, q, c, cb, cbn, codes, li, K),
        sds((NQ, N_PROBES), jnp.int32), sds((NQ, DIM), jnp.float32),
        sds((N_LISTS, DIM), jnp.float32),
        sds((PQ_DIM, BOOK, PQ_LEN), jnp.float32),
        sds((PQ_DIM, BOOK), jnp.float32),
        sds((N_LISTS, LIST_PAD, PQ_DIM), jnp.uint8),
        sds((N_LISTS, LIST_PAD), jnp.int32))


def test_fused_cagra_topk_compiles_at_sift1m(sds):
    from raft_tpu.neighbors import cagra

    itopk, width, max_iter, n_seeds = cagra.resolve_search_plan(
        cagra.SearchParams(itopk_size=ITOPK), K, N)
    _compile(lambda q, ds, g, s: pk.fused_cagra_topk(
        q, ds, g, s, K, itopk, width, max_iter=max_iter),
        sds((NQ, DIM), jnp.float32), sds((N, DIM), jnp.float32),
        sds((N, DEGREE), jnp.int32), sds((NQ, n_seeds), jnp.int32))


def test_ring_shift_compiles_on_a_2x2_mesh(topo):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices).reshape(4), ("x",))
    ring = jax.shard_map(lambda b: pk.pallas_ring_shift(b, "x", 4),
                         mesh=mesh, in_specs=P(None, "x"),
                         out_specs=P(None, "x"), check_vma=False)
    # the ring merge's packed [3, nq, k] candidate block, per device
    block = jax.ShapeDtypeStruct((3, 4 * NQ, K), jnp.float32,
                                 sharding=NamedSharding(mesh, P(None, "x")))
    _compile(ring, block)


def test_sharded_knn_temp_fits_at_deep100m_shard(topo):
    """``parallel.sharded.knn`` at the deep100m-exact cell's shape (1000
    queries, 12.5M × 96 rows over a 2x2 mesh, k=100), with the tiles a
    v5e's default ``Resources`` plans: the local scan is tiled, so each
    chip's workspace stays a few tiles, not the [1000, 3.125M] distance
    matrix (12.5 GB) the full-row scan needed."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu import Resources
    from raft_tpu.parallel import comms as comms_mod
    from raft_tpu.parallel import sharded

    comms = comms_mod.init_comms(list(topo.devices), axis="data")
    q = jax.ShapeDtypeStruct((1000, 96), jnp.float32,
                             sharding=NamedSharding(comms.mesh, P()))
    x = jax.ShapeDtypeStruct((12_500_000, 96), jnp.float32,
                             sharding=NamedSharding(comms.mesh,
                                                    P("data", None)))
    res = Resources(workspace_limit_bytes=int(V5E_BYTES_LIMIT * 0.25))
    compiled = jax.jit(lambda q, x: sharded.knn(comms, q, x, 100, res=res)
                       ).lower(q, x).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 2 << 30, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= HBM_BYTES
