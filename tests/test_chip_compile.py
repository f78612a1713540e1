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


@pytest.mark.parametrize("dtype,list_pad", [(jnp.float32, 1456),
                                            (jnp.bfloat16, 1448)],
                         ids=["float32", "bfloat16_odd_group"])
def test_list_scan_compiles_at_the_ivfpq_cell(sds, dtype, list_pad):
    """The list-major cache kernel at the ivfpq-sift1m-batch cell's plan
    (10k queries × nprobe 32 over 1024 lists: 3,516 blocks of 128 rows),
    its slabs read in place; the bfloat16 case's last group starts 8
    rows off a 16-row packed tile."""
    from raft_tpu.neighbors import ivf_pq

    plan, why = ivf_pq.plan_list_scan(
        "tpu", NQ, N_PROBES, N_LISTS, list_pad, DIM,
        jnp.dtype(dtype).itemsize, K, 0, V5E_BYTES_LIMIT // 4)
    assert why == "list_kernel" and plan.n_super == 1
    n_g = pk.list_scan_groups(list_pad)
    prec = ivf_pq.contraction_precision(dtype, jnp.float32)
    _compile(lambda bl, n, r, c, ld, rt: pk.list_scan(
        bl, n, r, c, ld, rt, l2=True, precision=prec),
        sds((plan.n_blocks,), jnp.int32), sds((1,), jnp.int32),
        sds((plan.n_blocks, plan.block_rows, DIM), jnp.float32),
        sds((N_LISTS, DIM), jnp.float32),
        sds((N_LISTS, list_pad, DIM), dtype),
        sds((N_LISTS, n_g, pk.SCAN_GROUP), jnp.float32))


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


#: compiled temp a chip of the deep cell's program with XLA's tile
#: producer (described-v5e compile before the group kernel)
DEEP_XLA_TILE_TEMP = 1_756_326_400


def _assert_no_large_copy(txt: str, collection, limit: int):
    """No ``copy`` or ``transpose`` in the optimized HLO ``txt`` writes
    the collection (``collection`` [rows, dim], either way round) or any
    f32 array of ``limit`` elements or more."""
    import re

    for ln in txt.splitlines():
        op = re.match(r"\s*(?:ROOT )?%\S+ = .*? (copy|copy-start|transpose)\(",
                      ln)
        if not op:
            continue
        for dims in re.findall(r"f32\[([\d,]+)\]", ln.split(op.group(1))[0]):
            shape = [int(d) for d in dims.split(",")]
            assert shape not in (list(collection), list(collection)[::-1]), \
                ln[:200]
            assert np.prod(shape) < limit, ln[:200]


def _kernel_vmem_fits(txt: str, q_tile: int, dim: int, plan):
    """Every Mosaic kernel of ``txt`` takes no more scoped VMEM than the
    terms the step planner solved with give for its step."""
    import re

    kernels = [ln for ln in txt.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels, "no group kernel in the program"
    t = pk.group_scan_vmem_terms(q_tile, dim, plan.lanes_rows)
    rows, q = plan.gb * 128, pk._sublanes(q_tile)
    planned = (t["outer_bytes"] * rows + t["inner_bytes"] * q
               + t["cell_bytes"] * rows * q)
    assert planned <= pk.DEFAULT_VMEM_BUDGET
    for ln in kernels:
        used = re.search(r'"used_scoped_memory_configs":\[\{[^}]*"size":'
                         r'"(\d+)"', ln)
        assert used and int(used.group(1)) <= planned, ln[:200]


def _gathers(txt: str):
    """(output shape, in a loop body) of each gather of the optimized HLO
    ``txt`` that XLA runs as an op of its own: a ``gather``, or a fusion
    of one, outside the fusions' bodies."""
    import re

    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", txt))
    out, body = [], None
    for ln in txt.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", ln)
        if head:
            body = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%\S+ = [fs]32\[([\d,]*)\]\S* "
                     r"(gather|fusion)\(", ln)
        if m and body not in fused and re.search(
                r'op_name="[^"]*/gather"', ln) and (
                m.group(2) == "gather" or "kind=kCustom" in ln):
            out.append(([int(d) for d in m.group(1).split(",")],
                        "/while/body/" in ln))
    return out


def test_sharded_knn_group_kernel_at_deep100m_shard(topo):
    """The deep100m-exact cell's program as a v5e compiles it, with each
    distance tile and its group minima made by the group kernel: the
    kernel is there, within its planned VMEM; no copy holds a
    [1000, ~208k] distance tile (the tile is written groups-major and the
    gather reads it as a bitcast) or the f32[3125000, 96] shard (the
    kernel reads its [96, rows] view, a bitcast of the v5e's layout of a
    96-wide array); and the temp is no more than with XLA's tile. The
    group merge gathers no minimum or first row one element at a time
    (they ride through its sorts): the q·k = 100,000-element gathers left
    are the final top-k's ids and the select's, outside the tile loop,
    and the loop gathers only the kept groups' rows."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu import Resources
    from raft_tpu.neighbors import brute_force
    from raft_tpu.obs import explain as obs_explain
    from raft_tpu.parallel import comms as comms_mod
    from raft_tpu.parallel import sharded

    comms = comms_mod.init_comms(list(topo.devices), axis="data")
    q = jax.ShapeDtypeStruct((1000, 96), jnp.float32,
                             sharding=NamedSharding(comms.mesh, P()))
    x = jax.ShapeDtypeStruct((12_500_000, 96), jnp.float32,
                             sharding=NamedSharding(comms.mesh,
                                                    P("data", None)))
    res = Resources(workspace_limit_bytes=int(V5E_BYTES_LIMIT * 0.25))
    with obs_explain.capture() as cap:
        compiled = jax.jit(lambda q, x: sharded.knn(comms, q, x, 100,
                                                    res=res)
                           ).lower(q, x).compile()
    sharded.plan_cache_clear()
    (rec,) = [r for r in cap.records if r.family == "brute_force_group_scan"]
    assert (rec.engine, rec.reason) == ("pallas", "group_kernel")
    assert rec.plan["rows_on_lanes"] and not rec.plan["interpret"]
    plan = brute_force.GroupScan("pallas", "group_kernel",
                                 rec.plan["rows_per_step"] // 128, True)
    assert plan.gb == 4
    txt = compiled.as_text()
    _kernel_vmem_fits(txt, 1000, 96, plan)
    _assert_no_large_copy(txt, (3_125_000, 96), 1000 * 200_000)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= DEEP_XLA_TILE_TEMP, mem.temp_size_in_bytes
    gathers = _gathers(txt)
    elements = [loop for shape, loop in gathers if shape == [100_000]]
    assert not any(elements) and len(elements) <= 2, gathers
    assert sorted(shape for shape, loop in gathers if loop) == [
        [100_000, 128]] * 2, gathers


@pytest.mark.parametrize("nq,n,dim,k,lanes_rows", [
    (1000, 1_000_000, 128, 100, False),
    (64, 1_000_000, 128, 10, False),
    (64, 1_000_000, 96, 10, True),
    (64, 500_000, 768, 10, False),
    (64, 500_000, 960, 10, True),
], ids=["sift_1000q", "sift_64q", "deep_64q", "768d_64q", "960d_64q"])
def test_exact_scan_group_kernel_reads_collection_in_place(
        topo, nq, n, dim, k, lanes_rows):
    """The single-chip exact scan with the group kernel, as a v5e compiles
    it at the width of each collection: the kernel reads the collection in
    the layout the chip gives it — the [dim, rows] view where that is a
    bitcast (``pk.rows_on_lanes``: rows on the lanes), [rows, dim] blocks
    where it is not (SIFT's 128, 768) — so no copy or transpose of the
    collection, nor of a distance tile, is made; up to 128 wide, each
    kernel fits its planned VMEM."""
    from jax.sharding import SingleDeviceSharding

    from raft_tpu.neighbors import brute_force
    from raft_tpu.ops.distance import DistanceType

    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    budget = int(V5E_BYTES_LIMIT * 0.25)
    q_tile, db_tile = brute_force.choose_tiles(nq, n, dim, k, budget)
    plan = brute_force.plan_group_scan(DistanceType.L2Expanded, jnp.float32,
                                       dev, q_tile, n, db_tile, dim, k)
    assert plan.producer == "pallas" and plan.lanes_rows == lanes_rows
    compiled = jax.jit(lambda q, x, xn: brute_force.knn_core(
        q, x, xn, jnp.zeros((0,), jnp.uint32), DistanceType.L2Expanded, 2.0,
        k, q_tile, db_tile, budget, group_scan=plan)).lower(
        *(jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
          for s in ((nq, dim), (n, dim), (n,)))).compile()
    txt = compiled.as_text()
    if dim <= 128:  # wider rows beside 64 queries: the model is 1–3% under
        _kernel_vmem_fits(txt, q_tile, dim, plan)
    _assert_no_large_copy(txt, (n, dim), 50_000_000)


@pytest.mark.parametrize("dim", [96, 100, 127, 128, 129, 256, 768, 960])
def test_rows_on_lanes_is_the_v5e_parameter_layout(topo, dim):
    """``pk.rows_on_lanes`` reads the layout the v5e compiler gives a
    [rows, dim] float32 argument: rows on the lanes (``{0,1}``) where that
    pads less than rows-major."""
    import re

    from jax.sharding import SingleDeviceSharding

    dev = topo.devices[0]
    x = jax.ShapeDtypeStruct((1_000_000, dim), jnp.float32,
                             sharding=SingleDeviceSharding(dev))
    txt = jax.jit(lambda x: x.sum(0)).lower(x).compile().as_text()
    layout = re.search(r"entry_computation_layout=\{\(f32\[[\d,]+\]\{([\d,]+)",
                       txt).group(1)
    assert pk.rows_on_lanes(dev, jnp.float32, (1_000_000, dim)) == (
        layout == "0,1"), layout
