"""Chaos tests: inject real faults (byte flips, lost files, severed
sockets, shrunken memory budgets) and require the serving stack to either
degrade gracefully or fail with a typed, actionable error — never hang,
never serve silently-wrong results. Runs entirely on the 8-device virtual
CPU mesh."""

import shutil
import socket
import struct
import threading
import time

import numpy as np
import pytest

from raft_tpu.core.errors import IntegrityError
from raft_tpu.core.resources import Resources
from raft_tpu.neighbors import ivf_flat, ivf_pq
from raft_tpu.parallel import comms as comms_mod
from raft_tpu.parallel import sharded
from raft_tpu.parallel.host_p2p import _HDR, _MAGIC, HostP2P
from raft_tpu.testing import faults

N_ROWS, DIM, N_SHARDS = 4096, 32, 8


@pytest.fixture(scope="module")
def pq_checkpoint(tmp_path_factory):
    """One sharded IVF-PQ build + checkpoint, copied per test before any
    fault is injected (rows split 512/shard, so losing one shard is
    exactly 1/8 of coverage)."""
    rng = np.random.default_rng(7)
    centers = (rng.standard_normal((32, DIM)) * 4).astype(np.float32)
    x = (centers[rng.integers(0, 32, N_ROWS)]
         + rng.standard_normal((N_ROWS, DIM))).astype(np.float32)
    q = (centers[rng.integers(0, 32, 16)]
         + rng.standard_normal((16, DIM))).astype(np.float32)
    comms = comms_mod.init_comms(axis="faults_pq")
    idx = sharded.build_ivf_pq(
        comms, x, ivf_pq.IndexParams(n_lists=16, pq_dim=16,
                                     kmeans_n_iters=3),
        res=Resources(seed=0), scan_mode="lut")
    d = tmp_path_factory.mktemp("pq_ckpt")
    sharded.serialize_ivf_pq(idx, str(d / "idx"))
    return d, q


@pytest.fixture()
def pq_prefix(pq_checkpoint, tmp_path):
    src, q = pq_checkpoint
    for p in src.iterdir():
        shutil.copy(p, tmp_path / p.name)
    return str(tmp_path / "idx"), q


def _elastic_subset(el, ranks):
    """An ElasticIvfPq over a hand-picked subset of a FULL restore's
    shards — the ground truth a degraded restore must match bit-for-bit."""
    sel = np.asarray(ranks)

    def tk(a):
        return None if a is None else np.asarray(a)[sel]

    return sharded.ElasticIvfPq(
        len(ranks), tk(el.centers), tk(el.rotation), tk(el.list_indices),
        tk(el.list_sizes), el.metric, el.n_rows,
        list_decoded=tk(el.list_decoded),
        decoded_norms=tk(el.decoded_norms), codebooks=tk(el.codebooks),
        list_codes=tk(el.list_codes), per_cluster=el.per_cluster,
        pq_dim=el.pq_dim, pq_bits=el.pq_bits,
        overflow_decoded=tk(el.overflow_decoded),
        overflow_norms=tk(el.overflow_norms),
        overflow_indices=tk(el.overflow_indices))


# --------------------------------------------------- checkpoint integrity


def test_delete_rank_degraded_restore(pq_prefix):
    """Acceptance (a): losing 1 of 8 rank files -> allow_partial restore
    with coverage exactly 7/8, searching only surviving shards
    bit-identically to a full restore restricted to the same shards;
    strict restore names the missing path."""
    prefix, q = pq_prefix
    el_full = sharded.deserialize_ivf_pq_elastic(prefix)
    assert el_full.coverage == 1.0

    dead = 3
    gone = faults.delete_rank_file(prefix, dead)
    with pytest.raises(ValueError, match=r"missing \[3\]") as ei:
        sharded.deserialize_ivf_pq_elastic(prefix)
    assert f"idx.rank{dead}" in str(ei.value)

    el = sharded.deserialize_ivf_pq_elastic(prefix, allow_partial=True)
    assert el.coverage == (N_SHARDS - 1) / N_SHARDS
    assert el.n_shards == N_SHARDS - 1
    assert el.shard_ranks == [r for r in range(N_SHARDS) if r != dead]

    sp = ivf_pq.SearchParams(n_probes=8)
    result = el.search(q, 10, sp)
    d1, i1 = result  # still unpacks as a 2-tuple
    assert result.coverage == el.coverage

    # bit-identity vs the full restore restricted to the same shards
    d2, i2 = _elastic_subset(el_full, el.shard_ranks).search(q, 10, sp)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    # no id from the dead shard's rows (rows split evenly -> contiguous)
    ids = np.asarray(i1)
    lo, hi = dead * (N_ROWS // N_SHARDS), (dead + 1) * (N_ROWS // N_SHARDS)
    assert not np.any((ids >= lo) & (ids < hi)), gone


def test_flip_byte_typed_integrity_error(pq_prefix):
    """Acceptance (b): one flipped payload byte -> IntegrityError naming
    the file and the record; degraded restore routes around it."""
    prefix, q = pq_prefix
    bad = f"{prefix}.rank2"
    # record 6 is past the header scalars, inside the field payloads
    faults.flip_record_byte(bad, 6, offset=5)
    with pytest.raises(IntegrityError) as ei:
        sharded.deserialize_ivf_pq_elastic(prefix)
    assert ei.value.reason == "corrupt"
    assert ei.value.path == bad
    assert ei.value.record == 6

    el = sharded.deserialize_ivf_pq_elastic(prefix, allow_partial=True)
    assert el.coverage == (N_SHARDS - 1) / N_SHARDS
    assert 2 not in el.shard_ranks
    d, i = el.search(q, 10, ivf_pq.SearchParams(n_probes=8))
    assert np.asarray(i).shape == (len(q), 10)


def test_truncated_rank_file(pq_prefix):
    prefix, _ = pq_prefix
    bad = f"{prefix}.rank5"
    faults.truncate_record(bad, 4)
    with pytest.raises(IntegrityError) as ei:
        sharded.deserialize_ivf_pq_elastic(prefix)
    assert ei.value.reason == "truncated"
    assert ei.value.path == bad
    el = sharded.deserialize_ivf_pq_elastic(prefix, allow_partial=True)
    assert 5 not in el.shard_ranks


def test_footer_detects_silent_tail_truncation(pq_prefix):
    """Cutting the footer off (no record torn) must still read as
    truncated — a file can otherwise lose its tail records silently."""
    prefix, _ = pq_prefix
    bad = f"{prefix}.rank0"
    faults.truncate_file(bad, drop_bytes=4)
    with pytest.raises(IntegrityError) as ei:
        sharded.deserialize_ivf_pq_elastic(prefix)
    assert ei.value.reason == "truncated"


def test_verify_checkpoint_classifies(pq_prefix):
    """The pre-flight tool (TPU runbook) classifies every fault class
    without reading payloads into memory."""
    prefix, _ = pq_prefix
    rep = sharded.verify_checkpoint(prefix)
    assert rep["ok"] and not rep["missing_ranks"]
    assert rep["size"] == N_SHARDS
    assert all(s == "ok" for s in rep["files"].values())

    faults.delete_rank_file(prefix, 0)
    faults.truncate_record(f"{prefix}.rank1", 3)
    faults.flip_record_byte(f"{prefix}.rank2", 2)
    rep = sharded.verify_checkpoint(prefix)
    assert not rep["ok"]
    assert rep["files"]["idx.rank0"] == "missing"
    assert rep["files"]["idx.rank1"] == "truncated"
    assert rep["files"]["idx.rank2"] == "corrupt"
    assert rep["missing_ranks"] == [0, 1, 2]
    assert rep["coverage_ranks"] == [3, 4, 5, 6, 7]


def test_ivf_flat_elastic_degraded(tmp_path):
    """The IVF-Flat twin: same delete-one-shard contract."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal((N_ROWS, 16)).astype(np.float32)
    q = x[:8] + 0.01 * rng.standard_normal((8, 16)).astype(np.float32)
    comms = comms_mod.init_comms(axis="faults_flat")
    idx = sharded.build_ivf_flat(
        comms, x, ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=2),
        res=Resources(seed=0))
    prefix = str(tmp_path / "flat")
    sharded.serialize_ivf_flat(idx, prefix)

    el_full = sharded.deserialize_ivf_flat_elastic(prefix)
    assert el_full.coverage == 1.0
    d0, i0 = el_full.search(q, 10, ivf_flat.SearchParams(n_probes=16))
    faults.delete_rank_file(prefix, 6)
    with pytest.raises(ValueError, match=r"missing \[6\]"):
        sharded.deserialize_ivf_flat_elastic(prefix)
    el = sharded.deserialize_ivf_flat_elastic(prefix, allow_partial=True)
    assert el.coverage == (N_SHARDS - 1) / N_SHARDS
    res = el.search(q, 10, ivf_flat.SearchParams(n_probes=16))
    assert res.coverage == el.coverage
    ids = np.asarray(res.indices)
    lo, hi = 6 * (N_ROWS // N_SHARDS), 7 * (N_ROWS // N_SHARDS)
    assert not np.any((ids >= lo) & (ids < hi))
    # every result that did not come from the dead shard is unchanged
    keep = ~((np.asarray(i0) >= lo) & (np.asarray(i0) < hi))
    assert np.all(np.isin(np.asarray(i0)[keep], np.asarray(res.indices)))


# ------------------------------------------------------- host p2p faults


def _ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def test_sever_mid_stream_send_retries():
    """Acceptance (c): cut the live connection between two sends — the
    sender's retry/backoff re-delivers and waitall completes."""
    ports = _ports(2)
    peers = [("127.0.0.1", p) for p in ports]
    a = HostP2P(0, 2, peers=peers, timeout=30,
                retries=5, retry_backoff=0.02, retry_backoff_max=0.1)
    b = HostP2P(1, 2, peers=peers, timeout=30)
    try:
        a.isend(b"first", dest=1).wait(30)
        assert b.irecv(source=0).wait(30) == b"first"
        assert faults.sever_connection(a, 1)  # hard-cut the live socket
        reqs = [a.isend(f"m{i}".encode(), dest=1, tag=1) for i in range(4)]
        HostP2P.waitall(reqs, timeout=30)  # completes via retry, no poison
        got = [b.irecv(source=0, tag=1).wait(30) for _ in range(4)]
        # at-least-once: retry may duplicate the frame in flight when the
        # cut landed post-buffer; order within the stream is preserved
        assert got[0] == b"m0" and set(got) <= {b"m0", b"m1", b"m2", b"m3"}
    finally:
        a.close()
        b.close()


def test_retries_zero_restores_fail_fast():
    """retries=0 keeps the original poison-on-first-failure contract."""
    ports = _ports(2)
    peers = [("127.0.0.1", p) for p in ports]
    a = HostP2P(0, 2, peers=peers, timeout=5, retries=0)
    try:
        with pytest.raises(OSError):
            a.isend(b"x", dest=1).wait(10)  # nothing listens on port 1
        with pytest.raises(ConnectionError, match="poisoned"):
            a.isend(b"y", dest=1).wait(10)
    finally:
        a.close()


def test_unreachable_peer_wait_bounded():
    """Acceptance (c): wait(timeout=t) against an unreachable peer raises
    TimeoutError within 2t — for sends still retrying AND for receives
    whose message can never come; wait() with no timeout uses the
    endpoint's deadline instead of hanging."""
    ports = _ports(2)
    peers = [("127.0.0.1", p) for p in ports]
    a = HostP2P(0, 2, peers=peers, timeout=0.8,
                retries=1000, retry_backoff=0.2, retry_backoff_max=0.2)
    try:
        t = 1.0
        s = a.isend(b"x", dest=1)  # port 1 refuses; send keeps retrying
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            s.wait(timeout=t)
        assert time.monotonic() - t0 < 2 * t

        r = a.irecv(source=1)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            r.wait(timeout=t)
        assert time.monotonic() - t0 < 2 * t

        r2 = a.irecv(source=1)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            r2.wait()  # no explicit timeout: endpoint timeout applies
        assert time.monotonic() - t0 < 2 * 0.8
    finally:
        a.close()


def test_peer_death_fails_pending_irecvs():
    """A connection cut MID-FRAME with no re-delivery within peer_grace
    fails every pending irecv from that source with ConnectionError —
    promptly, not after the full endpoint timeout."""
    ports = _ports(2)
    peers = [("127.0.0.1", p) for p in ports]
    ep = HostP2P(0, 2, peers=peers, timeout=60, peer_grace=0.3)
    try:
        raw = socket.create_connection(peers[0], timeout=5)
        # one whole frame first: establishes src=1 and bumps its
        # delivery generation
        payload = b"hello"
        raw.sendall(_HDR.pack(_MAGIC, 1, 0, len(payload)))
        raw.sendall(b"B")
        raw.sendall(payload)
        assert ep.irecv(source=1).wait(10) == b"hello"

        pending = [ep.irecv(source=1, tag=t) for t in (0, 1)]
        other_src = ep.irecv(source=0, tag=0)
        raw.sendall(_HDR.pack(_MAGIC, 1, 0, 999)[:7])  # cut mid-header
        raw.close()
        t0 = time.monotonic()
        for r in pending:
            with pytest.raises(ConnectionError, match="presumed dead"):
                r.wait(10)
        assert time.monotonic() - t0 < 5  # grace + slack, not timeout=60
        assert not other_src.done()  # unrelated source untouched
    finally:
        ep.close()


def test_reconnect_within_grace_voids_death_verdict():
    """A sender retry that reconnects inside the grace window proves the
    peer alive: pending irecvs must get the re-delivered message, not a
    death error."""
    ports = _ports(2)
    peers = [("127.0.0.1", p) for p in ports]
    ep = HostP2P(0, 2, peers=peers, timeout=60, peer_grace=0.5)
    try:
        raw = socket.create_connection(peers[0], timeout=5)
        raw.sendall(_HDR.pack(_MAGIC, 1, 0, 1))
        raw.sendall(b"B")
        raw.sendall(b"a")
        assert ep.irecv(source=1).wait(10) == b"a"
        pending = ep.irecv(source=1)
        raw.sendall(_HDR.pack(_MAGIC, 1, 0, 999)[:5])  # abnormal cut
        raw.close()
        # "retry": a fresh connection delivering within the grace window
        raw2 = socket.create_connection(peers[0], timeout=5)
        raw2.sendall(_HDR.pack(_MAGIC, 1, 0, 5))
        raw2.sendall(b"B")
        raw2.sendall(b"again")
        assert pending.wait(10) == b"again"
        time.sleep(0.8)  # outlive the grace timer: verdict must be void
        late = ep.irecv(source=1)
        raw2.sendall(_HDR.pack(_MAGIC, 1, 0, 4))
        raw2.sendall(b"B")
        raw2.sendall(b"more")
        assert late.wait(10) == b"more"
        raw2.close()
    finally:
        ep.close()


def test_mark_peer_dead_short_circuits():
    ports = _ports(2)
    peers = [("127.0.0.1", p) for p in ports]
    ep = HostP2P(0, 2, peers=peers, timeout=60)
    try:
        r = ep.irecv(source=1)
        ep.mark_peer_dead(1)
        with pytest.raises(ConnectionError, match="marked dead"):
            r.wait(5)
    finally:
        ep.close()


# -------------------------------------------------- build cancellation


def test_map_shards_cancels_siblings_on_failure(monkeypatch):
    """First shard-build failure cancels the siblings via
    core.interruptible instead of letting them run to completion."""
    from raft_tpu.core import interruptible

    monkeypatch.setenv("RAFT_TPU_PARALLEL_BUILD", "1")
    comms = comms_mod.init_comms(axis="faults_cancel")
    state = {"cancelled": 0, "completed": 0}
    lock = threading.Lock()

    def one(r, shard_res):
        if r == 0:
            return r  # the (serial) warm-up shard: instant
        if r == 1:
            time.sleep(0.2)  # let siblings enter their loops
            raise RuntimeError("shard build exploded")
        try:
            for _ in range(200):  # ~10s if never cancelled
                interruptible.yield_now()
                time.sleep(0.05)
        except interruptible.InterruptedException:
            with lock:
                state["cancelled"] += 1
            raise
        with lock:
            state["completed"] += 1
        return r

    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="shard build exploded"):
        # uniform spans -> exactly one (instant) warm-up shard, rank 0
        sharded._map_shards(comms, one, Resources(seed=0),
                            spans=[1] * comms.size)
    elapsed = time.monotonic() - t0
    # warm-up ranks (serial, pre-failure) complete; the parallel siblings
    # get cancelled long before their 10s of sleeping finishes
    assert state["cancelled"] >= 1
    assert elapsed < 8.0, elapsed


# ----------------------------------------------------- memory pressure


def test_workspace_shrink_same_results():
    """A 1 MiB workspace budget forces the tiled paths; results must not
    change (acceptance: memory pressure degrades speed, never answers).
    Ids are compared exactly. Distances agree to a few ulp of the row's
    distance scale, not bitwise: XLA:CPU rounds the tiled LUT contraction
    in a shape-dependent order, and a near-zero ADC distance is a
    difference of ~30-sized terms, so its error is absolute (atol), not
    relative."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2048, DIM)).astype(np.float32)
    q = x[:16] + 0.01 * rng.standard_normal((16, DIM)).astype(np.float32)
    res = Resources(seed=0)
    idx = ivf_pq.build(x, ivf_pq.IndexParams(n_lists=16, pq_dim=16,
                                             kmeans_n_iters=3), res=res)
    # pin the engine: the budget may only change TILING, not numerics
    sp = ivf_pq.SearchParams(n_probes=8, scan_mode="lut")
    d0, i0 = ivf_pq.search(idx, q, 10, sp, res=res)
    with faults.shrink_workspace(res, 1 << 20):
        assert res.workspace_limit_bytes == 1 << 20
        d1, i1 = ivf_pq.search(idx, q, 10, sp, res=res)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6,
                               atol=1e-5)
