"""True multi-controller (2-process × 4-device) distributed tests.

Reference analog: raft-dask's multi-worker Comms bootstrap + per-worker
builds (raft_dask/common/comms.py:138-173, test_comms.py on a
LocalCUDACluster). Here each process is a jax.distributed controller owning
4 virtual CPU devices; ``init_distributed`` plays the NCCL-uniqueId
rendezvous role and ``build_ivf_pq_from_file`` builds only the shards whose
devices are process-local (per-process row spans of the shared fbin file).
"""

import os
import pathlib
import socket
import subprocess
import sys

import numpy as np
import pytest

_REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])

_WORKER = r"""
import os, sys
pid = int(sys.argv[1])
port = sys.argv[2]
fbin_path = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from raft_tpu import Resources, native
from raft_tpu.neighbors import brute_force, ivf_pq
from raft_tpu.parallel import comms as cm, sharded
from raft_tpu.stats import neighborhood_recall

comms = cm.init_distributed(f"localhost:{port}", 2, pid)
assert jax.process_count() == 2
assert comms.size == 8, comms.size

# count the shards this process actually builds (4 of 8)
built = []
orig = sharded._map_shards
def counting_map(c, fn, res, **kw):
    out = orig(c, fn, res, **kw)
    built.extend(out.keys())
    return out
sharded._map_shards = counting_map

idx = sharded.build_ivf_pq_from_file(
    comms, fbin_path,
    ivf_pq.IndexParams(n_lists=4, pq_dim=8, kmeans_n_iters=3),
    res=Resources(seed=2), batch_rows=400, scan_mode="lut")
print(f"P{pid} LOCAL_BUILDS {sorted(built)}", flush=True)

db = native.read_bin(fbin_path)
rng = np.random.default_rng(11)
q = rng.standard_normal((20, db.shape[1])).astype(np.float32)
d, i = sharded.search_ivf_pq(idx, q, 10, ivf_pq.SearchParams(n_probes=4))
i = np.asarray(i)
assert i.shape == (20, 10)
assert (i >= -1).all() and (i < len(db)).all()
_, gt = brute_force.knn(q, db, k=10, metric="sqeuclidean")
rec = float(neighborhood_recall(i, np.asarray(gt)))
print(f"P{pid} RECALL {rec:.4f}", flush=True)
assert rec >= 0.6, rec
print(f"P{pid} OK", flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_distributed_build_and_search(tmp_path):
    from raft_tpu import native

    rng = np.random.default_rng(7)
    db = rng.standard_normal((1600, 16)).astype(np.float32)
    fbin = str(tmp_path / "base.fbin")
    native.write_bin(fbin, db)
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    port = _free_port()

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = _REPO_ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), str(port), fbin],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=_REPO_ROOT)
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=900)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-4000:]}"
        assert f"P{pid} OK" in out, out[-4000:]
    joined = "\n".join(outs)
    # each controller built exactly its 4 local shards
    assert "P0 LOCAL_BUILDS [0, 1, 2, 3]" in joined, joined[-4000:]
    assert "P1 LOCAL_BUILDS [4, 5, 6, 7]" in joined, joined[-4000:]
