"""The rest of a run, with the timed path broken underneath: ``correct``
comes out false for each fault a cell can have. (No cell keeps state
between calls, so "a step that returns its state unchanged" has no
counterpart here.)"""

import os
from concurrent.futures import Future

import pytest

from benchmark import controls, harness
from conftest import run_tiny


def _system(root, name):
    return harness.load_module("systems", name,
                               os.path.join(root, "benchmark")).System


@pytest.mark.parametrize("fault", ["half", "altered"])
@pytest.mark.parametrize("workload,system,n_dev", [
    ("tiny-ivfpq.batch", "ivf_pq", 1),
    ("tiny-exact.batch", "sharded_knn", 4)])
def test_batch_faults(copy_root, workload, system, n_dev, fault):
    """Half of every batch answered with the other half's rows; one id
    of every batch altered (controls.faulty, which the chip runs use)."""
    out = run_tiny(copy_root, workload, seconds=0.3, n_devices=n_dev,
                   system_factory=controls.faulty(
                       _system(copy_root, system), fault))
    assert not out["correct"], out["check"]


def test_exact_exchange_left_out(copy_root):
    """No merge: the answer is the first chip's local top-k alone."""
    real = _system(copy_root, "sharded_knn")
    ref = harness.load_module("references", "exact_knn",
                              os.path.join(copy_root, "benchmark"))

    class NoExchange(real):
        def __init__(self, cfg, shards, devices, annotate):
            super().__init__(cfg, shards, devices, annotate)
            self.first = shards[:1]

        def stage(self, queries, batch):
            self.host = [queries[j * batch:(j + 1) * batch]
                         for j in range(len(queries) // batch)]
            return super().stage(queries, batch)

        def call(self, j):
            v, i, _ = ref.knn(self.first, self.host[j], self.k)
            return v, i

    out = run_tiny(copy_root, "tiny-exact.batch", seconds=0.3, n_devices=4,
                   system_factory=NoExchange)
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("fault", ["altered", "split"])
def test_ivfpq_served_faults(copy_root, fault):
    """Through serving.Engine: one answer altered, or a batch split so
    that every second rider gets its neighbour's answer."""
    real = _system(copy_root, "ivf_pq")

    class Broken(real):
        def serve(self, span_sink):
            submit = super().serve(span_sink)
            state = {"n": 0, "prev": None}

            def broken(q, k):
                fut = submit(q, k)
                out = Future()
                out.set_running_or_notify_cancel()
                d, i = fut.result(timeout=60)
                d, i = d.copy(), i.copy()
                state["n"] += 1
                if fault == "altered" and state["n"] % 5 == 0:
                    i[0] = (i[0] + 1) % 4096
                if fault == "split":
                    if state["n"] % 2 == 0 and state["prev"] is not None:
                        d, i = state["prev"]
                    state["prev"] = (d, i)
                out.set_result((d, i))
                return out
            return broken

    out = run_tiny(copy_root, "tiny-ivfpq.served", seconds=1.0,
                   system_factory=Broken)
    assert not out["correct"], out["check"]
