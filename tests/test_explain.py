"""Execution-plan attribution (docs/observability.md "Query explain").

The contract under test: every family ``search()`` resolves to exactly
one reason-coded :class:`~raft_tpu.obs.explain.ExplainRecord`, the
record never perturbs the answer (bit-identity against the plain call),
the ``raft_tpu_dispatch_total`` counter reconciles with what actually
ran (zero ``unknown``-reason increments, ever), and the TPU no-verdict
warning fires exactly once per process."""

import logging

import jax
import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.obs import explain as obs_explain
from raft_tpu.obs import metrics as obm
from raft_tpu.ops import pallas_kernels as pk
from raft_tpu.ops.select_k import select_k_plan

pytestmark = pytest.mark.fast

DIM = 24
K = 5
N = 600


@pytest.fixture(scope="module")
def db():
    rng = np.random.default_rng(11)
    return rng.standard_normal((N, DIM)).astype(np.float32)


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(12)
    return rng.standard_normal((4, DIM)).astype(np.float32)


# ------------------------------------------------------- record plumbing

def test_record_dispatch_rejects_unvocabularied_reason():
    with pytest.raises(ValueError, match="reason"):
        obs_explain.record_dispatch("brute_force", "auto", "xla",
                                    "because_i_said_so")


def test_capture_stack_nests_and_isolates():
    with obs_explain.capture() as outer:
        obs_explain.record_dispatch("brute_force", "auto", "xla", "forced")
        with obs_explain.capture() as inner:
            obs_explain.record_dispatch("ivf_flat", "auto", "xla",
                                        "forced")
        # nested scope sees only its own record; outer sees both
        assert [r.family for r in inner.records] == ["ivf_flat"]
        assert [r.family for r in outer.records] == ["brute_force",
                                                     "ivf_flat"]
        assert outer.last.family == "ivf_flat"
    # no open capture: recording still counts, just lands nowhere
    rec = obs_explain.record_dispatch("cagra", "auto", "xla",
                                      "only_engine")
    assert rec.brief()["reason"] == "only_engine"


def test_record_serializes_and_briefs():
    rec = obs_explain.record_dispatch(
        "ivf_pq", "auto", "cache", "tpu_absent",
        params={"k": 10}, plan={"q_tile": 64})
    d = rec.to_dict()
    assert d["family"] == "ivf_pq" and d["plan"]["q_tile"] == 64
    assert set(rec.brief()) == {"family", "requested", "engine", "reason"}


# --------------------------------------- family parity + counter hygiene

def _build_family(family, db):
    if family == "brute_force":
        return brute_force.build(db)
    if family == "ivf_flat":
        return ivf_flat.build(db, ivf_flat.IndexParams(n_lists=8))
    if family == "ivf_pq":
        return ivf_pq.build(db, ivf_pq.IndexParams(n_lists=8, pq_dim=8))
    return cagra.build(db, cagra.IndexParams(graph_degree=8))


def _search_family(family, idx, queries, explain):
    if family == "brute_force":
        return brute_force.search(idx, queries, K, explain=explain)
    if family == "ivf_flat":
        return ivf_flat.search(idx, queries, K,
                               ivf_flat.SearchParams(n_probes=4),
                               explain=explain)
    if family == "ivf_pq":
        return ivf_pq.search(idx, queries, K,
                             ivf_pq.SearchParams(n_probes=4),
                             explain=explain)
    return cagra.search(idx, queries, K, explain=explain)


@pytest.mark.parametrize("family", ["brute_force", "ivf_flat", "ivf_pq",
                                    "cagra"])
def test_explain_bit_identical_and_reason_coded(family, db, queries):
    idx = _build_family(family, db)
    before = obs_explain.dispatch_counts()
    v0, i0 = _search_family(family, idx, queries, explain=False)
    v1, i1, rec = _search_family(family, idx, queries, explain=True)
    # the attribution is an observer: the answer is bit-identical
    assert np.array_equal(np.asarray(v0), np.asarray(v1))
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    assert rec.family == family
    assert rec.reason in obs_explain.REASONS
    assert rec.reason != "unknown"
    assert rec.params["k"] == K and rec.params["nq"] == 4
    # every dispatch lands on the counter — two searches, two counts
    after = obs_explain.dispatch_counts()
    key = (family, rec.engine, rec.reason)
    assert after[key] - before.get(key, 0) == 2
    # zero unknown-reason increments, ever (the schema escape hatch is
    # for readers of foreign artifacts, never for this codebase to emit)
    assert not any(k[2] == "unknown" for k in after)


def test_explain_returns_plan_tiles_on_xla_paths(db, queries):
    _, _, rec = _search_family("ivf_flat", _build_family("ivf_flat", db),
                               queries, explain=True)
    if rec.engine == "xla":  # the CPU-CI resolution
        assert rec.reason == "tpu_absent"
        assert rec.plan["predicted_workspace_bytes"] > 0
        assert rec.plan["q_tile"] >= 1
    # select_k resolution rides as notes at TRACE time only — force a
    # retrace so the note lands regardless of jit-cache state
    jax.clear_caches()
    _, _, rec = _search_family("ivf_flat", _build_family("ivf_flat", db),
                               queries, explain=True)
    assert any(n.get("op") == "select_k" for n in rec.notes)


def test_select_k_plan_matches_note(db, queries):
    jax.clear_caches()  # notes are captured at trace time (see above)
    _, _, rec = _search_family("brute_force",
                               _build_family("brute_force", db),
                               queries, explain=True)
    notes = [n for n in rec.notes if n.get("op") == "select_k"]
    assert notes, "brute_force search resolved no select_k"
    # the dry-run planner surface agrees with what the search recorded
    note = notes[0]
    plan = select_k_plan(note["n"], note["k"])
    assert plan["algo"] == note["algo"]
    assert plan["k_pad"] == note["k_pad"]


def test_forced_scan_mode_reasons(db, queries):
    idx = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=8, pq_dim=8))
    _, _, rec = ivf_pq.search(
        idx, queries, K, ivf_pq.SearchParams(n_probes=4, scan_mode="lut"),
        explain=True)
    assert rec.engine == "lut" and rec.reason == "forced"
    assert rec.plan["memory_model"] == "lut"
    assert rec.plan["memory_auto"] is False


# ------------------------------------------------ auto on a TPU backend

@pytest.mark.parametrize("family", ["brute_force", "ivf_flat", "ivf_pq",
                                    "cagra"])
def test_auto_stays_on_xla_on_tpu(family, monkeypatch, caplog):
    # no fused kernel has a chip measurement beating XLA: auto routes
    # XLA on a TPU backend with reason fused_unmeasured, and logs nothing
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    with caplog.at_level(logging.DEBUG,
                         logger="raft_tpu.ops.pallas_kernels"):
        assert pk.fused_dispatch_explained(family, "auto") == (
            False, False, "fused_unmeasured")
    assert not caplog.records, [r.getMessage() for r in caplog.records]


def test_merge_auto_is_tree_on_tpu(monkeypatch):
    from raft_tpu.parallel import sharded

    monkeypatch.setattr(sharded.jax, "default_backend", lambda: "tpu")
    assert sharded.merge_dispatch_explained("auto", 4) == (
        "tree", "merge_tree", "")
    # the ring kernel runs only when asked for
    assert sharded.merge_dispatch_explained("ring", 4) == (
        "ring", "forced", "pallas")


def test_dispatch_counts_reads_custom_registry():
    reg = obm.Registry()
    ctr = reg.counter("raft_tpu_dispatch_total", "test",
                      ("family", "engine", "reason"))
    ctr.labels("brute_force", "xla", "tpu_absent").inc(3)
    counts = obs_explain.dispatch_counts(registry=reg)
    assert counts == {("brute_force", "xla", "tpu_absent"): 3}
