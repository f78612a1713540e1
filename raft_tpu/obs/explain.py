"""Execution-plan attribution: every search dispatch explains itself.

PR 9's ``scan_mode`` dispatch falls back from the fused Pallas engines
to XLA *silently* (docs/tuning.md fallback matrix) — correct by design,
invisible by accident: production traffic gave no signal whether the
fused hot path was even live. This module makes every dispatch decision
observable, three ways from one emission point:

- a structured :class:`ExplainRecord` — family, requested vs resolved
  engine, a reason code from the closed :data:`REASONS` vocabulary,
  planner tile choices and predicted workspace bytes, probe/bucket
  params;
- the ``raft_tpu_dispatch_total{family,engine,reason}`` counter family
  on the default registry, incremented once per public ``search()``
  call (the scrape-able reason histogram — r06's proof that fused
  routing actually flipped on);
- the thread-local :func:`capture` collector, which the serving engine
  wraps around each batch dispatch so the records ride the batch/request
  spans as ``explain`` breadcrumbs, and which ``search(...,
  explain=True)`` uses to hand the record back to the caller.

Layering: this module is registry-only (no jax, no neighbors import —
obs sits beside core). The neighbor families and ``ops/select_k`` call
:func:`record_dispatch` / :func:`note_select_k` at their dispatch
points; graftcheck rule R007 enforces that no silent-fallback branch
ships without one.

Counter semantics: family dispatch decisions happen in Python per
``search()`` call, so ``raft_tpu_dispatch_total`` reconciles 1:1 with
batch-level span breadcrumbs. ``select_k``'s AUTO resolution runs at
*trace time* inside jitted search bodies (once per compiled shape, not
per call), so it records into the active capture only — counting it
would alias the jit cache, not the traffic.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Iterator, List, Optional

from raft_tpu.obs import metrics as _metrics

__all__ = [
    "ExplainRecord",
    "REASONS",
    "capture",
    "record_dispatch",
    "note_select_k",
    "dispatch_counts",
]

#: The closed fallback-cause vocabulary (docs/observability.md "Explain
#: records"). Every dispatch emission MUST use one of these — the
#: reconciliation tests assert zero increments outside it (and zero
#: ``unknown``s, which exists only as the schema's escape hatch for
#: forward-compat readers, never as something the repo emits).
REASONS = frozenset({
    # engine chosen positively
    "forced",                  # scan_mode explicitly named this engine
    "interpret",               # RAFT_TPU_PALLAS_INTERPRET=1 parity hook
    "only_engine",             # family has a single engine (kept in the
                               # vocabulary for artifact replay; cagra —
                               # its last emitter — now has the fused
                               # Pallas beam engine and dispatches like
                               # the other fused families)
    # fused considered but routed to XLA
    "tpu_absent",              # pallas/auto on a host with no TPU backend
    "fused_unmeasured",        # auto on TPU: no chip measurement of the
                               # fused kernel beating XLA
    "non_l2",                  # metric outside the fused L2 matrix
    "filtered",                # bitset filter (no in-carry filter epilogue)
    "fast_scan",               # bf16 fast scan requested (fp32-only carry)
    "k_gt_1024",               # k above the VMEM top-k carry bound
    "non_float_dtype",         # integer dataset (no float carry)
    "lut_params_unsupported",  # fused-LUT regime needs pq_bits=8 etc.
    # exact group scan's tile producer (brute_force.plan_group_scan;
    # "tpu_absent"/"fast_scan" above are shared with it)
    "group_kernel",            # TPU: the Pallas kernel makes each tile
    "unsupported_metric",      # metric outside L2/L2Sqrt/inner product
    "not_float32",             # data or queries not float32
    "query_tile_vmem",         # the query tile cannot stay in VMEM
    # ivf_pq decoded-cache core (ivf_pq.plan_list_scan; "tpu_absent"
    # above is shared with it)
    "list_kernel",             # TPU: list-major, the Pallas list scan
    "short_lists",             # query-major: list_pad under 128 slots
    "list_vmem",               # query-major: a slab overflows the VMEM
    # sharded cross-chip merge dispatch (parallel/sharded.py merge_mode;
    # "forced" above is shared with the merge ladder)
    "merge_tree",              # auto: log₂S ppermute tree merge
    "merge_allgather",         # auto: non-power-of-two mesh fallback
    # deadline-aware adaptive planning (planner/adaptive.py choice
    # reasons — emitted with requested="adaptive", engine="planner";
    # also counted in raft_tpu_adaptive_choice_total{family,reason})
    "pareto_default",          # highest-recall frontier point fits
    "deadline_degraded",       # budget forced a lower-recall point
    "floor_clamped",           # recall floor stopped the degradation
    "no_frontier",             # no committed points: static params serve
    # schema escape hatch for readers; never emitted by this repo
    "unknown",
})

_DISPATCH = _metrics.REGISTRY.counter(
    "raft_tpu_dispatch_total",
    "Search dispatch decisions by family, resolved engine, and "
    "reason code (docs/observability.md reason vocabulary).",
    ("family", "engine", "reason"))


@dataclasses.dataclass
class ExplainRecord:
    """One dispatch decision, fully attributed.

    ``params`` carries the query-shape side (k, nq, n_probes, metric,
    bucket…); ``plan`` carries the planner side (tile choices, predicted
    workspace/VMEM bytes). Both are flat JSON-safe dicts so a record
    drops straight into a span or a JSONL line.
    """

    family: str      # "brute_force" | "ivf_flat" | "ivf_pq" | "cagra" | ...
    requested: str   # scan_mode as the caller asked ("auto", "pallas", ...)
    engine: str      # what actually ran: "pallas", "xla", "cache", ...
    reason: str      # a REASONS member: why `engine` was the resolution
    params: Dict[str, object] = dataclasses.field(default_factory=dict)
    plan: Dict[str, object] = dataclasses.field(default_factory=dict)
    #: trace-time sub-decisions (select_k AUTO resolution) observed while
    #: this record's search was the innermost active capture
    notes: List[dict] = dataclasses.field(default_factory=list)

    def brief(self) -> dict:
        """The span breadcrumb: just the attribution triple + request."""
        return {"family": self.family, "requested": self.requested,
                "engine": self.engine, "reason": self.reason}

    def to_dict(self) -> dict:
        return {"family": self.family, "requested": self.requested,
                "engine": self.engine, "reason": self.reason,
                "params": dict(self.params), "plan": dict(self.plan),
                "notes": [dict(n) for n in self.notes]}


class _Capture:
    """Collector for one ``with capture():`` scope (single-thread use —
    the scope lives on the thread that opened it)."""

    def __init__(self) -> None:
        self.records: List[ExplainRecord] = []

    @property
    def last(self) -> Optional[ExplainRecord]:
        return self.records[-1] if self.records else None

    def briefs(self) -> List[dict]:
        return [r.brief() for r in self.records]


_tls = threading.local()


def _stack() -> List[_Capture]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


@contextlib.contextmanager
def capture() -> Iterator[_Capture]:
    """Collect every :class:`ExplainRecord` emitted on THIS thread while
    the scope is open. Scopes nest (each record lands in every open
    scope, so an engine-level capture still sees records a tool-level
    inner capture claims). Never raises into the instrumented path."""
    col = _Capture()
    stack = _stack()
    stack.append(col)
    try:
        yield col
    finally:
        # tolerate a peer popping out of order rather than corrupting
        # the instrumented call (telemetry never fails serving)
        with contextlib.suppress(ValueError):
            stack.remove(col)


def record_dispatch(family: str, requested: str, engine: str, reason: str,
                    params: Optional[dict] = None,
                    plan: Optional[dict] = None) -> ExplainRecord:
    """THE emission point: build the record, bump
    ``raft_tpu_dispatch_total{family,engine,reason}``, and hand the
    record to every open :func:`capture` scope on this thread.

    ``reason`` outside :data:`REASONS` is a programming error and
    raises — the vocabulary is closed so dashboards and the
    reconciliation tests can enumerate it."""
    if reason not in REASONS:
        raise ValueError(f"reason {reason!r} outside the documented "
                         f"vocabulary (docs/observability.md)")
    rec = ExplainRecord(family=family, requested=requested, engine=engine,
                        reason=reason, params=dict(params or {}),
                        plan=dict(plan or {}))
    _DISPATCH.labels(family, engine, reason).inc()
    for col in _stack():
        col.records.append(rec)
    return rec


def note_select_k(n: int, k: int, algo: str, k_pad: int = 0) -> None:
    """Attach a select_k AUTO/pad resolution to the active capture(s).

    Runs at trace time inside jitted search bodies — once per compiled
    shape — so it deliberately does NOT touch the dispatch counter (see
    the module docstring); it exists so ``tools/explain.py`` and
    ``search(..., explain=True)`` show the full plan of a cold query."""
    stack = _stack()
    if not stack:
        return
    note = {"op": "select_k", "n": int(n), "k": int(k), "algo": str(algo),
            "k_pad": int(k_pad)}
    for col in stack:
        if col.records:
            col.records[-1].notes.append(note)
        else:
            # select_k used standalone under a capture: synthesize a
            # record so the decision is still attributable
            col.records.append(ExplainRecord(
                family="select_k", requested="auto", engine=str(algo),
                reason="forced", params={"n": int(n), "k": int(k)},
                plan={"k_pad": int(k_pad)}))


def dispatch_counts(
        registry: Optional[_metrics.Registry] = None) -> Dict[tuple, int]:
    """``{(family, engine, reason): count}`` view of the dispatch
    counter — the explain reason histogram serving_bench artifacts
    record next to the pallasgate verdicts."""
    reg = registry if registry is not None else _metrics.REGISTRY
    fam = reg.get("raft_tpu_dispatch_total")
    if fam is None:
        return {}
    return {tuple(key): int(child.value) for key, child in fam.collect()
            if int(child.value)}
