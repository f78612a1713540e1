"""Artifact consistency gate (``graftcheck --artifacts``, rule A001).

The repo's committed JSON artifacts are load-bearing: the dispatch
layer reads ``TOPK_PAD_*``/``PALLAS_PROBE_*`` at
import time to pick engines, the adaptive planner reads ``PARETO_*``
frontiers, and graftcheck itself reads ``graftcheck_baseline.json``.
Each of those loaders was written against a schema that has already
been revved (the pallas probe is on v3) — and every scanner
deliberately *skips* malformed artifacts rather than crashing the
import, which is right for serving and exactly wrong for CI: a schema
drift would demote a committed artifact to silently-ignored and nothing
would notice until a TPU session burned time rediscovering it.

This module re-runs every committed ``*.json`` at the repo root through
the loader that consumes it:

- ``TOPK_PAD_*`` → the pad-rule extractor (``art["pad_rules"]``);
- ``PALLAS_PROBE_*`` → the fused-verdict extractor plus
  ``tools/pallas_probe.missing_verdicts`` coverage over
  ``REQUIRED_VERDICT_FAMILIES``.  The committed probe predates the v3
  ``"fused"`` verdict section (ROADMAP item 1 is precisely about
  regenerating it), so a pre-v3 probe is *reported* — loudly, in the
  report lines — but is not a finding; a v3 probe with missing or
  errored verdict rows IS a finding, because that means the one queued
  TPU session produced an artifact the dispatch layer cannot act on.
- ``PARETO_*`` → :func:`raft_tpu.planner.adaptive.load_frontier`
  (schema-validating);
- ``graftcheck_baseline.json`` → :func:`load_baseline`;
- everything else → ``json.load`` (the artifact must at least parse).

Findings carry rule ``A001`` and flow through the same baseline /
``--json`` machinery as every other tier.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional, Tuple

from raft_tpu.analysis.findings import Finding

__all__ = ["run_artifacts", "artifact_kind"]

_RULE = "A001"


def _load_pallas_probe_helpers(root: str):
    """``tools/`` is not a package; pull ``missing_verdicts`` and
    ``REQUIRED_VERDICT_FAMILIES`` straight from the file so the checker
    can never drift from the probe's own coverage definition."""
    path = os.path.join(root, "tools", "pallas_probe.py")
    spec = importlib.util.spec_from_file_location(
        "_graftcheck_pallas_probe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.missing_verdicts, mod.REQUIRED_VERDICT_FAMILIES


def artifact_kind(name: str) -> str:
    """The loader family a root-level artifact belongs to."""
    if name == "graftcheck_baseline.json":
        return "baseline"
    for prefix, kind in (("PALLAS_PROBE_", "pallas_probe"),
                         ("TOPK_PAD_", "topk_pad"),
                         ("PARETO_", "pareto"),
                         ("TIERED_MANIFEST_", "tiered_manifest")):
        if name.startswith(prefix):
            return kind
    return "json"


def _check_topk_pad(art: dict, path: str) -> None:
    # mirrors select_k._load_pad_rules's extractor: the artifact rows
    # are merged per (n, k) cell with the builtins, so both keys (and
    # the k_pad payload) must exist on every row
    from raft_tpu.ops.select_k import _BUILTIN_PAD_RULES, _merge_pad_rules
    platform = art["platform"]
    merged = _merge_pad_rules(
        _BUILTIN_PAD_RULES.get(platform, []), art["pad_rules"])
    for row in merged:
        if not all(k in row for k in ("n", "k", "k_pad")):
            raise ValueError(f"pad rule {row} lacks an n/k/k_pad key")


def _check_pareto(art: dict, path: str) -> None:
    from raft_tpu.planner.adaptive import load_frontier
    load_frontier(path)


def _check_tiered_manifest(art: dict, path: str) -> None:
    # the exact front half of tiered.load_tiered: schema + geometry +
    # per-file crc32/header agreement, so a committed manifest that
    # load_tiered would refuse (or silently mis-read) fails CI here
    from raft_tpu.neighbors.tiered import validate_manifest
    validate_manifest(art, base_dir=os.path.dirname(os.path.abspath(path)),
                      check_files=True)


def _check_baseline(art: dict, path: str) -> None:
    from raft_tpu.analysis.findings import load_baseline
    entries = load_baseline(path)
    for key, justification in entries.items():
        if not isinstance(justification, str):
            raise ValueError(f"baseline entry {key} has a non-string "
                             f"justification")


_CHECKERS: Dict[str, Callable[[dict, str], None]] = {
    "topk_pad": _check_topk_pad,
    "pareto": _check_pareto,
    "baseline": _check_baseline,
    "tiered_manifest": _check_tiered_manifest,
}


def run_artifacts(root: str) -> Tuple[List[Finding], List[str]]:
    """Validate every root-level ``*.json`` under its consuming loader.

    Returns ``(findings, report_lines)`` — findings for parse/loader
    failures and missing v3 probe verdicts, report lines for the
    per-artifact ledger (including the known-stale pre-v3 probe note).
    """
    findings: List[Finding] = []
    report: List[str] = []
    missing_verdicts: Optional[Callable] = None
    required: tuple = ()
    try:
        missing_verdicts, required = _load_pallas_probe_helpers(root)
    except Exception as e:
        findings.append(Finding(
            _RULE, "tools/pallas_probe.py", "<module>", 0,
            f"cannot load the probe's verdict vocabulary: "
            f"{type(e).__name__}: {e}"))

    paths = sorted(glob.glob(os.path.join(root, "*.json")))
    n_ok = 0
    for path in paths:
        name = os.path.basename(path)
        kind = artifact_kind(name)
        try:
            with open(path) as fh:
                art = json.load(fh)
        except Exception as e:
            findings.append(Finding(
                _RULE, name, "<artifact>", 0,
                f"does not parse as JSON: {type(e).__name__}: {e}"))
            continue
        if kind == "pallas_probe":
            line = _check_pallas_probe(
                art, name, missing_verdicts, required, findings)
            report.append(line)
            if "FINDING" not in line:
                n_ok += 1
            continue
        checker = _CHECKERS.get(kind)
        if checker is None:
            report.append(f"{name}: ok (json)")
            n_ok += 1
            continue
        try:
            checker(art, path)
        except Exception as e:
            findings.append(Finding(
                _RULE, name, "<artifact>", 0,
                f"rejected by its {kind} loader: "
                f"{type(e).__name__}: {e} — the runtime scanner would "
                f"silently skip this artifact"))
            report.append(f"{name}: FINDING ({kind} loader rejected)")
            continue
        report.append(f"{name}: ok ({kind})")
        n_ok += 1
    report.append(f"{n_ok}/{len(paths)} artifact(s) loadable under their "
                  f"consuming loaders")
    return findings, report


def _check_pallas_probe(art: dict, name: str, missing_verdicts, required,
                        findings: List[Finding]) -> str:
    if not isinstance(art, dict) or "platform" not in art:
        findings.append(Finding(
            _RULE, name, "<artifact>", 0,
            "probe artifact has no 'platform' key — the runtime scanner "
            "would silently skip it"))
        return f"{name}: FINDING (unkeyed probe)"
    if "fused" not in art:
        # the known-stale pre-v3 probe: report, don't fail (ROADMAP
        # item 1 queues its regeneration)
        fams = ", ".join(required) if required else "?"
        return (f"{name}: STALE pre-v3 probe (no 'fused' verdict "
                f"section) — families unverified: {fams}; the queued "
                f"TPU session must regenerate it")
    if missing_verdicts is None:
        return f"{name}: v3 probe (verdict vocabulary unavailable)"
    missing = missing_verdicts(art, on_tpu=True, mergeable_mesh=False)
    if missing:
        findings.append(Finding(
            _RULE, name, "<artifact>", 0,
            f"v3 probe is missing measured verdicts for: "
            f"{', '.join(missing)} — the dispatch layer treats an "
            f"absent/errored row as 'pallas loses', wasting the "
            f"measurement"))
        return f"{name}: FINDING (verdicts missing: {', '.join(missing)})"
    return f"{name}: ok (v3 probe, all verdict families covered)"
