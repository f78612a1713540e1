"""Artifact consistency gate (``graftcheck --artifacts``, rule A001).

Some committed JSON artifacts at the repo root are read at run time:
the adaptive planner reads ``PARETO_*`` frontiers, the tiered index
reads its manifests, and graftcheck itself reads
``graftcheck_baseline.json``. Their loaders skip or refuse a malformed
file, which is right for serving and wrong for CI: a schema drift would
demote a committed artifact to silently-ignored. No artifact chooses a
kernel or a k: those rules live in code (``ops/select_k.py``,
``ops/pallas_kernels.py``).

This module re-runs every committed ``*.json`` at the repo root through
the loader that consumes it:

- ``PARETO_*`` → :func:`raft_tpu.planner.adaptive.load_frontier`
  (schema-validating);
- ``TIERED_MANIFEST_*`` → :func:`raft_tpu.neighbors.tiered.validate_manifest`;
- ``graftcheck_baseline.json`` → :func:`load_baseline`;
- everything else → ``json.load`` (the artifact must at least parse).

Findings carry rule ``A001`` and flow through the same baseline /
``--json`` machinery as every other tier.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Callable, Dict, List, Tuple

from raft_tpu.analysis.findings import Finding

__all__ = ["run_artifacts", "artifact_kind"]

_RULE = "A001"


def artifact_kind(name: str) -> str:
    """The loader family a root-level artifact belongs to."""
    if name == "graftcheck_baseline.json":
        return "baseline"
    for prefix, kind in (("PARETO_", "pareto"),
                         ("TIERED_MANIFEST_", "tiered_manifest")):
        if name.startswith(prefix):
            return kind
    return "json"


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
    "pareto": _check_pareto,
    "baseline": _check_baseline,
    "tiered_manifest": _check_tiered_manifest,
}


def run_artifacts(root: str) -> Tuple[List[Finding], List[str]]:
    """Validate every root-level ``*.json`` under its consuming loader.

    Returns ``(findings, report_lines)`` — findings for parse/loader
    failures, report lines for the per-artifact ledger.
    """
    findings: List[Finding] = []
    report: List[str] = []
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

