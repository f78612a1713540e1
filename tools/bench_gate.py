#!/usr/bin/env python
"""bench_gate — noise-aware bench regression gate.

Diffs a candidate bench run (one or more repeat JSONs) against a
baseline bench JSON and emits a typed verdict per metric:

- ``improved`` / ``flat`` / ``regressed`` — relative change vs the
  tolerance band (default ±5%), direction-aware: ``qps``/``recall``/
  ``rows_per_s`` are higher-better, ``latency``/``build_s``/``*_ms``/
  ``wall_s`` lower-better; metrics whose direction cannot be classified
  are reported ``ignored`` and never gate;
- ``missing`` — present in the baseline, absent from every candidate
  repeat (a silently-dropped bench is a regression of the *bench*).

Noise rule: with N candidate repeats the gate scores the BEST repeat
per metric. A real regression reproduces in every repeat; a one-off
scheduler hiccup does not — so best-of-N kills the false-positive rate
without hiding sustained losses. Pass repeats as extra positional
files.

Accepts the repo's bench artifact shapes: a wrapped run
(``{"parsed": {...}}``), a raw bench.py stdout object
(``{"metric", "value", "recall", "extra": {family: {...}}}``), a flat
``{"metrics": {name: value}}`` document, or a ``.log`` file whose last
JSON-parseable line contains ``"metric"``.

Frontier kind: a document whose ``schema`` is ``raft_tpu.pareto/*``
(the committed ``PARETO_<platform>.json`` autotune artifacts) is
compared as a CURVE, not pointwise — per (family, k, bucket) frontier
the gate scores the hypervolume and the best-QPS per recall band
(``pareto.<fam>.k<k>.b<b>.hypervolume`` / ``.qps_at_r<band>``, both
higher-better). Individual operating points may move, appear, or
vanish freely across a re-sweep; only a shrinking dominated area or a
QPS loss at a recall band gates. The summaries are recomputed from the
points themselves (``raft_tpu.planner.adaptive.frontier_metrics``) so
a stale embedded mirror cannot mask a curve regression.

Exit status: 0 all gated metrics flat/improved; 1 any ``regressed`` (or
``missing`` without ``--allow-missing``); 2 usage/parse errors.

Typical use::

    python tools/bench_gate.py BENCH_r03.json BENCH_r04.json
    python tools/bench_gate.py baseline.json run1.json run2.json run3.json
    python tools/bench_gate.py --tolerance 0.08 old.json new.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Optional

DEFAULT_TOLERANCE = 0.05

#: metric-name suffix/token → direction. Longest match wins; tokens are
#: matched against '.'-and-'_'-split pieces of the metric name.
_HIGHER = ("qps", "recall", "rows_per_s", "throughput", "hypervolume",
           "hit_rate")
_LOWER = ("latency_ms", "latency_ms_b1", "latency_ms_b10", "mean_ms",
          "p50_ms", "p99_ms", "build_s", "build_warm_s", "warm_s",
          "wall_s", "fit_s", "chained_ms")


def metric_direction(name: str) -> Optional[int]:
    """+1 higher-better, -1 lower-better, None unknown. Token-based so
    embedded shape/config qualifiers (``brute_force_knn_qps_sift10k_k10``)
    don't hide the measure."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf in _HIGHER or any(leaf.endswith(t) for t in _HIGHER):
        return +1
    tokens = set(leaf.split("_"))
    if tokens & {"qps", "recall", "throughput"}:
        return +1
    if (leaf in _LOWER or leaf.endswith("_ms") or leaf.endswith("_s")
            or "latency" in tokens):
        return -1
    return None


# ----------------------------------------------------------- doc flattening
def _payload(doc: dict) -> dict:
    """Unwrap a bench artifact to the bench.py stdout object."""
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        return doc["parsed"]
    return doc


def _flatten_frontier(p: dict) -> dict:
    """Pareto-frontier doc → curve summaries (the ``frontier`` artifact
    kind). Recomputed from the points via the planner's own summary code
    when importable; the artifact's embedded ``metrics`` mirror is the
    fallback (identical by construction — tools/autotune.py writes the
    mirror with the same function)."""
    try:
        import os
        import sys
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from raft_tpu.planner.adaptive import frontier_metrics
        return {k: float(v) for k, v in frontier_metrics(p).items()}
    except Exception:
        metrics = p.get("metrics")
        if isinstance(metrics, dict):
            return {str(k): float(v) for k, v in metrics.items()
                    if isinstance(v, (int, float))}
        return {}


def flatten_metrics(doc: dict) -> dict:
    """Bench doc → ``{metric_name: float}``. The top-level metric keeps
    its own name; per-family ``extra`` entries become ``family.field``.
    Frontier docs (``schema: raft_tpu.pareto/*``) flatten to their curve
    summaries instead — see :func:`_flatten_frontier`."""
    out: dict = {}
    p = _payload(doc)
    if str(p.get("schema", "")).startswith("raft_tpu.pareto/"):
        return _flatten_frontier(p)
    if isinstance(p.get("metrics"), dict):  # flat mini-bench document
        for k, v in p["metrics"].items():
            if isinstance(v, (int, float)):
                out[str(k)] = float(v)
    name = p.get("metric")
    if name and isinstance(p.get("value"), (int, float)):
        out[str(name)] = float(p["value"])
        if isinstance(p.get("recall"), (int, float)):
            out[f"{name}.recall"] = float(p["recall"])
    extra = p.get("extra")
    if isinstance(extra, dict):
        for fam, fields in extra.items():
            if not isinstance(fields, dict):
                continue
            for k, v in fields.items():
                if isinstance(v, (int, float)):
                    out[f"{fam}.{k}"] = float(v)
    return out


def load_bench(path: str) -> dict:
    """Read a bench artifact (.json, or .log scanned for the last
    JSON line carrying "metric") → flat metric dict."""
    if path.endswith(".log"):
        doc = None
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not (line.startswith("{") and '"metric"' in line):
                    continue
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
        if doc is None:
            raise ValueError(f"{path}: no JSON bench line found")
        return flatten_metrics(doc)
    with open(path) as fh:
        return flatten_metrics(json.load(fh))


# ------------------------------------------------------------------ the gate
@dataclasses.dataclass
class Verdict:
    metric: str
    verdict: str  # improved | flat | regressed | missing | ignored
    baseline: float
    best: Optional[float]  # best candidate repeat (None when missing)
    rel_change: Optional[float]  # signed, direction-normalized

    def format(self) -> str:
        tag = self.verdict.upper().ljust(9)
        if self.best is None:
            return f"  {tag} {self.metric}: baseline {self.baseline:g}, " \
                   f"absent from candidate"
        pct = (f"{self.rel_change * 100:+.1f}%"
               if self.rel_change is not None else "n/a")
        return (f"  {tag} {self.metric}: {self.baseline:g} -> "
                f"{self.best:g} ({pct})")


def gate(baseline: dict, candidates: list, tolerance: float
         ) -> list:
    """→ one :class:`Verdict` per baseline metric. ``candidates`` is a
    list of flat metric dicts (the repeats)."""
    out = []
    for name in sorted(baseline):
        base = baseline[name]
        direction = metric_direction(name)
        vals = [c[name] for c in candidates if name in c]
        if not vals:
            out.append(Verdict(name, "missing", base, None, None))
            continue
        if direction is None:
            out.append(Verdict(name, "ignored", base, vals[-1], None))
            continue
        best = max(vals) if direction > 0 else min(vals)
        if base == 0:
            rel = 0.0 if best == 0 else float("inf")
        else:
            rel = (best - base) / abs(base) * direction
        if rel < -tolerance:
            v = "regressed"
        elif rel > tolerance:
            v = "improved"
        else:
            v = "flat"
        out.append(Verdict(name, v, base, best, rel))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_gate", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("baseline", help="baseline bench JSON (or .log)")
    ap.add_argument("candidate", nargs="+",
                    help="candidate bench JSON(s); extras are noise "
                         "repeats scored best-of-N")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="relative tolerance band (default 0.05 = 5%%)")
    ap.add_argument("--allow-missing", action="store_true",
                    help="metrics absent from the candidate do not gate")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="also write the verdicts as JSON to this path")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="print only the summary line")
    args = ap.parse_args(argv)

    try:
        base = load_bench(args.baseline)
        cands = [load_bench(p) for p in args.candidate]
    except (OSError, ValueError) as e:
        print(f"bench_gate: {e}", file=sys.stderr)
        return 2
    if not base:
        print(f"bench_gate: no metrics found in {args.baseline}",
              file=sys.stderr)
        return 2

    verdicts = gate(base, cands, args.tolerance)
    counts: dict = {}
    for v in verdicts:
        counts[v.verdict] = counts.get(v.verdict, 0) + 1
        if not args.quiet and v.verdict != "flat":
            print(v.format())

    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump({"tolerance": args.tolerance,
                       "n_repeats": len(cands),
                       "verdicts": [dataclasses.asdict(v)
                                    for v in verdicts]}, fh, indent=1)
            fh.write("\n")

    gating = counts.get("regressed", 0)
    if not args.allow_missing:
        gating += counts.get("missing", 0)
    summary = ", ".join(f"{counts.get(k, 0)} {k}" for k in
                        ("improved", "flat", "regressed", "missing",
                         "ignored"))
    print(f"bench_gate: {summary} (tolerance {args.tolerance:.0%}, "
          f"best of {len(cands)} repeat(s))")
    return 1 if gating else 0


if __name__ == "__main__":
    sys.exit(main())
