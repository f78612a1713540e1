"""bench_gate tests: direction classification, artifact-shape flattening,
the best-of-N noise rule, and the three exit codes the CI/queue wiring
relies on (0 clean, 1 regressed/missing, 2 unusable input)."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
import bench_gate  # noqa: E402

pytestmark = pytest.mark.fast


# --------------------------------------------------------------- direction
@pytest.mark.parametrize("name,want", [
    ("brute_force_knn_qps_sift10k_k10", +1),
    ("mini_brute_force_qps_2000x32_k10", +1),
    ("ivf_flat_nprobe8.qps", +1),
    ("ivf_flat_nprobe8.recall", +1),
    ("select_k_256x8192.rows_per_s", +1),
    ("cagra.build_s", -1),
    ("ivf_pq.latency_ms_b1", -1),
    ("fused.p99_ms", -1),
    ("serving.wall_s", -1),
    ("some_random_counter", None),
    ("n_lists", None),
])
def test_metric_direction(name, want):
    assert bench_gate.metric_direction(name) == want


# -------------------------------------------------------------- flattening
def test_flatten_accepts_all_three_artifact_shapes():
    raw = {"metric": "knn_qps", "value": 100.0, "recall": 0.98,
           "extra": {"ivf_flat": {"qps": 50.0, "build_s": 2.0},
                     "notes": "not-a-dict-of-numbers"}}
    flat = bench_gate.flatten_metrics(raw)
    assert flat == {"knn_qps": 100.0, "knn_qps.recall": 0.98,
                    "ivf_flat.qps": 50.0, "ivf_flat.build_s": 2.0}
    # the {"parsed": ...} wrapper unwraps to the same thing
    assert bench_gate.flatten_metrics({"parsed": raw}) == flat
    # a flat metrics document passes through
    assert bench_gate.flatten_metrics(
        {"metrics": {"a_qps": 1.0, "skip": "str"}}) == {"a_qps": 1.0}


def test_load_bench_scans_log_for_last_metric_line(tmp_path):
    log = tmp_path / "bench.log"
    log.write_text(
        "warmup chatter\n"
        '{"metric": "knn_qps", "value": 90.0}\n'
        "not json {\n"
        '{"metric": "knn_qps", "value": 110.0}\n')
    assert bench_gate.load_bench(str(log)) == {"knn_qps": 110.0}
    empty = tmp_path / "empty.log"
    empty.write_text("nothing here\n")
    with pytest.raises(ValueError, match="no JSON bench line"):
        bench_gate.load_bench(str(empty))


# -------------------------------------------------------------------- gate
def _verdict(verdicts, name):
    return next(v for v in verdicts if v.metric == name)


def test_gate_verdicts_are_direction_aware():
    base = {"a_qps": 100.0, "b.latency_ms": 10.0, "c_qps": 100.0,
            "d.build_s": 5.0, "mystery": 3.0, "gone_qps": 1.0}
    cand = {"a_qps": 90.0,        # -10% on higher-better: regressed
            "b.latency_ms": 9.0,  # -10% on lower-better: improved
            "c_qps": 103.0,       # +3% inside the band: flat
            "d.build_s": 5.1,     # +2% inside the band: flat
            "mystery": 9.9}       # unknown direction: ignored
    vs = bench_gate.gate(base, [cand], tolerance=0.05)
    got = {v.metric: v.verdict for v in vs}
    assert got == {"a_qps": "regressed", "b.latency_ms": "improved",
                   "c_qps": "flat", "d.build_s": "flat",
                   "mystery": "ignored", "gone_qps": "missing"}
    assert _verdict(vs, "a_qps").rel_change == pytest.approx(-0.10)
    # lower-better rel_change is direction-normalized: less is positive
    assert _verdict(vs, "b.latency_ms").rel_change == pytest.approx(+0.10)


def test_gate_best_of_n_forgives_one_noisy_repeat():
    """A one-off hiccup in one repeat must not gate; a loss sustained
    across every repeat must."""
    base = {"a_qps": 100.0}
    hiccup = [{"a_qps": 60.0}, {"a_qps": 99.0}]  # one bad, one fine
    assert bench_gate.gate(base, hiccup, 0.05)[0].verdict == "flat"
    sustained = [{"a_qps": 80.0}, {"a_qps": 82.0}]
    assert bench_gate.gate(base, sustained, 0.05)[0].verdict == "regressed"
    # lower-better best is the MIN across repeats
    base_ms = {"a.latency_ms": 10.0}
    vs = bench_gate.gate(base_ms, [{"a.latency_ms": 14.0},
                                   {"a.latency_ms": 10.1}], 0.05)
    assert vs[0].verdict == "flat" and vs[0].best == 10.1


def test_gate_zero_baseline_does_not_divide():
    vs = bench_gate.gate({"a_qps": 0.0}, [{"a_qps": 0.0}], 0.05)
    assert vs[0].verdict == "flat"
    vs = bench_gate.gate({"a_qps": 0.0}, [{"a_qps": 5.0}], 0.05)
    assert vs[0].verdict == "improved"


# -------------------------------------------------------------- exit codes
def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_main_exit_codes(tmp_path, capsys):
    base = _write(tmp_path, "base.json",
                  {"metrics": {"a_qps": 100.0, "b.latency_ms": 10.0}})
    same = _write(tmp_path, "same.json",
                  {"metrics": {"a_qps": 101.0, "b.latency_ms": 9.9}})
    worse = _write(tmp_path, "worse.json",
                   {"metrics": {"a_qps": 80.0, "b.latency_ms": 10.0}})
    partial = _write(tmp_path, "partial.json", {"metrics": {"a_qps": 99.0}})

    assert bench_gate.main([base, same]) == 0
    assert bench_gate.main([base, worse]) == 1
    # best-of-N: the clean repeat rescues the noisy one
    assert bench_gate.main([base, worse, same]) == 0
    # missing gates by default, --allow-missing waives it
    assert bench_gate.main([base, partial]) == 1
    assert bench_gate.main([base, partial, "--allow-missing"]) == 0
    # unusable inputs are exit 2, not a traceback
    assert bench_gate.main([str(tmp_path / "nope.json"), same]) == 2
    empty = _write(tmp_path, "empty.json", {"metrics": {}})
    assert bench_gate.main([empty, same]) == 2
    capsys.readouterr()


def test_main_writes_verdict_json(tmp_path, capsys):
    base = _write(tmp_path, "base.json", {"metrics": {"a_qps": 100.0}})
    cand = _write(tmp_path, "cand.json", {"metrics": {"a_qps": 120.0}})
    out = tmp_path / "verdicts.json"
    assert bench_gate.main([base, cand, "--json", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["n_repeats"] == 1
    assert doc["verdicts"][0]["verdict"] == "improved"
    assert "1 improved" in capsys.readouterr().out


# ---------------------------------------------------------- frontier kind
def _pareto_doc(curves):
    """``curves``: {family: [(recall, qps), ...]} → a minimal
    raft_tpu.pareto/v1 doc (k=10, bucket=8)."""
    fams = {}
    for fam, pts in curves.items():
        fams[fam] = {"frontier": {"10": {"8": [
            {"params": {"n_probes": i}, "bucket": 8, "qps": q,
             "recall": r, "predicted_ms": 8.0 / q * 1e3}
            for i, (r, q) in enumerate(pts)]}}}
    return {"schema": "raft_tpu.pareto/v1", "platform": "cpu",
            "families": fams}


def test_flatten_frontier_yields_curve_summaries_not_points():
    flat = bench_gate.flatten_metrics(
        _pareto_doc({"ivf_flat": [(0.99, 100.0), (0.90, 900.0)]}))
    assert flat["pareto.ivf_flat.k10.b8.n_points"] == 2.0
    assert flat["pareto.ivf_flat.k10.b8.qps_at_r90"] == 900.0
    assert flat["pareto.ivf_flat.k10.b8.hypervolume"] > 0
    # no per-point metric leaks out — points may move freely on re-sweep
    assert not any("n_probes" in k or "predicted_ms" in k for k in flat)


def test_gate_frontier_pass_on_moved_points_same_curve(tmp_path):
    base = _write(tmp_path, "pareto_base.json",
                  _pareto_doc({"ivf_flat": [(0.99, 100.0), (0.90, 900.0)]}))
    # a re-sweep found a different but equivalent frontier: an extra
    # mid-curve point, slight point movement within tolerance
    cand = _write(tmp_path, "pareto_cand.json",
                  _pareto_doc({"ivf_flat": [(0.99, 101.0), (0.95, 400.0),
                                            (0.90, 905.0)]}))
    assert bench_gate.main([base, cand, "--allow-missing"]) == 0


def test_gate_frontier_fails_on_degraded_curve(tmp_path):
    base = _write(tmp_path, "pareto_base.json",
                  _pareto_doc({"ivf_flat": [(0.99, 100.0), (0.90, 900.0)]}))
    # the high-recall end got 40% slower: hypervolume + qps_at_r99 drop
    worse = _write(tmp_path, "pareto_worse.json",
                   _pareto_doc({"ivf_flat": [(0.99, 60.0), (0.90, 900.0)]}))
    assert bench_gate.main([base, worse]) == 1


def test_gate_frontier_recomputes_ignoring_stale_mirror(tmp_path):
    # an embedded metrics mirror claiming a better curve must not mask
    # the regression — the gate recomputes from the points
    doc = _pareto_doc({"ivf_flat": [(0.99, 60.0)]})
    doc["metrics"] = {"pareto.ivf_flat.k10.b8.qps_at_r99": 100.0}
    base = _write(tmp_path, "pareto_base.json",
                  _pareto_doc({"ivf_flat": [(0.99, 100.0)]}))
    lying = _write(tmp_path, "pareto_lying.json", doc)
    assert bench_gate.main([base, lying, "--allow-missing"]) == 1
