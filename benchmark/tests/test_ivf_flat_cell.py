"""The IVF-Flat cell's parts at a tiny size on the CPU: its count worked by
hand, its roofline reader on a made-up trace, and a run of the system
through ``harness.run_cell`` in which the program passes its limits and
the reference at ``high`` fails one."""

import json
import os
import types

import pytest

from benchmark import controls, harness
from conftest import make_copy, run_tiny

#: a tiny stand-in of sift1m-ivfflat: 16,384 x 32 rows in 32 lists, 8
#: probed. Limits from tiny readings on the CPU (seeds 11, 12, 2**33 + 5):
#: the program's recall_gap 0.0063-0.0102, dist_error 4.4e-6-5.4e-6; the
#: reference at high dist_error 4.6e-5-5.8e-5; the half_scan setting
#: recall_gap 0.0067-0.0141, dist_error 3.1e-6-4.1e-6 (its re-rank is
#: float32, so dist_error does not see it). rank_excess is not limited, as
#: in the configuration: the program's probe misses (0.135-0.195) read
#: above the reference at high (0)
TINY = {
    "dataset": {"rows": 16384, "queries": 512, "dim": 32, "chunk": 4096},
    "index": {"nlist": 32, "niter": 4},
    "search": {"nprobe": 8, "batch": 256},
    "check": {"sample": 256, "limits": {
        "invalid_rows": 0, "recall_gap": 0.05, "dist_error": 1.5e-5}},
}
CELL = "tiny-ivfflat.batch"


@pytest.fixture(scope="module")
def flat_root(tmp_path_factory):
    """The committed benchmark with a tiny IVF-Flat configuration and its
    cell added as a new file and new entries."""
    root = make_copy(str(tmp_path_factory.mktemp("flat")))
    base = harness.load_json(os.path.join(root, "benchmark", "configs",
                                          "sift1m-ivfflat.json"))
    cfg = harness._merged(base, TINY)
    cfg["name"] = "tiny-ivfflat"
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-ivfflat.json"), "w") as f:
        json.dump(cfg, f)
    spec = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({"name": "tiny-ivfflat", "source": "test",
                            "file": "benchmark/configs/tiny-ivfflat.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-ivfflat",
                              "traffic": "batch_closed", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "ivfflat-sift1m-batch" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def test_ivf_flat_search_count():
    fl = harness.load_module("counts", "ivf_flat")
    # 2 queries, 64 rows in 4 lists of 16, dim 8, 2 probes, k 5
    c = fl.search(nq=2, n_rows=64, dim=8, n_lists=4, n_probes=2, k=5)
    assert c["flops"] == pytest.approx(2 * 2 * 4 * 8       # coarse: 128
                                       + 2 * 2 * 32 * 8)   # fine: 1024
    distinct = 4 * (1 - (1 - 2 / 4) ** 2)  # 3 lists
    assert c["bytes"] == pytest.approx(distinct * 16 * (8 * 4 + 4)  # 1728
                                       + 4 * 8 * 4 + 2 * 8 * 4 + 2 * 5 * 8)
    _, bound = fl.least_seconds(c, {"flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9})
    assert bound == "bytes"
    # the cell: 10k queries, 1M x 128 in 1024 lists, nprobe 128, k 10 —
    # about 322.6 GFLOP, flops-bound at the v5e's peaks
    c = fl.search(nq=10000, n_rows=1_000_000, dim=128, n_lists=1024,
                  n_probes=128, k=10)
    assert c["flops"] == pytest.approx(2 * 10000 * 1024 * 128
                                       + 2 * 10000 * 128e6 / 1024 * 128)
    t, bound = fl.least_seconds(c, {"flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9})
    assert bound == "flops" and t == pytest.approx(1.6377e-3, rel=1e-3)


def test_roofline_reads_every_search_module(flat_root):
    """Each ``jit__search_*core`` module of the window counts (the
    query-major core, the fused one, a list-major one); other modules do
    not."""
    spec = harness.load_json(os.path.join(flat_root, "BENCHMARK.json"))
    cell = harness.Cell(spec, CELL, root=flat_root)
    dev = types.SimpleNamespace(
        module_s={"jit__search_core(3)": 1.0, "jit__search_fused_core": 0.5,
                  "jit__search_lists_core(1)": 0.5, "jit_pad_rows": 9.0},
        module_n={"jit__search_core(3)": 2, "jit__search_fused_core": 1,
                  "jit__search_lists_core(1)": 1, "jit_pad_rows": 4})
    peaks = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e12}
    ctx = {"trace": types.SimpleNamespace(devices={"/device:TPU:0": dev}),
           "loop": "closed", "config": cell.config, "peaks": peaks,
           "counts": lambda fam: harness.load_module("counts", fam,
                                                     cell.bench)}
    fl = harness.load_module("counts", "ivf_flat", cell.bench)
    ds, sx = cell.config["dataset"], cell.config["search"]
    least, _ = fl.least_seconds(fl.search(
        sx["batch"], ds["rows"], ds["dim"], cell.config["index"]["nlist"],
        sx["nprobe"], sx["k"]), peaks)
    got = cell.reader("ivf_flat_search_roofline").read(ctx)
    assert got == pytest.approx(100.0 * 4 * least / 2.0)
    ctx["trace"] = None  # an untraced run: nothing to read
    assert cell.reader("ivf_flat_search_roofline").read(ctx) is None


@pytest.mark.parametrize("seed", [2**33 + 21, 22])
def test_program_passes_high_fails(flat_root, seed):
    spec = harness.load_json(os.path.join(flat_root, "BENCHMARK.json"))
    cell = harness.Cell(spec, CELL, root=flat_root)
    prog = run_tiny(flat_root, CELL, seed=seed, seconds=0.3)
    assert prog["correct"], prog["check"]
    assert set(prog["metrics"]) == {"qps", "recall", "setup_s"}
    high = run_tiny(flat_root, CELL, seed=seed, seconds=0.3,
                    **controls.variant_args(cell, "high"))
    assert not high["correct"], high["check"]
    assert high["check"]["dist_error"]["value"] > \
        high["check"]["dist_error"]["limit"]


def test_traced_run(flat_root):
    """A ``--trace 1`` run reports the per-layer metrics it can read; the
    CPU's trace holds no TPU plane, so the roofline is left out."""
    out = run_tiny(flat_root, CELL, seconds=0.5, trace=True)
    assert out["correct"], out["check"]
    assert "ivf_flat_search_roofline" not in out["metrics"]
    assert out["metrics"]["build_s"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(out["device"])
