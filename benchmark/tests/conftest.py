"""CPU rehearsals of the benchmark's parts at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Four virtual CPU devices stand in for the four-chip mesh. Nothing here
times anything: the benchmark itself runs only on the chip.
"""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmark import harness  # noqa: E402

#: the tiny stand-ins of the two configurations, written into a copy
TINY = {
    "tiny-ivfpq": {
        "base": "sift1m-ivfpq.json",
        "dataset": {"rows": 8192, "queries": 512, "dim": 32, "chunk": 4096},
        "index": {"nlist": 32, "pq_dim": 16, "niter": 4},
        "search": {"nprobe": 8, "batch": 256},
        # set from tiny readings on the CPU (seeds 11, 12, 2**33 + 5): the
        # program's recall_gap 0.080-0.083, dist_error 0.35-0.45 and
        # adc_error 8.6e-7-9.7e-7; int4 codes 0.259-0.270 and 0.79-1.19;
        # the half path's adc_error 5.8e-3-7.6e-3
        "check": {"sample": 256, "limits": {
            "invalid_rows": 0, "recall_gap": 0.15, "dist_error": 0.7,
            "adc_error": 1e-4}},
    },
    "tiny-exact": {
        "base": None,
        "system": "sharded_knn",
        "reference": "exact_knn",
        "dataset": {"rows": 16384, "queries": 512, "dim": 32,
                    "chunk": 4096},
        "search": {"k": 20, "batch": 128, "merge_mode": "auto"},
        # tiny readings on the CPU (seeds 11-16): the program's dist_error
        # 3.3e-6-4.4e-6, the reference at "high" 2.6e-5-4.2e-5; both read
        # rank_excess 0
        "check": {"sample": 128, "limits": {
            "invalid_rows": 0, "dist_error": 1.2e-5, "rank_excess": 1e-6}},
        "controls": {"high": {"reference_precision": "high"}},
    },
}


def make_copy(dest: str) -> str:
    """A copy of the committed benchmark (``BENCHMARK.json`` and
    ``benchmark/``) under ``dest``, with the tiny configurations and
    cells added as new files and entries. Returns the copy's root."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    gen = {"name": "low_rank_clusters", "n_centers": 96, "intrinsic": 16,
           "spread": 1.5}
    for name, over in TINY.items():
        over = dict(over)
        base_file = over.pop("base")
        base = (json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                            base_file)))
                if base_file else {"assumed": {"generator": gen},
                                   "check": {"limits": {}}})
        cfg = harness._merged(base, over)
        cfg["name"] = name
        path = os.path.join(dest, "benchmark", "configs", name + ".json")
        with open(path, "w") as f:
            json.dump(cfg, f)
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"benchmark/configs/{name}.json",
                                "reduced": [], "why": "test"})
    spec["workloads"] += [
        {"name": "tiny-ivfpq.batch", "config": "tiny-ivfpq",
         "traffic": "batch_closed", "chips": 1, "why": "test"},
        {"name": "tiny-ivfpq.served", "config": "tiny-ivfpq",
         "traffic": "tiny_poisson", "chips": 1, "why": "test"},
        {"name": "tiny-exact.batch", "config": "tiny-exact",
         "traffic": "batch_closed", "chips": 4, "why": "test"},
    ]
    with open(os.path.join(dest, "benchmark", "traffic",
                           "tiny_poisson.json"), "w") as f:
        json.dump({"loop": "open", "arrivals": "poisson", "rate_per_s": 100},
                  f)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(m["workloads"]) + [
                w["name"] for w in spec["workloads"]
                if w["name"].startswith("tiny")]
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dest


@pytest.fixture(scope="session")
def copy_root(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("bench")))


def run_tiny(root, workload, seed=2**33 + 7, seconds=1.0, trace=False,
             n_devices=1, **kw):
    """One run of a tiny cell of the copy on the CPU (the chip check is
    skipped; the rest of the run is the benchmark's own)."""
    import time

    import jax

    spec = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = harness.Cell(spec, workload, root=root)
    return harness.run_cell(cell, seed, seconds, trace,
                            jax.devices()[:n_devices], time.perf_counter(),
                            **kw)
