"""A configuration, a traffic mix and a per-layer metric are added as new
files and new ``BENCHMARK.json`` entries alone, in a copy of the
benchmark, and a run of the new cell finds and uses all three."""

import json
import os
import subprocess
import sys

from conftest import ROOT, make_copy

DRIVER = """
import json, sys, time
import jax
from benchmark import harness
spec = harness.load_json("BENCHMARK.json")
cell = harness.Cell(spec, "new-cell", root=".")
out = harness.run_cell(cell, 12345, 1.0, False, jax.devices()[:1],
                       time.perf_counter())
print(json.dumps(out))
"""


def test_new_config_mix_and_metric_are_files(tmp_path):
    root = make_copy(str(tmp_path))
    bench = os.path.join(root, "benchmark")
    before = {os.path.join(d, f): open(os.path.join(d, f), "rb").read()
              for d, _, fs in os.walk(bench) for f in fs}
    cfg = json.load(open(os.path.join(bench, "configs", "tiny-ivfpq.json")))
    cfg["name"] = "new-config"
    cfg["search"]["nprobe"] = 4
    cfg["check"]["limits"]["recall_gap"] = 0.5  # fewer probes, less recall
    with open(os.path.join(bench, "configs", "new-config.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "new_mix.json"), "w") as f:
        json.dump({"loop": "closed", "inflight": 1}, f)
    with open(os.path.join(bench, "metrics", "answers.new.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['n_answers'])\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "new-config", "source": "test",
                            "file": "benchmark/configs/new-config.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "new-cell", "config": "new-config",
                              "traffic": "new_mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "answers.new", "unit": "answers",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["new-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([root, ROOT]))
    p = subprocess.run([sys.executable, "-c", DRIVER], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["check"]
    assert out["metrics"]["answers.new"]["value"] > 0
    assert "setup_s" in out["metrics"] and "recall" in out["metrics"]
    # nothing that was there before was edited
    for path, blob in before.items():
        assert open(path, "rb").read() == blob, path
