"""The committed ``BENCHMARK.json`` resolves each cell's configuration,
traffic and metric readers by name (``benchmark/harness.py``), and the
sift1m-ivfpq configuration states the precision its search computes in."""

import os

import jax
import pytest

from benchmark import harness


@pytest.fixture(scope="module")
def spec():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell_name,config,chips,end_to_end,per_layer", [
    ("ivfpq-sift1m-batch", "sift1m-ivfpq", 1,
     {"qps", "recall", "setup_s"},
     {"ivf_pq_search_roofline", "build_s", "idle_share.batch"}),
    ("deep100m-exact-4chip", "deep100m-exact", 4,
     {"qps", "recall", "setup_s"},
     {"sharded_knn_roofline", "merge_ms.sharded", "idle_share.batch"}),
])
def test_cell_resolves_from_committed_spec(spec, cell_name, config, chips,
                                           end_to_end, per_layer):
    cell = harness.Cell(spec, cell_name)
    assert cell.chips == chips
    assert cell.config["name"] == cell.config_entry["name"] == config
    assert cell.traffic["loop"] == "closed"
    assert {m["name"] for m in cell.end_to_end} == end_to_end
    assert {m["name"] for m in cell.per_layer} == per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m["name"]).read), m["name"]
    harness.load_module("systems", cell.config["system"])
    harness.load_module("references", cell.config["reference"])


def test_ivfpq_cell_is_the_published_deployment(spec):
    """raft-ann-bench's raft_ivf_pq.d64b8n1024 at SIFT1M's sizes, nothing
    cut, held to the float64 ADC distance of its own codes."""
    cell = harness.Cell(spec, "ivfpq-sift1m-batch")
    cfg = cell.config
    assert cell.config_entry["reduced"] == []
    assert (cfg["dataset"]["rows"], cfg["dataset"]["dim"],
            cfg["dataset"]["queries"]) == (1_000_000, 128, 10_000)
    assert cfg["index"] == {"nlist": 1024, "pq_dim": 64, "pq_bits": 8,
                            "niter": 20}
    assert (cfg["search"]["nprobe"], cfg["search"]["k"]) == (32, 10)
    assert cfg["check"]["adc"] == "ivf_pq_adc"
    assert cfg["check"]["limits"]["adc_error"] == 1e-4


@pytest.mark.parametrize("variant,want", [
    (None, jax.lax.Precision.HIGHEST),
    ("half_lut", jax.lax.Precision.DEFAULT),
])
def test_ivfpq_cell_search_precision(spec, variant, want):
    """The configuration's float LUT and internal distances make the
    search contract at HIGHEST; its half_lut control (bfloat16) keeps the
    one-pass DEFAULT, so the control still sits below the stated
    precision."""
    from raft_tpu.neighbors import ivf_pq

    cell = harness.Cell(spec, "ivfpq-sift1m-batch")
    cfg = cell.config
    if variant is not None:
        cfg = harness._merged(cfg, cfg["controls"][variant]["overrides"])
    system = harness.load_module("systems", cfg["system"])
    _, params = system._params(cfg)
    assert ivf_pq.contraction_precision(
        params.scan_cache_dtype, params.internal_distance_dtype) == want
    assert ivf_pq.contraction_precision(
        params.lut_dtype, params.internal_distance_dtype) == want
