"""graftcheck --kernels suite: K001–K005 on one-violation fixture
twins, the DMA walker's path semantics, the interpret-mode VMEM sweep
(accountant bounds, alignment, family coverage), the artifact gate,
the repo gate under the committed baseline, the non-vacuity floors,
and the CLI/queue kernelcheck contract."""
import json
import logging
import os
import re
import sys

import pytest
from graftcheck_util import (REPO, check_suppression, check_twin,
                             fixture_mod as _mod, inject, run_cli, tmp_mod)

from raft_tpu.analysis import (kernel_stats, kernel_vmem_audit,
                               load_baseline, run_artifacts, run_kernels,
                               split_by_baseline)
from raft_tpu.analysis.kernels import (KERNEL_DRIFT_TOLERANCE, KERNEL_RULES,
                                       _numeric_alignment,
                                       _reset_kernel_warn,
                                       rule_carry_invariance,
                                       rule_dma_pairing,
                                       rule_interpret_divergence,
                                       rule_tile_alignment,
                                       rule_vmem_accounting)

RULES = {"K001": rule_dma_pairing, "K002": rule_vmem_accounting,
         "K003": rule_tile_alignment, "K004": rule_interpret_divergence,
         "K005": rule_carry_invariance}

_PALLAS_HEADER = (
    "from jax.experimental import pallas as pl  # noqa: F401\n"
    "from jax.experimental.pallas import tpu as pltpu\n\n\n")


# ------------------------------------------------------------ K-rule twins

@pytest.mark.parametrize("rule_id,stem,expect_qual", [
    ("K001", "k001", "leaky_kernel"),
    ("K002", "k002", "doubled"),
    ("K003", "k003", "_acc_kernel"),
    ("K004", "k004", "dispatch"),
    ("K005", "k005", "scan_rows"),
], ids=list(RULES))
def test_rule_flags_bad_and_passes_clean(rule_id, stem, expect_qual):
    check_twin(RULES[rule_id], rule_id, stem, expect_qual)


def test_clean_twins_pass_every_kernel_rule():
    for stem in ("k001", "k002", "k003", "k004", "k005"):
        mod = _mod(f"{stem}_clean.py")
        for rule in KERNEL_RULES:
            assert rule(mod) == [], (stem, rule.__name__)


@pytest.mark.parametrize("rule_id,fname,anchor", [
    ("K001", "k001_bad.py", "cp.start()"),
    ("K002", "k002_bad.py", "return pl.pallas_call("),
    ("K003", "k003_bad.py",
     "out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),"),
    ("K004", "k004_bad.py", "if interpret:"),
    ("K005", "k005_bad.py", "return (acc + x[i], best, i)"),
], ids=list(RULES))
def test_inline_suppression(tmp_path, rule_id, fname, anchor):
    check_suppression(RULES[rule_id], tmp_path, fname, anchor, rule_id)


# -------------------------------------------- K001 DMA walker semantics

def test_k001_double_start_without_wait(tmp_path):
    src = _PALLAS_HEADER + (
        "def kernel(a, b, sem):\n"
        "    cp = pltpu.make_async_copy(a, b, sem)\n"
        "    cp.start()\n"
        "    cp.start()\n"
        "    cp.wait()\n"
    )
    mod = tmp_mod(tmp_path, "double.py", src)
    found = rule_dma_pairing(mod)
    assert [(f.rule, f.qualname) for f in found] == [("K001", "kernel")]
    assert "started twice" in found[0].message


def test_k001_unbound_start_can_never_be_awaited(tmp_path):
    src = _PALLAS_HEADER + (
        "def kernel(a, b, sem):\n"
        "    pltpu.make_async_copy(a, b, sem).start()\n"
    )
    mod = tmp_mod(tmp_path, "unbound.py", src)
    found = rule_dma_pairing(mod)
    assert [(f.rule, f.qualname) for f in found] == [("K001", "kernel")]
    assert "unbound" in found[0].message


def test_k001_return_before_wait_is_an_exit_path(tmp_path):
    src = _PALLAS_HEADER + (
        "def kernel(a, b, sem, flag):\n"
        "    cp = pltpu.make_async_copy(a, b, sem)\n"
        "    cp.start()\n"
        "    if flag:\n"
        "        return 0\n"
        "    cp.wait()\n"
        "    return 1\n"
    )
    mod = tmp_mod(tmp_path, "early.py", src)
    found = rule_dma_pairing(mod)
    assert [(f.rule, f.qualname) for f in found] == [("K001", "kernel")]
    assert "no matching .wait()" in found[0].message


def test_k001_loop_body_start_without_wait_leaks(tmp_path):
    # one iteration starts a copy the next iteration's start clobbers
    src = _PALLAS_HEADER + (
        "def kernel(a, b, sem, rows):\n"
        "    for i in rows:\n"
        "        cp = pltpu.make_async_copy(a.at[i], b.at[i], sem)\n"
        "        cp.start()\n"
    )
    mod = tmp_mod(tmp_path, "loop.py", src)
    found = rule_dma_pairing(mod)
    assert [(f.rule, f.qualname) for f in found] == [("K001", "kernel")]


def test_k001_wait_only_descriptor_is_the_legal_idiom(tmp_path):
    src = _PALLAS_HEADER + (
        "def kernel(a, b, sem):\n"
        "    cp = pltpu.make_async_copy(a, b, sem)\n"
        "    cp.wait()\n"
    )
    assert rule_dma_pairing(tmp_mod(tmp_path, "waitonly.py", src)) == []


def test_k001_semaphore_imbalance(tmp_path):
    src = _PALLAS_HEADER + (
        "def kernel(left, right):\n"
        "    bar = pltpu.get_barrier_semaphore()\n"
        "    pltpu.semaphore_signal(bar, device_id=left)\n"
        "    pltpu.semaphore_signal(bar, device_id=right)\n"
        "    pltpu.semaphore_wait(bar, 3)\n"
    )
    mod = tmp_mod(tmp_path, "sem.py", src)
    found = rule_dma_pairing(mod)
    assert [(f.rule, f.qualname) for f in found] == [("K001", "kernel")]
    assert "2 signal(s) vs wait amount 3" in found[0].message


def test_k001_dynamic_wait_amount_is_not_statically_judged(tmp_path):
    src = _PALLAS_HEADER + (
        "def kernel(n):\n"
        "    bar = pltpu.get_barrier_semaphore()\n"
        "    pltpu.semaphore_signal(bar)\n"
        "    pltpu.semaphore_wait(bar, n)\n"
    )
    assert rule_dma_pairing(tmp_mod(tmp_path, "dyn.py", src)) == []


# ------------------------------------------------- K003/K004/K005 extras

def test_k003_literal_unaligned_block_dims(tmp_path):
    src = (
        "from jax.experimental import pallas as pl\n\n\n"
        "def plan(x):\n"
        "    return pl.BlockSpec((7, 100), lambda i: (i, 0))\n"
    )
    mod = tmp_mod(tmp_path, "unaligned.py", src)
    found = rule_tile_alignment(mod)
    assert [(f.rule, f.qualname) for f in found] == [("K003", "plan")]
    assert "lane dim 100" in found[0].message
    assert "sublane dim 7" in found[0].message


def test_k003_numeric_alignment_tolerates_subtile_dims():
    # (1, 96) is under one (8, 128) tile: Mosaic pads it — no finding;
    # (16, 640) is multi-tile and aligned; (24, 384) fine; (16, 200) bad
    assert _numeric_alignment([("in", (1, 96)), ("in", (16, 640)),
                               ("out", (24, 384))]) == []
    bad = _numeric_alignment([("in", (16, 200))])
    assert len(bad) == 1 and "lane dim 200" in bad[0]


_K003_GRID_SPEC_SRC = _PALLAS_HEADER + (
    "import jax\n\n\n"
    "def _merge(o_ref, x, first):\n"
    "    @pl.when(first)\n"
    "    def _():\n"
    "        o_ref[...] = x\n\n"
    "    @pl.when(jax.numpy.logical_not(first))\n"
    "    def _():\n"
    "        o_ref[...] = o_ref[...] + x\n\n\n"
    "def _tile_kernel(p_ref, x_ref, o_ref):\n"
    "    o_ref[...] = x_ref[...]\n\n\n"
    "def _acc_kernel(p_ref, x_ref, o_ref):\n"
    "    j = pl.program_id(1)\n"
    "    {acc}\n\n\n"
    "def tiles(p, x):\n"
    "    grid_spec = pltpu.PrefetchScalarGridSpec(\n"
    "        num_scalar_prefetch=1, grid=(4, 4),\n"
    "        in_specs=[pl.BlockSpec((8, 128), lambda i, j, p: (i, j))],\n"
    "        out_specs=pl.BlockSpec((8, 128), lambda i, j, p: (i, j)))\n"
    "    return pl.pallas_call(_tile_kernel, grid_spec=grid_spec,\n"
    "                          out_shape=x)(p, x)\n\n\n"
    "def reduce_cols(p, x):\n"
    "    grid_spec = pltpu.PrefetchScalarGridSpec(\n"
    "        num_scalar_prefetch=1, grid=(4, 4),\n"
    "        in_specs=[pl.BlockSpec((8, 128), lambda i, j, p: (i, j))],\n"
    "        out_specs=pl.BlockSpec((8, 128), lambda i, j, p: (i, 0)))\n"
    "    return pl.pallas_call(_acc_kernel, grid_spec=grid_spec,\n"
    "                          out_shape=x)(p, x)\n"
)


@pytest.mark.parametrize("acc,flagged", [
    ("o_ref[...] = o_ref[...] + x_ref[...]", True),
    ("_merge(o_ref, x_ref[...], j == 0)", False),
    ("_merge(o_ref, x_ref[...], j == 1)", True),
], ids=["no_init", "init_in_helper", "helper_guard_not_first"])
def test_k003_revisit_init_reads_each_sites_grid_spec(tmp_path, acc,
                                                      flagged):
    # two wrappers bind the same grid_spec name: each pallas_call is
    # checked against its own, so the revisiting reduce_cols is seen; a
    # first-visit init may sit in a helper guarded on its parameter
    mod = tmp_mod(tmp_path, "two_specs.py",
                  _K003_GRID_SPEC_SRC.format(acc=acc))
    found = [(f.rule, f.qualname) for f in rule_tile_alignment(mod)]
    assert found == ([("K003", "_acc_kernel")] if flagged else []), found


def test_k004_passthrough_kwarg_is_not_a_divergence(tmp_path):
    src = (
        "from jax.experimental import pallas as pl  # noqa: F401\n\n\n"
        "def run(kernel_fn, interpret=False):\n"
        "    return kernel_fn(interpret=interpret)\n"
    )
    assert rule_interpret_divergence(
        tmp_mod(tmp_path, "pass.py", src)) == []


def test_k004_not_interpret_expression_is_flagged(tmp_path):
    src = (
        "from jax.experimental import pallas as pl  # noqa: F401\n\n\n"
        "def run(kernel_fn, interpret=False):\n"
        "    return kernel_fn(barrier=not interpret)\n"
    )
    found = rule_interpret_divergence(tmp_mod(tmp_path, "notkw.py", src))
    assert [(f.rule, f.qualname) for f in found] == [("K004", "run")]


def test_k005_lambda_body_arity_mismatch(tmp_path):
    src = (
        "import jax\n"
        "from jax.experimental import pallas as pl  # noqa: F401\n\n\n"
        "def drain(x):\n"
        "    return jax.lax.while_loop(\n"
        "        lambda c: c[0] < 4,\n"
        "        lambda c: (c[0] + 1, c[1], 0),\n"
        "        (0, x),\n"
        "    )\n"
    )
    found = rule_carry_invariance(tmp_mod(tmp_path, "lam.py", src))
    assert [(f.rule, f.qualname) for f in found] == [("K005", "drain")]
    assert "init carries 2" in found[0].message


def test_k005_starred_init_is_out_of_static_reach(tmp_path):
    src = (
        "import jax\n"
        "from jax.experimental import pallas as pl  # noqa: F401\n\n\n"
        "def step(x, carry):\n"
        "    return jax.lax.fori_loop(\n"
        "        0, 4, lambda i, c: (c[0], c[1], 0), (*carry, 0))\n"
    )
    assert rule_carry_invariance(tmp_mod(tmp_path, "star.py", src)) == []


# ----------------------------------------- the interpret-mode VMEM sweep

@pytest.fixture(scope="module")
def sweep():
    return kernel_vmem_audit()


def test_sweep_covers_every_family_at_three_shapes(sweep):
    results, _ = sweep
    by_family = {}
    for r in results:
        by_family.setdefault(r.family, []).append(r)
    assert set(by_family) == {"l2", "ivf", "pq", "cagra", "ring"}
    for family, rows in by_family.items():
        assert len(rows) >= 3, family


def test_sweep_is_clean_and_accountants_bound_the_live_set(sweep):
    results, findings = sweep
    assert findings == [], "\n".join(f.format() for f in findings)
    for r in results:
        assert r.ok, (r.family, r.point, r.note)
        if r.family == "ring":
            assert "2 DMA semaphores" in r.note
            continue
        # the crash direction: the committed accountant must bound the
        # captured block+scratch live set from above, within tolerance
        assert r.measured_bytes > 0, (r.family, r.point)
        assert r.accountant_bytes >= r.measured_bytes, (r.family, r.point)
        assert r.ratio <= KERNEL_DRIFT_TOLERANCE, (r.family, r.point,
                                                   r.ratio)


def test_sweep_tiles_come_from_the_captured_call(sweep):
    results, _ = sweep
    tiled = [r for r in results if r.family in ("l2", "ivf", "pq", "cagra")]
    for r in tiled:
        assert re.match(r"^(tm=\d+,tn=\d+|pad_tile=\d+|ct=\d+)$", r.tiles), \
            (r.family, r.tiles)


def test_sweep_warns_once_when_pallas_is_unavailable(monkeypatch, caplog):
    import jax.experimental
    _reset_kernel_warn()
    # both halves matter: `from jax.experimental import pallas` resolves
    # via getattr on the parent package when it can, and only falls back
    # to sys.modules when the attribute is gone
    monkeypatch.delattr(jax.experimental, "pallas", raising=False)
    monkeypatch.setitem(sys.modules, "jax.experimental.pallas", None)
    with caplog.at_level(logging.WARNING, "raft_tpu.analysis.kernels"):
        assert kernel_vmem_audit() == ([], [])
        assert kernel_vmem_audit() == ([], [])
    skips = [r for r in caplog.records if "sweep skipped" in r.message]
    assert len(skips) == 1  # warn-once
    _reset_kernel_warn()


# ------------------------------------------------------ the artifact gate

def test_artifacts_gate_is_clean_and_reports_no_stale_probe():
    # every committed artifact loads under its reader, and none is a
    # kernel-verdict probe: kernel and k choices live in code
    findings, report = run_artifacts(REPO)
    assert findings == [], "\n".join(f.format() for f in findings)
    assert not [ln for ln in report if "STALE pre-v3" in ln]


def test_artifacts_gate_flags_unparseable_json(tmp_path):
    (tmp_path / "BROKEN.json").write_text("{not json")
    findings, _ = run_artifacts(str(tmp_path))
    assert any(f.file == "BROKEN.json" and "does not parse" in f.message
               for f in findings)


# --------------------------------------------------------------- the gate

def test_repo_is_clean_under_committed_baseline():
    findings = run_kernels(REPO)
    baseline = load_baseline(os.path.join(REPO, "graftcheck_baseline.json"))
    new, suppressed = split_by_baseline(findings, baseline)
    assert new == [], "\n".join(f.format() for f in new)
    # the two deliberate interpret divergences stay enumerated
    assert {(f.rule, f.qualname) for f in suppressed} == {
        ("K004", "pallas_ring_shift"),
        ("K004", "fused_dispatch_explained")}


def test_kernel_scan_is_not_vacuous():
    # a resolver regression must not pass as "zero findings" silently:
    # the scan must have actually seen the fused engines
    s = kernel_stats(REPO)
    assert s["modules"] >= 1, s
    assert s["pallas_calls"] >= 7, s
    assert s["fused_kernels"] >= 4, s
    assert s["dma_sites"] >= 10, s


# ------------------------------------------------------------ CLI contract

def test_cli_kernels_nonzero_on_injected_violation(tmp_path):
    root = inject(tmp_path, "k001_bad.py")
    proc = run_cli("--root", root, "--no-baseline", "--kernels",
                   "--no-kernel-sweep")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "K001" in proc.stdout and "leaky_kernel" in proc.stdout
    assert "[kernels]" in proc.stdout  # the scan stats line


def test_cli_without_kernels_skips_k_rules(tmp_path):
    root = inject(tmp_path, "k001_bad.py")
    proc = run_cli("--root", root, "--no-baseline")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "K001" not in proc.stdout


def test_cli_no_kernel_sweep_requires_kernels():
    proc = run_cli("--no-kernel-sweep")
    assert proc.returncode == 2
    assert "--no-kernel-sweep requires --kernels" in proc.stderr


def test_cli_json_dump_carries_kernel_findings(tmp_path):
    root = inject(tmp_path, "k004_bad.py")
    out = tmp_path / "findings.json"
    proc = run_cli("--root", root, "--no-baseline", "--kernels",
                   "--no-kernel-sweep", "-q", "--json", str(out))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    (f,) = [e for e in doc["findings"] if e["rule"] == "K004"]
    assert f["qualname"] == "dispatch" and f["baselined"] is False
    assert f["file"].endswith("injected.py") and f["line"] > 0


def test_cli_artifacts_gate_runs_clean_on_the_repo():
    proc = run_cli("--artifacts")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "STALE pre-v3" not in proc.stdout
    assert "[artifacts]" in proc.stdout
