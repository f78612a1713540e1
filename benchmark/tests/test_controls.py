"""Each configuration's control, at a size a test run can hold: the
program passes its limits and the control fails one of them. (The chip
readings at the cells' own size are in PERF.md.)"""

import os

import pytest

from benchmark import controls, harness
from conftest import run_tiny

CASES = [("tiny-ivfpq.batch", "int4_codes", 1),
         ("tiny-ivfpq.batch", "half_lut", 1),
         ("tiny-exact.batch", "high", 4)]


@pytest.mark.parametrize("workload,control,n_dev", CASES)
@pytest.mark.parametrize("seed", [2**33 + 21, 22])
def test_program_passes_control_fails(copy_root, workload, control, n_dev,
                                      seed):
    spec = harness.load_json(os.path.join(copy_root, "BENCHMARK.json"))
    cell = harness.Cell(spec, workload, root=copy_root)
    prog = run_tiny(copy_root, workload, seed=seed, seconds=0.3,
                    n_devices=n_dev)
    assert prog["correct"], prog["check"]
    ctl = run_tiny(copy_root, workload, seed=seed, seconds=0.3,
                   n_devices=n_dev, **controls.variant_args(cell, control))
    assert not ctl["correct"], ctl["check"]
