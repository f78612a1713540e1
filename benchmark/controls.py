#!/usr/bin/env python3
"""Readings that the ``check`` limits are set from, on the chip, in one
process (set-up is long, so many seeds share one start):

    python benchmark/controls.py --workload <cell> --seeds 1,2,3 \\
        --seconds 3 [--variant program|<control>]

``program`` runs the cell as committed (the lower readings). A control
named in the configuration's ``controls`` puts something else in the
program's place: ``overrides`` reruns the program with other settings
(its own lower-precision path), ``reference_precision`` puts the plain
reference itself there, computed in the precision below the one the
configuration states. Each run prints one JSON line with the compared
numbers; nothing here is a timing.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


class ReferenceSystem:
    """The configuration's plain reference in the program's place, at
    ``precision``: its expanded-form choice and values are the answers."""

    def __init__(self, precision: str, bench: str):
        from benchmark import harness

        self.precision = precision
        self.ref = harness.load_module("references", "exact_knn", bench)

    def __call__(self, cfg, shards, devices, annotate):
        self.k = int(cfg["search"]["k"])
        self.shards = shards
        self.build_s = None
        return self

    def stage(self, queries, batch: int) -> int:
        self.batches = [queries[j * batch:(j + 1) * batch]
                        for j in range(len(queries) // batch)]
        return len(self.batches)

    def call(self, j: int):
        v, i, _ = self.ref.knn(self.shards, self.batches[j], self.k,
                               precision=self.precision, margin=0)
        return v, i

    def release(self) -> None:
        self.shards = None


def faulty(base, fault: str):
    """The closed-loop system ``base`` with a fault planted where its
    answers are produced: ``altered`` changes one id of every batch,
    ``half`` answers the second half of every batch with the first
    half's rows."""
    import numpy as np

    class Faulty(base):
        def call(self, j):
            d, i = super().call(j)
            d, i = np.array(d), np.array(i)
            if fault == "altered":
                i[0, 0] = (i[0, 0] + 1) % 1000
            else:
                h = len(i) // 2
                d[h:2 * h], i[h:2 * h] = d[:h], i[:h]
            return d, i

    return Faulty


def variant_args(cell, variant: str) -> dict:
    """run_cell's keyword arguments that put ``variant`` in the program's
    place: the program itself, one of the configuration's ``controls``,
    or ``fault_altered`` / ``fault_half``."""
    from benchmark import harness

    if variant == "program":
        return {}
    if variant.startswith("fault_"):
        base = harness.load_module("systems", cell.config["system"],
                                   cell.bench).System
        return {"system_factory": faulty(base, variant[len("fault_"):])}
    spec = cell.config["controls"][variant]
    if "overrides" in spec:
        return {"config_overrides": spec["overrides"]}
    return {"system_factory": ReferenceSystem(spec["reference_precision"],
                                              cell.bench)}


def main(argv=None) -> int:
    from benchmark import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--variant", default="program")
    args = ap.parse_args(argv)
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(spec, args.workload)
    harness.use_compile_cache()
    devices = harness.chip_devices(cell.chips)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, devices, t0,
                               **variant_args(cell, args.variant))
        print(json.dumps({"workload": args.workload, "variant": args.variant,
                          "seed": seed, "correct": out["correct"],
                          "metrics": out["metrics"], "numbers": out["numbers"],
                          "check": out["check"]}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
