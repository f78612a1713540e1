#!/usr/bin/env python3
"""Run one benchmark cell once on the chip:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The last line of standard output is the result object; the numbers that
decided ``correct`` are the last lines of standard error. Exits non-zero,
with no result line, where JAX finds no TPU or fewer chips than the cell
asks for, or where the program under test is not in the checkout.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main() -> int:
    try:
        from benchmark import harness

        return harness.main(sys.argv[1:], T0)
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
