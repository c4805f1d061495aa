"""Run one benchmark cell once and print its result as the last line.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks
for.  Exits 2, with no result line, when JAX sees no accelerator or too
few chips.  See harness.py for what a run does.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    import harness
    sys.exit(harness.main(t_start=T_START))
