"""Run one benchmark cell and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the TPU chips the cell asks
for; anywhere else it exits non-zero without a result.  The last line of
standard output is the result object; the numbers compared for ``correct``
are the last lines of standard error.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

if __name__ == "__main__":
    from benchmarks.chip import harness
    sys.exit(harness.main(t_process=T_PROCESS))
