"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json`` at the repository
root.  The run fails, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T0 = time.perf_counter()  # set-up is timed from here

import pathlib  # noqa: E402
import sys  # noqa: E402

CHIP = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
