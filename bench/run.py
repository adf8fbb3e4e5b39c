#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1
    JAX_PLATFORMS=cpu python bench/run.py --workload <cell> --seed 1 \
        --seconds 2 --trace 0 --rehearse --rows 12000

A rehearsal runs on the CPU at ``--rows`` of the dataset's fact rows, its
other tables cut as the dataset module says.  Without ``--rehearse`` a host
whose JAX finds no TPU, or fewer chips than the cell asks for, fails at
once and prints no result.  Everything runs in this one process.  See
``bench/harness.py`` for what a run does.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    try:
        code = main(sys.argv[1:], t_start=T_START)
    except BaseException:  # noqa: BLE001 - a failed run prints no result
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the warehouse's worker threads must not hold the exit open
    os._exit(code)
