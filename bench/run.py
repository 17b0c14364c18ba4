"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are data under
``bench/`` found by the names in ``BENCHMARK.json``; see
``bench/benchkit/harness.py``.  Exits non-zero, printing no result,
without a TPU or with fewer chips than the cell asks for.
"""
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchkit.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=T_START))
