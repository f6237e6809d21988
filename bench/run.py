"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; the
numbers compared for ``correct`` are the last lines of standard error.
A run without a TPU, or with fewer chips than the cell asks for, exits
nonzero and prints no result.  See bench/harness.py.
"""
import time

T_START = time.perf_counter()     # set-up counts from here, before JAX loads

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
