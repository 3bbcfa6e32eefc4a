"""Run one cell of the chip benchmark (BENCHMARK.json at the repo root):

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` are the last lines of standard error.
Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
