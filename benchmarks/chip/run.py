#!/usr/bin/env python3
"""Benchmark of the served SPECTRA scheduler on a TPU: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

See ``chipbench/harness.py``. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))
