#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once; see ``chipbench.cell``.

    python3 benchmarks/chip/run.py --workload l2svm-mnist8m-fit \
        --seed 1 --seconds 10 --trace 0
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench.cell import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
