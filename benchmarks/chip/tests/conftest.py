"""The benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

They drive the harness at a small size through its Python entry
(``chipbench.cell.prepare`` / ``execute`` with the chip check skipped);
the command line refuses to run without a TPU.
"""

import os
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: the cells at a size a CPU test holds: the widths as configured, few
#: rows, XLA bodies instead of TPU kernels
SMALL = {"config": {"rows": 4096, "pallas": "never"}}
SMALL_SCORE = {"config": {"pallas": "never"},
               "traffic": {"rate_per_s": 20, "rows_max": 2048,
                           "pool_rows": 4096, "pad_to": 512,
                           "check_sample": 12}}


def small(workload: str) -> dict:
    return SMALL_SCORE if workload.endswith("-score") else SMALL


@pytest.fixture(autouse=True)
def _cache_off(tmp_path, monkeypatch):
    """Keep the tests' compiles out of the checkout's compile cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
