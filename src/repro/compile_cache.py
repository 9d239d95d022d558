"""JAX's persistent compilation cache, kept where a checkout finds it again.

Entry points that compile (``chip_smoke.py``, ``benchmarks/run.py``, the
launch CLIs) call :func:`enable` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and nothing here overrides it; otherwise the cache goes to a fixed
directory inside the checkout (``.jax_cache``, git-ignored).  The path
is part of each entry's key, so it never names a temp dir, a pid or a
time.
"""

from __future__ import annotations

import os
from pathlib import Path

#: fixed in-checkout cache directory (used when the environment sets none)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on for every compile, however short, and
    return its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
