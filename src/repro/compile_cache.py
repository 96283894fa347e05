"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that compile for the accelerator (`chip_smoke.py`,
`benchmarks/run.py`, the device-engine example) call
`enable_compile_cache` before their first JAX compile, so a later run
in the same checkout reloads the compiled kernels instead of compiling
them again.  JAX keys cache entries on, among other things, the cache
path, so the path never moves: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, and nothing else
is set here), otherwise ``<checkout>/.jax_cache`` (git-ignored).  The
test suite never calls this, so tests compile with the cache off.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every compile is written, however quick: the device engine's chunk
    programs and the kernels each compile in about a second."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
