"""JAX's persistent compilation cache, for the entry points.

The entry points (``chip_smoke.py``, ``python -m repro.launch.train``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start-up.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is set.  Otherwise the cache lives in ``<checkout>/.jax_cache``, a
fixed path, so a second run of the same program finds the first run's
executables.  Importing this module (or any library module) sets nothing.
"""

from __future__ import annotations

import os
import pathlib

#: root of the checkout this module belongs to (``src/repro/launch/..``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
