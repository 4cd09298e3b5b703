"""Where JAX keeps its persistent compilation cache for the repo's chip
entry points (``chip_smoke.py``, ``benchmarks/run.py``).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
here overrides it.  Otherwise the cache goes to one fixed directory inside
the checkout, ``<repo>/.jax_cache``: the path is part of the cache key, so
a directory named after a temp file, a pid or the time would never hit.
"""

from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
