"""Where JAX keeps its persistent compilation cache for the repo's chip
entry points (``chip_smoke.py``, ``benchmarks/run.py``).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
here overrides it.  Otherwise the cache goes to one fixed directory inside
the checkout, ``<repo>/.jax_cache``: the path is part of the cache key, so
a directory named after a temp file, a pid or the time would never hit.

Either way the key includes each operation's metadata.  JAX leaves it out
by default, and the join's stage names (``repro.tracing``) live there: a
cache shared with a build that named no stages, or other ones, would serve
executables whose profiler traces carry the wrong names.
"""

from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its one directory, key
    it on operation metadata too, and return that directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
