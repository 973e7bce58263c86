"""JAX's persistent compilation cache for the entry points.

Called from the ``__main__`` blocks of ``train.py`` and ``serve.py`` and
from ``chip_smoke.py``, never at import: a library import must not
change process-wide JAX configuration.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed, in-checkout and gitignored: the path is part of the cache key,
# so it is never derived from a temporary name, a pid or the time
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Keep compiled programs across processes.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    this sets nothing (returns ``None``); otherwise it points the cache
    at :data:`DEFAULT_DIR` and returns that path."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
