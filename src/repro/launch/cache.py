"""Where the entry points keep JAX's persistent compilation cache."""

from __future__ import annotations

import os
from pathlib import Path

#: the repository root (``src/repro/launch/cache.py`` -> three levels up).
REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    that setting stands: nothing here overrides it.  Otherwise the cache goes
    to ``.jax_cache/`` at the repository root — a fixed path, because the
    path is part of what a later run must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
