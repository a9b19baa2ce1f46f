"""Where JAX keeps its persistent compilation cache between runs."""
from __future__ import annotations

import os
from pathlib import Path

# One fixed directory inside the checkout (listed in .gitignore): the
# cache is keyed by its path, so a path that moved between runs would
# never hit.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled programs in :data:`CACHE_DIR`, unless the
    ``JAX_COMPILATION_CACHE_DIR`` environment variable already places the
    cache (JAX reads it itself).  Call from entry points, not on import."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
