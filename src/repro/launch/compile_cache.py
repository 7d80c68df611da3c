"""JAX's persistent compilation cache at a fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives in ``<repo>/.jax_cache``:
a fixed path, because the path is part of the cache's key, so a directory
that moves from run to run never hits.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns the directory
    this call chose, or None where the environment already chose one."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
