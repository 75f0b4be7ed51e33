"""Where JAX keeps its persistent compilation cache.

Call ``configure_compile_cache()`` once, before the first compile.  The
rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here picks another directory; otherwise the cache lives at
the fixed path ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
The path is part of what a later run looks up, so it never carries a
temp name, pid or timestamp.  Every compile is cached, however short:
the kernels' Mosaic compiles take a second or two each, and there are
many of them.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
