"""Persistent XLA compilation cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
variable itself and no other directory is set in code.  Otherwise the
cache lives at a fixed directory inside the checkout, ``<repo>/.jax_cache``
(git-ignored).  The path never derives from a temp name, a pid or the
time: a directory that moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on (call before the first compile);
    returns the directory it writes to."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
