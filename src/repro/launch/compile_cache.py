"""Where JAX's persistent compilation cache lives.

Call `enable_compile_cache()` from a program's `main()`, never at
import time: importing a module must not change JAX's configuration.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path, because the cache directory is
# part of what a later run must find again (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    no other path is set here; otherwise the cache goes to the
    checkout's ``.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
