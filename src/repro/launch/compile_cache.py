"""JAX's persistent compilation cache for the launchers.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at one fixed
path inside the checkout (``<repo>/.jax_cache``, gitignored): the path is
part of what a later run must find again, so it never derives from a
temp name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / '.jax_cache'


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update('jax_enable_compilation_cache', True)
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update('jax_compilation_cache_dir', str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
