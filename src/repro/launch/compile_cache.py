"""JAX's persistent compilation cache for the repo's entry points.

Called from each script's ``main()``, never at import, so importing the
library changes no JAX configuration.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: a fixed path, because the path is part of the
# cache's key and a directory that moves never hits.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    nothing is set here. Otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
