"""Persistent XLA compilation cache at a fixed place.

A published-width edge round takes tens of seconds to compile, and every
fresh process on a chip machine starts cold.  JAX's persistent cache keeps
compiled programs on disk between processes, but only if it is pointed at
a directory that stays put.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache, resolved from this file (src/repro/launch/...), so every
# process of one checkout shares it whatever its working directory
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    changes nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    Call it before the process compiles anything: JAX decides once, at the
    first compile, whether the cache is in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
