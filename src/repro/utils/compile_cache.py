"""Where JAX's persistent compilation cache lives.

Entries are found again only in the directory they were written to, so the
path must not move between runs: `JAX_COMPILATION_CACHE_DIR` wins when set;
otherwise the cache sits at one fixed, git-ignored directory of the
checkout, never at a temp name, a pid or a time. Entry points call
`enable_compile_cache()` first thing; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """`$JAX_COMPILATION_CACHE_DIR` if set (and non-empty), else the fixed
    `<checkout>/.jax_cache`."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `compile_cache_dir()` and
    return that path."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
