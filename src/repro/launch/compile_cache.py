"""Where JAX keeps its persistent compilation cache for this repo's programs.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call :func:`use_compile_cache` before their first compile.  Library modules
never do: importing one leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the default cache directory (listed in .gitignore): fixed, so every run
#: from this checkout finds what earlier runs compiled
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the
    repo root.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
