"""Where JAX's persistent compilation cache lives.

A compiled executable is keyed on, among other things, the directory it is
cached in, so the directory must not move between runs: it is either the
one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself and
nothing here overrides it) or the fixed ``.jax_cache`` inside the checkout,
which ``.gitignore`` lists.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the entry points cache compiled executables in."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return its directory.  Call before the first compile."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
