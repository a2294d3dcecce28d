"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles for the chip (``chip_smoke.py``,
``python -m repro.launch.serve``, ``benchmarks/run.py``): when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here; otherwise the cache is ``<checkout>/.jax_cache``.  The path is part of
what a later process must find, so it is fixed: never a temporary
directory, a pid or a time.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: The repository checkout (``src/repro/launch/`` is three levels below).
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
