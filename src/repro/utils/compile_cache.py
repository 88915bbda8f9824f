"""The one location of JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call :func:`use_compile_cache` before their first compile, so repeated
runs of the same programs in one checkout reuse compiled executables.
Library modules never call it: importing ``repro`` changes no JAX
configuration.

``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is set in code
(JAX reads the variable itself). Otherwise the cache sits at one fixed,
git-ignored path in the checkout, :data:`CACHE_DIR` — fixed because the
path is part of what the cache is keyed on, so a directory that moved
between runs would never hit.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
