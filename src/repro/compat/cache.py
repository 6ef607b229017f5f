"""Where JAX's persistent compilation cache lives.

The cache key includes the cache directory, so the path must be fixed: a
directory built from a temp name, a pid or the time never hits again.
An operator-set ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it at
start-up and nothing here overrides it); otherwise the cache sits in the
checkout at ``.jax_cache`` (git-ignored). Only entry points call
:func:`enable_compile_cache` — importing this module changes nothing, and
the tests never turn the cache on.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping, Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory an entry point uses (no side effects)."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return the path. With the environment
    variable set, JAX already uses it and this sets nothing."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
