"""JAX's persistent compilation cache for the entry points.

`enable()` is called by the entry points (`chip_smoke.py`,
`launch/serve.py` and `launch/train.py` ``main``), never at library
import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here. Otherwise the cache goes to the fixed
``.jax_cache/`` at the root of the checkout (gitignored): a temp-, pid- or
time-derived directory would be new on every run, so nothing would hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory JAX uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
