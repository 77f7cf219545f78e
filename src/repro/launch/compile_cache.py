"""JAX's persistent compilation cache, at one fixed place.

A path that moves between runs (temporary, pid- or time-derived) never
finds what the last run cached, so the place is fixed.  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
    module sets nothing;
  * otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

``launch.serve``, ``chip_smoke.py`` and ``benchmarks/run.py`` call
``enable_compile_cache()`` before they compile anything.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def cache_dir() -> str:
    """Where compiled programs go under the rule above."""
    return os.environ.get(ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on at ``cache_dir()``; returns the path."""
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
