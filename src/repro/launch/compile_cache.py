"""JAX's persistent compilation cache for the entry points.

A TPU run compiles every program from cold unless a compile cache is on:
minutes for a 22-layer model. The entry points (``chip_smoke.py``,
``launch/train.py``, ``benchmarks/run.py``, ``examples/serve_lm.py``) call
``enable_compile_cache()`` once at start-up; importing ``repro`` never
turns the cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and this sets no other directory. Otherwise the cache lives at one
fixed path inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``): the directory is part of what a later run must find
again, so it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
