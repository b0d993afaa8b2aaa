"""Where JAX's persistent compilation cache lives, for every entry point.

The launchers (``search``, ``serve_search``, ``serve_http``), the benchmark
runner and ``chip_smoke.py`` call :func:`enable_persistent_cache` before
their first compile, so a second run of the same programs on the same
machine loads executables instead of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX has already read it into
  ``jax_compilation_cache_dir``; no directory is set in code.
* unset: the cache goes to ``<checkout>/.jax_cache`` -- one fixed path,
  because the cache directory is part of what a later run must find again.

``JAX_ENABLE_COMPILATION_CACHE=false`` still turns the cache off (the test
suite sets it, so tests never write one).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root, three levels up.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Place the persistent compilation cache; returns its directory.

    Every compiled program is cached, however short its compile: the cost
    kernels and per-item aggregates compile in well under JAX's default
    one-second threshold, yet a cold process pays for dozens of them.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
