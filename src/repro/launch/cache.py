"""Where the persistent XLA compilation cache lives, for every entry point.

Every ``main()`` of the launchers, ``benchmarks.run``, ``chip_smoke.py``
and the test suite's ``conftest.py`` call ``use_compile_cache()`` first.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: ``<checkout>/.jax_cache``: a fixed path, because the cache directory
#: is part of what a cached entry is found by — a moving one never hits
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled programs in ``CACHE_DIR``, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX then reads that variable
    itself, and this sets nothing."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
