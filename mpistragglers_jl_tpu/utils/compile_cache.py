"""Where XLA's persistent compilation cache lives: one rule for the
test suite, ``chip_smoke.py`` and the examples.

The directory is part of what a cached executable is found by, so it
must not move between runs. Where ``JAX_COMPILATION_CACHE_DIR`` is set
(the machine's operator placed the cache) JAX reads it by itself and
this module sets no directory; where it is not, the cache sits at one
fixed path inside the checkout, ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["wire_compile_cache"]

CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def wire_compile_cache(min_compile_secs: float = 0.1) -> str:
    """Turn the persistent compilation cache on and return the
    directory in use. Programs that compiled in under
    ``min_compile_secs`` are not persisted: the test suite keeps 0.1
    (persisting every tiny CPU program measured 11% slower cold and 27%
    faster warm on three test files, and the cold suite runs close to
    its cap); ``chip_smoke.py`` passes 0, so that a second run on the
    chip compiles nothing."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )
    return cache_dir
