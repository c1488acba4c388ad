"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``chip_smoke.py``, the device-touching
tools' ``main`` and ``proc/daemon.py``): where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
is set in code; where it is not, the cache is ``<checkout>/.jax_cache``
(listed in ``.gitignore``).  The path is part of the cache key, so it
is fixed — never a temp name, a pid or a time.  JAX's own thresholds
(minimum entry size and compile time) are left alone: a cache that
kept every small CPU program of every test worker would only grow the
tree.  Called from entry points, never while a module is imported.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Return the active cache directory, pointing JAX at
    ``CHECKOUT_CACHE`` first when the environment names none."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
