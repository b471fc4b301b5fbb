"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles for the device
(``chip_smoke.py``, ``bench.py``, ``vstart --serve``, the bench CLIs):
``JAX_COMPILATION_CACHE_DIR`` wins when the environment sets it (JAX
reads it itself — nothing is set in code); otherwise the cache sits at
``<checkout>/.jax_cache``. The path is part of the cache key's
environment, so it is never a temp name, a pid or a time.
"""

from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
