"""Where the scripts keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory; JAX reads the
variable itself and nothing here overrides it. Otherwise the cache lives at
``<checkout>/.jax_cache`` — a fixed path, because the path is part of what
makes a later run find the entries again. The library never sets a cache on
import; only the command-line scripts (``chip_smoke.py``,
``benchmarks/run.py``) call this.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
