"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, the ``benchmarks`` mains) call
:func:`use_compile_cache` once, before their first compile.  Library
modules and the test suite never call it.
"""
from __future__ import annotations

import os

#: the checkout root: ``src/repro/`` sits two levels below it
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing else is set here.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (git-ignored): a path that changed from run
    to run would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
