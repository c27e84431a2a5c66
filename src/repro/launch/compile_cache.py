"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``repro.launch.train``, ``chip_smoke.py``):
if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here; otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout.  The path is fixed — never a temporary name, a process id or the
time — because it is part of what a later process must find again.
"""
from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/``
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Touches no backend, so a parent that only starts
    children stays off the chip."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
