"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``repro.launch.train``, ``chip_smoke.py``,
``bench/run.py``): if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no directory is set here; otherwise the cache goes to
``.jax_cache/`` at the root of the checkout.  The path is fixed — never a
temporary name, a process id or the time — because it is part of what a
later process must find again.

Either way an entry's key includes the ops' metadata, which JAX leaves out
of it by default: the step's named scopes (``fed.oracle``, ...) live there
alone, and a profiler reads them from the compiled program, so a program
must never load an executable compiled from one whose scopes differ.
"""
from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/``
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory, key its
    entries by the ops' metadata too, and return that directory.  Touches
    no backend, so a parent that only starts children stays off the
    chip."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
