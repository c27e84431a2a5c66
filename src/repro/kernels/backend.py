"""Backend policy shared by the Pallas kernels of every package."""
from __future__ import annotations

import jax


def resolve_interpret(interpret):
    """interpret=None → auto: compile on TPU, interpret elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
