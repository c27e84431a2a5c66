"""jit'd wrapper for the LRU scan kernel: padding + initial state handling."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.lru.kernel import TIME_TILE, lru_scan_padded


def lru_scan(a, b, h0=None, *, time_tile: int = TIME_TILE,
             interpret: bool | None = None):
    """h_t = a_t·h_{t−1} + b_t along axis 1. a, b: [B, S, C] fp32.

    Pads S to the time tile (a=1, b=0 padding is a no-op on the state) and C
    to the channel block.
    """
    B, S, C = a.shape
    tt = min(time_tile, max(8, S))
    pad_s = (-S) % tt
    from repro.kernels.lru.kernel import CHAN_BLOCK
    cb = min(CHAN_BLOCK, C)
    pad_c = (-C) % cb
    if h0 is None:
        h0 = jnp.zeros((B, C), jnp.float32)
    if pad_s or pad_c:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_c)),
                    constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_c)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_c)))
    out = lru_scan_padded(a.astype(jnp.float32), b.astype(jnp.float32),
                          h0.astype(jnp.float32), time_tile=tt,
                          interpret=interpret)
    return out[:, :S, :C]
