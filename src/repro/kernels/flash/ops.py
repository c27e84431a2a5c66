"""jit'd wrapper: [B, S, H, D] attention → flash kernel on [B·H, S, D].

Pads the sequence to the tile size and flattens (batch, heads); this is the
call site used by ``repro.models.layers.attention`` when the trainer's
``use_flash`` flag is enabled.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash.kernel import DEFAULT_BK, DEFAULT_BQ, flash_attention_bh


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float = None,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool | None = None):
    """q, k, v: [B, S, H, D] (kv heads already repeated to H). Returns the
    attention output in the same layout."""
    B, S, H, D = q.shape
    bq = min(bq, max(16, 1 << (S - 1).bit_length() if S < bq else bq))
    bk = min(bk, bq)
    pad = (-S) % bq
    padk = (-S) % bk

    def prep(x, p):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, S, D)
        if p:
            x = jnp.pad(x, ((0, 0), (0, p), (0, 0)))
        return x

    qf = prep(q, pad)
    kf = prep(k, padk)
    vf = prep(v, padk)
    out = flash_attention_bh(qf, kf, vf, causal=causal, window=window,
                             softcap=softcap, scale=scale, bq=bq, bk=bk,
                             interpret=interpret)
    out = out[:, :S, :].reshape(B, H, S, D)
    return jnp.transpose(out, (0, 2, 1, 3))
