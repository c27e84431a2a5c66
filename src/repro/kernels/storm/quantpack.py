"""Per-tile int8 pack/unpack — Pallas TPU kernel for compressed comm.

The compressed communication policies (``repro.optim.flat.CompressCfg``)
move the client reductions in a narrow dtype.  bf16 is a plain cast; int8
needs a per-tile scale: each ``block``-sized tile of the flat buffer is
quantized symmetrically to ``q = round(x / (absmax/127))`` with its scale
stored alongside (one f32 per tile — 4/block bytes of overhead per
element).  Per-TILE granularity is deliberate: tiles are the substrate's
section/shard quantum, so the very same tile boundaries exist on the
unsharded buffer and on every ``shard_map`` chunk, and the quantization
error is independent of how the buffer is partitioned.

Layout contract (same as the storm3 kernels): flat [N] input with N a
multiple of ``block``, 1-D grid of (block,) VMEM tiles.  ``quantpack_flat``
emits the int8 payload plus the [N // block] scale vector; ``quantunpack_flat``
consumes them.  Scales move one per grid step through SMEM in both
directions (``kernel.TILE_SMEM``, like the per-tile lr/decay tables): a
``(1,)`` VMEM block is below the 128-lane tile Mosaic accepts.  The
``*_jnp`` lowerings delegate to ``ref.quantpack_ref`` /
``ref.quantunpack_ref`` — the single source of the jnp math — and are
bit-identical to the kernels (same ``jnp.round`` half-to-even rounding,
same ``where``-guarded zero-tile divisor), so the substrate can dispatch
per backend (``_dispatch`` in ``optim/flat.py``) without changing a single
communicated bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.storm.kernel import (BLOCK, TILE_SMEM,
                                        _resolve_block_launch, tile_scalars)


def _quantpack_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / safe), -127.0, 127.0).astype(jnp.int8)
    s_ref[0, 0] = scale


def _quantunpack_kernel(s_ref, q_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantpack_flat(x, *, block: int = BLOCK, interpret: bool | None = None):
    """Pack a flat f32 [N] buffer into (q int8 [N], scales f32 [N//block])."""
    n = x.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)
    bspec = pl.BlockSpec((block,), lambda i: (i,))
    q, s = pl.pallas_call(
        _quantpack_kernel,
        grid=grid,
        in_specs=[bspec],
        out_specs=[bspec, TILE_SMEM],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int8),
                   jax.ShapeDtypeStruct((n // block, 1, 1), jnp.float32)],
        name="quantpack_flat",
        interpret=_resolve_block_launch(interpret, block),
    )(x)
    return q, s.reshape(-1)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantunpack_flat(q, scales, *, block: int = BLOCK,
                     interpret: bool | None = None):
    """Dequantize (q int8 [N], scales f32 [N//block]) back to f32 [N]."""
    n = q.shape[0]
    assert n % block == 0, (n, block)
    assert scales.shape == (n // block,), (scales.shape, n, block)
    grid = (n // block,)
    bspec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _quantunpack_kernel,
        grid=grid,
        in_specs=[TILE_SMEM, bspec],
        out_specs=bspec,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        name="quantunpack_flat",
        interpret=_resolve_block_launch(interpret, block),
    )(tile_scalars(scales), q)


@functools.partial(jax.jit, static_argnames=("block",))
def quantpack_flat_jnp(x, *, block: int):
    from repro.kernels.storm.ref import quantpack_ref
    return quantpack_ref(x, block)


@functools.partial(jax.jit, static_argnames=("block",))
def quantunpack_flat_jnp(q, scales, *, block: int):
    from repro.kernels.storm.ref import quantunpack_ref
    return quantunpack_ref(q, scales, block)
