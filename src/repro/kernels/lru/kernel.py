"""RG-LRU gated linear recurrence scan — Pallas TPU kernel.

    h_t = a_t ⊙ h_{t−1} + b_t          (per-channel, per-batch)

On GPU this is typically a custom CUDA scan; the TPU adaptation keeps the
running state ``h`` resident in VMEM scratch while streaming (a, b) time
tiles HBM→VMEM: grid = (B, W_blocks, T_tiles) with the time dimension
innermost (TPU grids are sequential minor-to-major, so the scratch state
carries across time tiles). Within a tile the recurrence is an unrolled
loop over rows — sequential in time but fully vectorised over the channel
lanes, which matches the VPU's (8, 128) register tiling.

Compared with ``lax.associative_scan`` (O(log T) full-array passes through
HBM) the kernel reads a/b once and writes h once — the memory-roofline floor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

TIME_TILE = 128
CHAN_BLOCK = 512


def _lru_kernel(a_ref, b_ref, h0_ref, o_ref, state_ref, *, time_tile: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        state_ref[...] = h0_ref[...]

    h = state_ref[...]                                  # [1, C] fp32
    a = a_ref[0]                                        # [time_tile, C]
    b = b_ref[0]
    rows = []
    for t in range(time_tile):
        h = a[t][None, :] * h + b[t][None, :]
        rows.append(h)
    o_ref[0] = jnp.concatenate(rows, axis=0)
    state_ref[...] = h


@functools.partial(jax.jit, static_argnames=("time_tile", "interpret"))
def lru_scan_padded(a, b, h0, *, time_tile: int = TIME_TILE,
                    interpret: bool | None = None):
    """a, b: [B, S, C] fp32 (S divisible by time_tile); h0: [B, C].
    Returns h: [B, S, C] with h[:, t] = a[:,t]·h[:,t-1] + b[:,t], h[:,-1]=h0."""
    B, S, C = a.shape
    assert S % time_tile == 0, (S, time_tile)
    cb = min(CHAN_BLOCK, C)
    assert C % cb == 0, (C, cb)
    grid = (B, C // cb, S // time_tile)

    kernel = functools.partial(_lru_kernel, time_tile=time_tile)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, time_tile, cb), lambda bi, ci, ti: (bi, ti, ci)),
            pl.BlockSpec((1, time_tile, cb), lambda bi, ci, ti: (bi, ti, ci)),
            pl.BlockSpec((1, cb), lambda bi, ci, ti: (bi, ci)),
        ],
        out_specs=pl.BlockSpec((1, time_tile, cb), lambda bi, ci, ti: (bi, ti, ci)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, cb), jnp.float32)],
        name="lru_scan_padded",
        interpret=resolve_interpret(interpret),
    )(a, b, h0)
