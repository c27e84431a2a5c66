"""Block-wise (flash) attention with causal + sliding-window masking.

TPU adaptation of the long-context attention hot spot (gemma2 local layers,
recurrentgemma's 2048-token windows, 32k–500k contexts): instead of
materialising the [S, S] score matrix in HBM, q/k/v are streamed through VMEM
in MXU-aligned (BQ × BK) tiles with an online-softmax accumulator, and —
the structural win for sliding windows — **k-blocks entirely outside the
(causal, window) band are skipped via the grid index map**, so a W-token
window costs O(S·W) instead of O(S²).

Grid: (B·H, num_q_blocks, num_k_blocks); the innermost k dimension iterates
sequentially per q block (TPU grids execute minor-to-major sequentially, so
the VMEM accumulator carries across k steps).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  bq: int, bk: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # skip blocks fully outside the (causal, window) band
    first_q = qi * bq
    last_q = first_q + bq - 1
    first_k = ki * bk
    last_k = first_k + bk - 1
    in_band = True
    if causal:
        in_band = jnp.asarray(first_k <= last_q)
    if window and window > 0:
        in_band = jnp.logical_and(in_band, jnp.asarray(last_k > first_q - window))

    @pl.when(in_band)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # [bq, d]
        k = k_ref[0].astype(jnp.float32)                     # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if softcap and softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        ok = k_pos < kv_len                                  # padding mask
        if causal:
            ok &= k_pos <= q_pos
        if window and window > 0:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                                  # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0].astype(jnp.float32)                     # [bk, d]
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot(p.astype(v.dtype), v))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "bq", "bk",
                     "interpret"))
def flash_attention_bh(q, k, v, *, causal: bool = True, window: int = 0,
                       softcap: float = 0.0, scale: float = None,
                       bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                       interpret: bool | None = None):
    """q, k, v: [BH, S, D] (batch×heads flattened). S must divide by bq/bk."""
    BH, S, D = q.shape
    kv_len = k.shape[1]
    assert S % bq == 0 and kv_len % bk == 0, (S, kv_len, bq, bk)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    grid = (BH, S // bq, kv_len // bk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, kv_len=kv_len)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max m
            pltpu.VMEM((bq,), jnp.float32),      # running denom l
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        name="flash_attention_bh",
        interpret=resolve_interpret(interpret),
    )(q, k, v)
