"""Pure-jnp oracle for the fused STORM update kernel."""
from __future__ import annotations

import jax.numpy as jnp


def storm_update_ref(p, m, g_new, g_old, lr, decay):
    """Elementwise reference (fp32 accumulation, matching the kernel):

        p_new = p − lr·m
        m_new = g_new + decay·(m − g_old)
    """
    m32 = m.astype(jnp.float32)
    p_new = (p.astype(jnp.float32) - lr * m32).astype(p.dtype)
    m_new = (g_new.astype(jnp.float32)
             + decay * (m32 - g_old.astype(jnp.float32))).astype(m.dtype)
    return p_new, m_new


def _per_element(table, block):
    """Per-tile table [T] (or [T, R], one scalar per row) → per-element
    [T·block] (or [R, T·block]), aligned with the buffers."""
    t = jnp.asarray(table, jnp.float32)
    return jnp.repeat(jnp.moveaxis(t, 0, -1), block, axis=-1)


def storm3_update_ref(p, m, g_new, g_old, lrs, decays, block):
    """Triple-sequence reference: per-block (lr, decay) scalars expanded to
    per-element, then the same fp32 elementwise update as the kernel.
    Buffers [N] with tables [N // block], or [R, N] with [N // block, R].

    Single source of the jnp math — kernel.py's ``storm3_*_flat_jnp``
    lowerings (the off-TPU production path) delegate here, so the Pallas
    kernel stays the only independent implementation and the
    kernel-vs-ref test sweeps remain meaningful.
    """
    lr = _per_element(lrs, block)
    decay = _per_element(decays, block)
    m32 = m.astype(jnp.float32)
    p_new = (p.astype(jnp.float32) - lr * m32).astype(p.dtype)
    m_new = (g_new.astype(jnp.float32)
             + decay * (m32 - g_old.astype(jnp.float32))).astype(m.dtype)
    return p_new, m_new


def sgd3_step_ref(p, g, lrs, block):
    """Plain SGD reference: p_new = p − lr·g (fp32 accumulation)."""
    lr = _per_element(lrs, block)
    return (p.astype(jnp.float32) - lr * g.astype(jnp.float32)).astype(p.dtype)


def momsgd3_step_ref(p, m, g, lrs, betas, block):
    """Heavy-ball reference (fp32 accumulation, matching the kernel):

        m_new = β·m + g
        p_new = p − lr·m_new      (the *updated* momentum — FedAvg ordering;
                                   β = 0 degenerates to SGD: p − lr·g)
    """
    lr = _per_element(lrs, block)
    beta = _per_element(betas, block)
    m_new = beta * m.astype(jnp.float32) + g.astype(jnp.float32)
    p_new = (p.astype(jnp.float32) - lr * m_new).astype(p.dtype)
    return p_new, m_new.astype(m.dtype)


def storm3_step_ref(p, m, g_old, lrs, decays, block):
    """Half-step reference: p − lr·m and the partial momentum
    decay·(m − g_old) (the correction add happens post-communication)."""
    lr = _per_element(lrs, block)
    decay = _per_element(decays, block)
    m32 = m.astype(jnp.float32)
    p_new = (p.astype(jnp.float32) - lr * m32).astype(p.dtype)
    m_part = (decay * (m32 - g_old.astype(jnp.float32))).astype(m.dtype)
    return p_new, m_part


def quantpack_ref(x, block):
    """Per-tile symmetric int8 quantization of a flat [N] buffer
    (N a multiple of ``block``): each tile is scaled by its own
    absmax/127 and rounded (half-to-even, ``jnp.round`` — the kernel uses
    the same rounding so pack results are bit-identical).  A zero tile
    stores scale 0 and quantizes to zeros (the divisor is ``where``-guarded).

    Returns ``(q int8 [N], scales f32 [N // block])``.
    """
    t = x.astype(jnp.float32).reshape(-1, block)
    amax = jnp.max(jnp.abs(t), axis=-1)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(t / safe[:, None]), -127.0, 127.0).astype(jnp.int8)
    return q.reshape(x.shape), scale


def quantunpack_ref(q, scales, block):
    """Dequantize :func:`quantpack_ref` output back to f32 [N]:
    ``q · scale_tile`` (exact — each step is one f32 multiply)."""
    t = q.astype(jnp.float32).reshape(-1, block)
    return (t * scales.astype(jnp.float32)[:, None]).reshape(q.shape)
