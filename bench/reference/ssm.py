"""Plain float32 reference of a Mamba-2 language model (arXiv:2405.21060):
pre-norm SSD mixer blocks with a residual, no MLP.

Per block: ``[z, x, B, C, dt] = rmsnorm(h) W_in``; ``x, B, C`` go through a
causal depthwise convolution and SiLU; ``dt = softplus(dt + dt_bias)``;
the scan ``s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t``, ``y_t = C_t s_t +
D x_t`` with ``A = -exp(A_log)`` (one head per ``ssm_head_dim`` channels, one
B/C group); then ``rmsnorm(y * silu(z)) W_out``.  The scan is evaluated in
the chunked form of the paper's minimal SSD listing, exact in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import common as c


def _dims(s):
    d_inner = s["ssm_heads"] * s["ssm_head_dim"]
    return d_inner, s["ssm_state"], d_inner + 2 * s["ssm_state"]


def init_layer(key, s, dtype):
    d = s["d_model"]
    d_inner, n, conv_dim = _dims(s)
    h = s["ssm_heads"]
    ks = jax.random.split(key, 5)
    return {"0_ssm": {"ssm": {
        "ln": c.norm_init(d, dtype),
        "in_proj": c.dense(ks[0], (d, 2 * d_inner + 2 * n + h), dtype),
        "conv_w": c.normal(ks[1], (s["conv_width"], conv_dim), 0.1, dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "out_ln": c.norm_init(d_inner, dtype),
        "out_proj": c.dense(ks[2], (d_inner, d), dtype,
                            scale=1.0 / math.sqrt(d_inner)),
    }}}


def init(key, sizes, dtype):
    return c.lm_init(key, sizes, dtype, init_layer)


def _segsum(a):
    """[..., Q] -> [..., Q, Q]: sum of a[j+1..i] below the diagonal, -inf
    above it."""
    q = a.shape[-1]
    x = jnp.broadcast_to(a[..., None], a.shape + (q,))
    x = jnp.where(jnp.tril(jnp.ones((q, q), bool), -1), x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((q, q), bool)), x, -jnp.inf)


def ssd(x, a, bm, cm, chunk, ar):
    """y_t = sum_{s<=t} C_t.B_s exp(a_{s+1} + ... + a_t) x_s.
    x [B, L, H, P] (already weighted by dt), a [B, L, H], bm/cm [B, L, N]."""
    b, l, h, p = x.shape
    q = min(chunk, l)
    pad = -l % q
    if pad:
        x, a, bm, cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                        for t in (x, a, bm, cm))
    nc = x.shape[1] // q
    x = x.reshape(b, nc, q, h, p)
    a = a.reshape(b, nc, q, h).transpose(0, 3, 1, 2)          # [B,H,C,Q]
    bm = bm.reshape(b, nc, q, -1)
    cm = cm.reshape(b, nc, q, -1)
    cum = jnp.cumsum(a, axis=-1)
    y_in = ar.einsum("bcln,bcsn,bhcls,bcshp->bclhp", cm, bm,
                     jnp.exp(_segsum(a)), x)
    states = ar.einsum("bcln,bhcl,bclhp->bchpn", bm,
                       jnp.exp(cum[..., -1:] - cum), x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    between = jnp.exp(_segsum(jnp.pad(cum[..., -1], ((0, 0), (0, 0),
                                                     (1, 0)))))
    states = ar.einsum("bhzc,bchpn->bzhpn", between, states)[:, :-1]
    y_out = ar.einsum("bcln,bchpn,bhcl->bclhp", cm, states, jnp.exp(cum))
    return (y_in + y_out).reshape(b, nc * q, h, p)[:, :l]


def layer(p, x, s, ar):
    p = p["0_ssm"]["ssm"]
    b, l, _ = x.shape
    d_inner, n, _ = _dims(s)
    hh, pp = s["ssm_heads"], s["ssm_head_dim"]
    proj = ar.mm(c.rmsnorm(p["ln"], x, s["norm_eps"]), p["in_proj"])
    z, xbc, dt = (proj[..., :d_inner], proj[..., d_inner:2 * d_inner + 2 * n],
                  proj[..., 2 * d_inner + 2 * n:])
    w = p["conv_w"]
    padded = jnp.pad(xbc, ((0, 0), (w.shape[0] - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + l] * w[i] for i in range(w.shape[0]))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, bm, cm = (xbc[..., :d_inner], xbc[..., d_inner:d_inner + n],
                  xbc[..., d_inner + n:])
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # [B,L,H]
    a_head = -jnp.exp(p["A_log"])
    xh = xs.reshape(b, l, hh, pp)
    y = ssd(xh * dt[..., None], dt * a_head, bm, cm, s["ssm_chunk"], ar)
    y = (y + p["D"][:, None] * xh).reshape(b, l, d_inner)
    y = c.rmsnorm(p["out_ln"], y * jax.nn.silu(z), s["norm_eps"])
    return x + ar.mm(y, p["out_proj"]), jnp.zeros((), jnp.float32)


def loss(params, batch, sizes, ar):
    return c.lm_loss(params, batch, sizes, ar, layer)
