"""Plain float32 reference of a mixture-of-experts decoder (Granite-3.0 MoE
layer mix): every layer is pre-norm grouped-query attention with rotary
positions and a causal mask, then a pre-norm top-k routed SwiGLU expert MLP,
each with a residual.

Attention: ``q, k, v = h W_q, h W_k, h W_v``; query head ``i`` reads key and
value head ``i // (heads / kv_heads)``; the rotary embedding turns the two
halves of each head by ``pos / theta^(j / half)``; scores are scaled by
1/sqrt(head_dim).  Experts: the router's softmax over all experts, the top
``experts_per_token`` renormalised to sum to one; each chosen expert gives
``(silu(h W_g) * (h W_i)) W_o``.  The layer's auxiliary loss is the Switch
balance term ``E * sum_e mean_t(p_e) * mean_t(count_e) / k``, weighted 0.01
in the loss.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import common as c

AUX_WEIGHT = 0.01


def init_layer(key, s, dtype):
    d, hq, hkv = s["d_model"], s["num_heads"], s["num_kv_heads"]
    hd, e, f = d // hq, s["num_experts"], s["d_ff"]
    k1, k2, _ = jax.random.split(key, 3)
    a = jax.random.split(k1, 4)
    m = jax.random.split(k2, 4)
    return {"0_attn": {
        "ln1": c.norm_init(d, dtype),
        "mix": {"wq": c.dense(a[0], (d, hq * hd), dtype),
                "wk": c.dense(a[1], (d, hkv * hd), dtype),
                "wv": c.dense(a[2], (d, hkv * hd), dtype),
                "wo": c.dense(a[3], (hq * hd, d), dtype,
                              scale=1.0 / math.sqrt(hq * hd))},
        "ln2": c.norm_init(d, dtype),
        # the recipe scales the expert inputs by 1/sqrt(shape[0]) = 1/sqrt(E)
        "ffn": {"router": c.dense(m[0], (d, e), dtype, scale=0.02),
                "wi": c.dense(m[1], (e, d, f), dtype),
                "wg": c.dense(m[2], (e, d, f), dtype),
                "wo": c.dense(m[3], (e, f, d), dtype,
                              scale=1.0 / math.sqrt(f))},
    }}


def init(key, sizes, dtype):
    return c.lm_init(key, sizes, dtype, init_layer)


def _rotary(x, theta):
    """x [B, S, H, D]: rotate the halves of each head by position."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, h, s, ar):
    b, t, d = h.shape
    hq, hkv = s["num_heads"], s["num_kv_heads"]
    hd = d // hq
    q = _rotary(ar.mm(h, p["wq"]).reshape(b, t, hq, hd), s["rope_theta"])
    k = _rotary(ar.mm(h, p["wk"]).reshape(b, t, hkv, hd), s["rope_theta"])
    v = ar.mm(h, p["wv"]).reshape(b, t, hkv, hd)
    group = jnp.arange(hq) // (hq // hkv)
    k, v = k[:, :, group], v[:, :, group]                   # [B,S,Hq,D]
    scores = ar.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = ar.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, hq * hd)
    return ar.mm(out, p["wo"])


def experts(p, h, s, ar):
    e, k = s["num_experts"], s["experts_per_token"]
    probs = jax.nn.softmax(ar.mm(h, p["router"]), axis=-1)      # [B,S,E]
    top_w, top_i = lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, e, dtype=jnp.float32)        # [B,S,k,E]
    gate = jnp.sum(chosen * top_w[..., None], axis=-2)          # [B,S,E]
    aux = e * jnp.sum(jnp.mean(probs, axis=(0, 1))
                      * jnp.mean(jnp.sum(chosen, axis=-2), axis=(0, 1)) / k)
    hid = (jax.nn.silu(ar.einsum("bsd,edf->bsef", h, p["wg"]))
           * ar.einsum("bsd,edf->bsef", h, p["wi"]))
    # the experts the router did not choose have gate 0: they add nothing
    out = ar.einsum("bsef,efd,bse->bsd", hid, p["wo"], gate)
    return out, aux


def layer(p, x, s, ar):
    p = p["0_attn"]
    x = x + attention(p["mix"], c.rmsnorm(p["ln1"], x, s["norm_eps"]), s, ar)
    out, aux = experts(p["ffn"], c.rmsnorm(p["ln2"], x, s["norm_eps"]), s, ar)
    return x + out, aux


def loss(params, batch, sizes, ar):
    return c.lm_loss(params, batch, sizes, ar, layer, AUX_WEIGHT)
