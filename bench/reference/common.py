"""Building blocks of the plain references: float32 arithmetic, every
matrix product at ``Precision.HIGHEST`` (a TPU otherwise multiplies float32
in lower precision), and the seeded initialisers that give the same weights
from the same key as the configuration's recipe (normal draws scaled by
1/sqrt(fan_in), rounded to the stated parameter dtype)."""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def fp8(a):
    """``a`` rounded to float8_e4m3 with a per-tensor scale, passed straight
    through by the derivative: the control's precision."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    q = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return a + lax.stop_gradient(q - a)


class Arith(NamedTuple):
    """How the reference computes: float32, every product at HIGHEST; or
    (``control``) in float8, every operand and result of a product and
    every layer's output rounded to float8; or (``control`` and
    ``operands_only``) with float8 operands alone, each product summed in
    float32, as a matmul moved to float8 inputs would compute."""
    control: bool = False
    operands_only: bool = False

    def q(self, a):
        """A product's result or a layer's output."""
        return fp8(a) if self.control and not self.operands_only else a

    def qin(self, a):
        """A product's operand."""
        return fp8(a) if self.control else a

    def mm(self, a, b):
        return self.q(jnp.matmul(self.qin(a), self.qin(b), precision=HIGHEST))

    def einsum(self, spec, *ops):
        return self.q(jnp.einsum(spec, *(self.qin(o) for o in ops),
                                 precision=HIGHEST))


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def dense(key, shape, dtype, scale=None):
    """N(0, 1) scaled by ``scale`` or 1/sqrt(shape[0])."""
    return normal(key, shape, 1.0 / math.sqrt(shape[0]) if scale is None
                  else scale, dtype)


def norm_init(d, dtype):
    return {"scale": jnp.zeros((d,), dtype)}


def stacked(key, reps, one):
    """``reps`` layers initialised by ``one(key)``, stacked on a leading
    axis (the layer keys split from ``key``)."""
    return jax.vmap(one)(jax.random.split(key, reps))


def rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + p["scale"])


def cross_entropy(logits, labels):
    """Mean next-token cross entropy over every position (labels < 0 are
    left out of the mean)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def lm_init(key, sizes, dtype, init_layers):
    """Embedding, layer stack, final norm and untied head, with the key
    split of the configuration's recipe."""
    kb, kh, ke, _ = jax.random.split(key, 4)
    d, v = sizes["d_model"], sizes["vocab_size"]
    _, sub = jax.random.split(kb)
    body = {"stages": [stacked(
        sub, sizes["num_layers"],
        lambda k: init_layers(jax.random.split(k, 1)[0], sizes, dtype))],
            "final_ln": norm_init(d, dtype),
            "embed": {"table": normal(ke, (v, d), 0.02, dtype)}}
    return {"body": body, "head": {"w": dense(kh, (d, v), dtype)}}


def lm_loss(params, batch, sizes, ar: Arith, layer, aux_weight=0.0):
    """Cross entropy (+ ``aux_weight`` x the layers' summed auxiliary loss)
    of a float32 language model whose layers ``layer(p, x, sizes, ar) ->
    (x, aux)`` are scanned over the stacked parameters, each recomputed in
    the backward pass to bound memory."""
    body = params["body"]
    x = body["embed"]["table"][batch["tokens"]]

    @jax.checkpoint
    def one(h, p):
        h, aux = layer(p, h, sizes, ar)
        return ar.q(h), aux

    x, aux = lax.scan(one, x, body["stages"][0])
    x = rmsnorm(body["final_ln"], x, sizes["norm_eps"])
    logits = ar.mm(x, params["head"]["w"])
    return cross_entropy(logits, batch["labels"]) + aux_weight * jnp.sum(aux)
