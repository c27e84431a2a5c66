"""Plain FedBiOAcc-Local (Alg. 4, local lower level) over M clients, one
client at a time, in float32 with the parameters stored in their stated
dtype.

Sections: x (the body, upper variable, averaged over the round's
participants every ``local_steps``-th step) and y (each client's own head,
its lower variable, never averaged), each with a STORM momentum (zero at
start), stepped by the loop of ``fedbioacc.py``.  The oracle at (x, y) on
one batch is (Phi, d_y g) with the truncated Neumann hypergradient

    Phi = d_x f - d2_xy g . tau sum_{k=0}^{Q} (I - tau d2_yy g)^k d_y f

(Q = ``neumann_q`` products with d2_yy g, tau = ``neumann_tau``), f the mean
cross entropy on the ``val`` stream and g the one on the ``train`` stream
plus (lower_l2 / 2)|y|^2.

Start: the key splits into M + 1; the body is drawn from the first, client
m's head from key m + 1, each by the family's own ``init`` (the heads are
private, so they must not start equal).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.fedbioacc import _f32, loop

AVERAGED = ("x",)


def make_oracle(loss, sizes, ar, lower_l2, q_terms, tau):
    def oracle(v, batch):
        x, y = _f32(v["x"]), _f32(v["y"])

        def g(xx, yy):
            reg = sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(yy))
            return (loss({"body": xx, "head": yy}, batch["train"], sizes, ar)
                    + 0.5 * lower_l2 * reg)

        def f(xx, yy):
            return loss({"body": xx, "head": yy}, batch["val"], sizes, ar)

        def hvp_yy(w):
            return jax.jvp(lambda yy: jax.grad(g, argnums=1)(x, yy), (y,),
                           (w,))[1]

        fx, fy = jax.grad(f, argnums=(0, 1))(x, y)
        term = acc = fy
        for _ in range(q_terms):
            term = jax.tree.map(lambda a, b: a - tau * b, term, hvp_yy(term))
            acc = jax.tree.map(jnp.add, acc, term)
        ihvp = jax.tree.map(lambda a: tau * a, acc)

        def grads(xx, yy):
            return jax.grad(g, argnums=(0, 1))(xx, yy)

        (_, gy), (txy, _) = jax.jvp(
            grads, (x, y), (jax.tree.map(jnp.zeros_like, x), ihvp))
        return {"x": jax.tree.map(jnp.subtract, fx, txy), "y": gy}
    return jax.jit(oracle)


def starts(model, sizes, dtype, key, num_clients):
    """Every client's start {"x": body, "y": its own head}."""
    ks = jax.random.split(key, num_clients + 1)
    body = model.init(ks[0], sizes, dtype)["body"]
    return [{"x": body, "y": model.init(k, sizes, dtype)["head"]}
            for k in ks[1:]]


def run(model, sizes, dtype, key, batches, hp, num_clients, ar):
    """:func:`bench.reference.fedbioacc.loop` of Alg. 4 from the weights
    :func:`starts` draws from ``key``."""
    start = jax.jit(lambda k: starts(model, sizes, dtype, k, num_clients))(
        key)
    oracle = make_oracle(model.loss, sizes, ar, hp["lower_l2"],
                         hp["neumann_q"], hp["neumann_tau"])
    return loop(oracle, start, AVERAGED, batches, hp)
