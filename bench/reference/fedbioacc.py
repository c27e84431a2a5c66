"""Plain FedBiOAcc (Alg. 2, global lower level) over M clients, one client
at a time, in float32 with the parameters stored in their stated dtype.

Sections: x (the body, upper variable), y (the head, lower variable) and u
(the auxiliary of the lower problem's linear system, head-shaped, zero at
start), each with a STORM momentum (zero at start).  Per client and step t,
with alpha = delta / (u0 + t)^(1/3) and one batch shared by every oracle:

    g_old = oracle(v)                       at the entering iterate
    m     = (1 - c alpha^2) (m - g_old)     partial momentum
    v     = v - lr alpha m_entering         rounded to the parameter dtype
    (every local_steps-th step: v = client mean of v)
    m     = m + oracle(v)                   at the new iterate
    (same steps: m = client mean of m)

where oracle(x, y, u) = (d_x f - d2_xy g u,  d_y g,  d2_yy g u - d_y f) with
f the mean cross entropy on the ``val`` stream and g the one on the
``train`` stream plus (lower_l2 / 2)|y|^2.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SECTIONS = ("x", "y", "u")


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def make_oracle(loss, sizes, ar, lower_l2):
    def oracle(v, batch):
        x, y, u = _f32(v["x"]), _f32(v["y"]), _f32(v["u"])

        def g(xx, yy):
            reg = sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(yy))
            return (loss({"body": xx, "head": yy}, batch["train"], sizes, ar)
                    + 0.5 * lower_l2 * reg)

        def f(xx, yy):
            return loss({"body": xx, "head": yy}, batch["val"], sizes, ar)

        def grads(xx, yy):
            return jax.grad(g, argnums=(0, 1))(xx, yy)

        (_, gy), (txy, tyy) = jax.jvp(
            grads, (x, y), (jax.tree.map(jnp.zeros_like, x), u))
        fx, fy = jax.grad(f, argnums=(0, 1))(x, y)
        sub = lambda a, b: jax.tree.map(jnp.subtract, a, b)
        return {"x": sub(fx, txy), "y": gy, "u": sub(tyy, fy)}
    return jax.jit(oracle)


def leaf_norms(tree, prefix=""):
    """{path: float32 2-norm} of every leaf of ``tree``."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        out[prefix + name] = jnp.sqrt(jnp.sum(jnp.square(
            leaf.astype(jnp.float32))))
    return out


def run(model, sizes, params, batches, hp, num_clients, ar):
    """Three (or ``len(batches)``) steps from ``params``; returns the
    readings the check compares: per-leaf norms over all clients of the
    momenta after the first step (``grad1``), of each section's change
    after the last (``change``) and of the momenta after the last
    (``mom``).  ``batches[t]`` holds every client's rows on a leading axis;
    ``hp`` the schedule (lr_x/y/u, c_nu/omega/u, alpha_delta, alpha_u0,
    local_steps, lower_l2)."""
    oracle = make_oracle(model.loss, sizes, ar, hp["lower_l2"])
    lr = {"x": hp["lr_x"], "y": hp["lr_y"], "u": hp["lr_u"]}
    cc = {"x": hp["c_nu"], "y": hp["c_omega"], "u": hp["c_u"]}
    start = {"x": params["body"], "y": params["head"],
             "u": jax.tree.map(jnp.zeros_like, params["head"])}

    @jax.jit
    def partial(v, m, g, a):
        m_new = {s: jax.tree.map(lambda mm, gg, s=s:
                                 (1.0 - cc[s] * a * a) * (mm - gg), m[s], g[s])
                 for s in SECTIONS}
        v_new = {s: jax.tree.map(lambda vv, mm, s=s: (
            vv.astype(jnp.float32) - (lr[s] * a) * mm).astype(vv.dtype),
            v[s], m[s]) for s in SECTIONS}
        return v_new, m_new

    @jax.jit
    def mean(trees):
        return jax.tree.map(
            lambda *a: jnp.mean(jnp.stack([t.astype(jnp.float32) for t in a]),
                                axis=0).astype(a[0].dtype), *trees)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    @jax.jit
    def norms_over(trees, base=None):
        sq = lambda t: {k: v * v for k, v in leaf_norms(t).items()}
        total = None
        for t in trees:
            d = t if base is None else jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                t, base)
            s = sq(d)
            total = s if total is None else {k: total[k] + s[k] for k in s}
        return {k: jnp.sqrt(v) for k, v in total.items()}

    vs = [start] * num_clients
    ms = [jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), start)
          ] * num_clients
    out = {}
    for t, batch in enumerate(batches):
        a = hp["alpha_delta"] / (hp["alpha_u0"] + jnp.float32(t)) ** (1.0 / 3.0)
        rows = [jax.tree.map(lambda r, i=i: r[i], batch)
                for i in range(num_clients)]
        stepped = [partial(v, m, oracle(v, b), a)
                   for v, m, b in zip(vs, ms, rows)]
        vs, ms = [s[0] for s in stepped], [s[1] for s in stepped]
        comm = (t + 1) % hp["local_steps"] == 0
        if comm:
            vs = [mean(vs)] * num_clients
        ms = [add(m, oracle(v, b)) for v, m, b in zip(vs, ms, rows)]
        if comm:
            ms = [mean(ms)] * num_clients
        if t == 0:
            out["grad1"] = norms_over(ms)
    out["change"] = norms_over(vs, start)
    out["mom"] = norms_over(ms)
    return {k: {p: float(x) for p, x in v.items()} for k, v in out.items()}
