"""Plain FedBiOAcc (Alg. 2, global lower level) over M clients, one client
at a time, in float32 with the parameters stored in their stated dtype; and
the STORM loop it shares with FedBiOAcc-Local (``fedbioacc_local.py``).

Sections: x (the body, upper variable), y (the head, lower variable) and u
(the auxiliary of the lower problem's linear system, head-shaped, zero at
start), each with a STORM momentum (zero at start).  Per client and step t,
with alpha = delta / (u0 + t)^(1/3) and one batch shared by every oracle:

    g_old = oracle(v)                       at the entering iterate
    m     = (1 - c alpha^2) (m - g_old)     partial momentum
    v     = v - lr alpha m_entering         rounded to the parameter dtype
    (every local_steps-th step: v = participants' mean of v)
    m     = m + oracle(v)                   at the new iterate
    (same steps: m = participants' mean of m)

where a client that does not take part in the step's round keeps its v and
m and runs no oracle, and

    oracle(x, y, u) = (d_x f - d2_xy g u,  d_y g,  d2_yy g u - d_y f)

with f the mean cross entropy on the ``val`` stream and g the one on the
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


#: the STORM constant of each section's momentum (Alg. 2 and 4)
DECAY = {"x": "c_nu", "y": "c_omega", "u": "c_u"}


def participants(hp, num_clients, rnd):
    """The clients that take part in round ``rnd``, by the rule that
    ``repro.federation.participation`` documents: every client under the
    ``full`` sampler; under ``uniform`` the first ``clients_per_round`` of
    the permutation of the clients keyed by ``fold_in(PRNGKey(seed),
    rnd)``."""
    p = hp["participation"]
    if p["sampler"] == "full":
        return list(range(num_clients))
    if p["sampler"] != "uniform":
        raise SystemExit(f"bench: the reference has no {p['sampler']!r} "
                         f"sampler")
    key = jax.random.fold_in(jax.random.PRNGKey(p["seed"]), rnd)
    perm = jax.random.permutation(key, num_clients)
    return sorted(int(i) for i in perm[:p["clients_per_round"]
                                        or num_clients])


def loop(oracle, starts, averaged, batches, hp):
    """The STORM loop of Alg. 2 and 4 over ``len(starts)`` clients, one at
    a time, from each client's start {section: tree}; returns the readings
    the check compares: per-leaf norms over all clients of the momenta
    after the first step (``grad1``), of each section's change after the
    last (``change``) and of the momenta after the last (``mom``).

    In each round (``local_steps`` steps) only the round's participants
    (:func:`participants`) run the oracle and step; the others keep their
    variables and momenta.  Every ``local_steps``-th step the participants'
    ``averaged`` sections, then their momenta, are replaced by the mean
    over the participants; the other sections are never averaged.
    ``batches[t]`` holds every client's rows on a leading axis; ``hp`` the
    schedule (``lr_<section>``, the :data:`DECAY` constants, alpha_delta,
    alpha_u0, local_steps, participation)."""
    sections, num_clients = tuple(starts[0]), len(starts)
    lr = {s: hp["lr_" + s] for s in sections}
    cc = {s: hp[DECAY[s]] for s in sections}

    @jax.jit
    def partial(v, m, g, a):
        m_new = {s: jax.tree.map(lambda mm, gg, s=s:
                                 (1.0 - cc[s] * a * a) * (mm - gg), m[s], g[s])
                 for s in sections}
        v_new = {s: jax.tree.map(lambda vv, mm, s=s: (
            vv.astype(jnp.float32) - (lr[s] * a) * mm).astype(vv.dtype),
            v[s], m[s]) for s in sections}
        return v_new, m_new

    @jax.jit
    def mean(trees):
        return jax.tree.map(
            lambda *a: jnp.mean(jnp.stack([t.astype(jnp.float32) for t in a]),
                                axis=0).astype(a[0].dtype), *trees)

    def share(trees, live):
        avg = mean([{s: trees[i][s] for s in averaged} for i in live])
        return [dict(t, **avg) if i in live else t
                for i, t in enumerate(trees)]

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    @jax.jit
    def norms_over(trees, bases=None):
        sq = lambda t: {k: v * v for k, v in leaf_norms(t).items()}
        total = None
        for i, t in enumerate(trees):
            d = t if bases is None else jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                t, bases[i])
            s = sq(d)
            total = s if total is None else {k: total[k] + s[k] for k in s}
        return {k: jnp.sqrt(v) for k, v in total.items()}

    vs = list(starts)
    ms = [jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), starts[0])
          ] * num_clients
    out = {}
    for t, batch in enumerate(batches):
        a = hp["alpha_delta"] / (hp["alpha_u0"] + jnp.float32(t)) ** (1.0 / 3.0)
        live = participants(hp, num_clients, t // hp["local_steps"])
        rows = {i: jax.tree.map(lambda r, i=i: r[i], batch) for i in live}
        for i in live:
            vs[i], ms[i] = partial(vs[i], ms[i], oracle(vs[i], rows[i]), a)
        comm = (t + 1) % hp["local_steps"] == 0
        if comm:
            vs = share(vs, live)
        for i in live:
            ms[i] = add(ms[i], oracle(vs[i], rows[i]))
        if comm:
            ms = share(ms, live)
        if t == 0:
            out["grad1"] = norms_over(ms)
    out["change"] = norms_over(vs, list(starts))
    out["mom"] = norms_over(ms)
    return {k: {p: float(x) for p, x in v.items()} for k, v in out.items()}


def run(model, sizes, dtype, key, batches, hp, num_clients, ar):
    """:func:`loop` of Alg. 2 from the weights ``model.init`` draws from
    ``key`` (in ``dtype``), every section averaged: x the body, y the head
    and u zero, the same on every client."""
    params = jax.jit(lambda k: model.init(k, sizes, dtype))(key)
    start = {"x": params["body"], "y": params["head"],
             "u": jax.tree.map(jnp.zeros_like, params["head"])}
    oracle = make_oracle(model.loss, sizes, ar, hp["lower_l2"])
    return loop(oracle, [start] * num_clients, SECTIONS, batches, hp)
