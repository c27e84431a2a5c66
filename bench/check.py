"""The output check: what the timed path produced against the plain
reference, leaf by leaf.

Three readings per side, each a 2-norm per leaf over all clients, of the
sections and momenta the run's algorithm declares (:func:`sections`): the
momenta after the first step (the first gradient as the optimizer holds
it: ``c alpha_0^2`` times the oracle at the start), each section's change
after the check's last step, and the momenta then.  A leaf's gap is
``|prog - ref| / max(ref, median leaf of ref)``; each reading gives two
numbers, the worst leaf's gap (``grad1_gap``, ``change_gap``, ``mom_gap``)
and the median leaf's (``*.median``).  A cell's limits file names the
numbers it compares.  Leaves whose reference gradient is under a thousandth
of the median leaf's move by round-off alone and are left out of the later
two readings.
"""
from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp

from bench.reference.fedbioacc import leaf_norms

#: a leaf whose reference gradient is below this share of the median leaf's
#: is nought to rounding
NOUGHT = 1e-3


def sections(run) -> list:
    """``[(section, momentum, averaged)]`` of the run's algorithm, in the
    order of its sequences: the names of the fields of ``run.views`` that
    hold each variable section and its momentum, and whether the section
    is ever averaged over clients (not private)."""
    from repro.optim.sequences import PRIVATE, SPECS
    return [(q.section, q.momentum, q.comm != PRIVATE)
            for q in SPECS[run.spec.algorithm.name].sequences]


def program_readers(run):
    """Jitted readers of the program's state: ``first(state)`` the start
    the changes are measured from (an averaged section's first client
    alone, since every client starts it the same; a private one's every
    client); ``grad1(state)`` and ``late(state, v0)`` the per-leaf norms
    the check compares."""
    seqs = sections(run)

    def split(state):
        v = run.views(state)
        return ({s: getattr(v, s) for s, _, _ in seqs},
                {s: getattr(v, m) for s, m, _ in seqs})

    def first(state):
        v = split(state)[0]
        return {s: jax.tree.map(lambda a: a[:1], v[s]) if shared else v[s]
                for s, _, shared in seqs}

    def grad1(state):
        return leaf_norms(split(state)[1])

    def late(state, v0):
        v, m = split(state)
        d = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                         - b.astype(jnp.float32), v, v0)
        return {"change": leaf_norms(d), "mom": leaf_norms(m)}

    return jax.jit(first), jax.jit(grad1), jax.jit(late)


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """{leaf: gap} over the leaves in ``keep`` (all if None); a leaf that
    is not finite on either side reads infinity."""
    paths = [p for p in ref if keep is None or p in keep]
    med = statistics.median(ref[p] for p in paths)

    def one(p):
        g = abs(prog[p] - ref[p]) / max(ref[p], med)
        return g if math.isfinite(g) else math.inf
    return {p: one(p) for p in paths}


def left_out(ref: dict) -> list:
    """Leaves whose reference gradient is nought to rounding."""
    g = ref["grad1"]
    med = statistics.median(g.values())
    return sorted(p for p, v in g.items() if v < NOUGHT * med)


def numbers(prog: dict, ref: dict) -> dict:
    """{number: (value, leaf)} of the program's readings against the
    reference's (both as :func:`bench.reference.fedbioacc.loop` returns):
    for each reading its worst leaf and its median leaf."""
    keep = set(ref["grad1"]) - set(left_out(ref))
    out = {}
    for name, k in (("grad1_gap", None), ("change_gap", keep),
                    ("mom_gap", keep)):
        reading = name.split("_")[0]
        gaps = leaf_gaps(prog[reading], ref[reading], k)
        order = sorted(gaps, key=gaps.get)
        out[name] = (gaps[order[-1]], order[-1])
        mid = order[(len(order) - 1) // 2]
        out[name + ".median"] = (gaps[mid], mid)
    return out
