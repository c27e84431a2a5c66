"""Hyper-gradient machinery (matrix-free, pytree-generic).

All second-order quantities the paper needs are Hessian-/Jacobian-vector
products evaluated without materialising any matrix:

* ``hvp_yy(g, x, y, batch, u)``   = ∇²_{yy} g(x,y;ξ) · u       (u-update, Eq. 4)
* ``jvp_xy(g, x, y, batch, u)``   = ∇²_{xy} g(x,y;ξ) · u       (ν-update)
* ``neumann_hypergrad``           = Eq. 6 truncated Neumann-series estimate
  (local-lower-level algorithms 3/4)

On TPU these lower to the same MXU matmuls as the forward/backward pass —
this is the hardware adaptation of the paper's linear algebra (DESIGN.md §6).
"""
from __future__ import annotations

from typing import Callable

import jax

from repro.core.tree_util import tree_axpy, tree_scale, tree_sub, tree_vdot
from repro.telemetry.trace import annotate

#: named scope of every differentiation of the lower objective g (∇g and its
#: Hessian-vector products): profiler/HLO metadata only
G_SCOPE = "fed.oracle_g"


def grad_x(f: Callable, x, y, batch):
    return jax.grad(f, argnums=0)(x, y, batch)


def grad_y(f: Callable, x, y, batch):
    return jax.grad(f, argnums=1)(x, y, batch)


def hvp_yy(g: Callable, x, y, batch, u):
    """∇²_{yy} g(x, y; batch) · u via forward-over-reverse."""
    with annotate(G_SCOPE):
        return jax.jvp(lambda yy: jax.grad(g, argnums=1)(x, yy, batch),
                       (y,), (u,))[1]


def jvp_xy(g: Callable, x, y, batch, u):
    """∇²_{xy} g(x, y; batch) · u  =  ∇_x ⟨∇_y g(x, y; batch), u⟩."""
    with annotate(G_SCOPE):
        return jax.grad(lambda xx: tree_vdot(
            jax.grad(g, argnums=1)(xx, y, batch), u))(x)


def u_step(g: Callable, f: Callable, x, y, u, batch_g, batch_f, tau: float):
    """One local step on the quadratic problem Eq. (4):

        u ← τ ∇_y f + (I − τ ∇²_{yy} g) u  =  u − τ (∇²_{yy} g · u − ∇_y f)
    """
    residual = tree_sub(hvp_yy(g, x, y, batch_g, u), grad_y(f, x, y, batch_f))
    return tree_axpy(-tau, residual, u)


def u_residual(g: Callable, f: Callable, x, y, u, batch_g, batch_f):
    """p = ∇²_{yy} g · u − ∇_y f (the q-momentum target in FedBiOAcc)."""
    return tree_sub(hvp_yy(g, x, y, batch_g, u), grad_y(f, x, y, batch_f))


def nu_direction(g: Callable, f: Callable, x, y, u, batch_g, batch_f):
    """ν = ∇_x f(x,y;B_f) − ∇²_{xy} g(x,y;B_g) · u  (Alg. 1 line 6)."""
    return tree_sub(grad_x(f, x, y, batch_f), jvp_xy(g, x, y, batch_g, u))


def _neumann_ihvp(g: Callable, x, y, batch_g, v0, q_terms: int, tau: float):
    """The truncated series [τ Σ_{k=0}^{Q} (I − τ∇²_{yy}g)^k] v0 — Q HVPs,
    the same minibatch reused across terms (the paper samples independent
    ξ_j — the bias difference is O(τ²σ²), covered by Proposition 2's
    variance bound; noted in DESIGN.md).  Shared by the unfused and fused
    local-lower oracles so the series semantics cannot diverge."""
    v = v0
    acc = v0
    with annotate(G_SCOPE):
        for _ in range(q_terms):
            v = tree_axpy(-tau, hvp_yy(g, x, y, batch_g, v), v)  # (I − τH) v
            acc = jax.tree.map(lambda a, b: a + b, acc, v)
        return tree_scale(tau, acc)


def neumann_hypergrad(g: Callable, f: Callable, x, y, batch_g, batch_f,
                      q_terms: int, tau: float):
    """Eq. (6): Φ(x,y;ξ) = ∇_x f − ∇_xy g · [τ Σ_{k=0}^{Q} (I − τ∇²_{yy}g)^k] ∇_y f."""
    ihvp = _neumann_ihvp(g, x, y, batch_g, grad_y(f, x, y, batch_f),
                         q_terms, tau)
    return tree_sub(grad_x(f, x, y, batch_f), jvp_xy(g, x, y, batch_g, ihvp))


def exact_hypergrad_quadratic(problem, x, y):
    """For tests: closed-form Φ(x, y_x) when the problem exposes it."""
    return problem.exact_hypergrad(x, y)


# ---------------------------------------------------------------------------
# Fused oracles (beyond-paper §Perf optimization)
# ---------------------------------------------------------------------------

def fused_g_oracles(g: Callable, x, y, batch, u):
    """(∇_y g, ∇²_xy g·u, ∇²_yy g·u) from ONE forward-over-reverse
    linearization of ∇_{(x,y)} g with tangent (0, u).

    Mathematically identical to the three separate calls (same minibatch);
    structurally it shares the forward/backward pass, which cuts the number
    of weight-streaming passes (and, under FSDP, weight all-gathers) from 3
    to ~2 per oracle point — the dominant roofline term for the
    client_replicated giants. See EXPERIMENTS.md §Perf.
    """
    from repro.core.tree_util import tree_zeros_like

    def grads(xx, yy):
        return jax.grad(g, argnums=(0, 1))(xx, yy, batch)

    with annotate(G_SCOPE):
        (_, gy), (txy, tyy) = jax.jvp(grads, (x, y),
                                      (tree_zeros_like(x), u))
    return gy, txy, tyy


def fused_local_oracles(g: Callable, f: Callable, x, y, batch,
                        q_terms: int, tau: float):
    """Both local-lower-level oracle directions (ω, Neumann hyper-gradient Φ)
    from shared linearizations on ONE minibatch:

        ω = ∇_y g
        Φ = ∇_x f − ∇²_xy g · [τ Σ_{k=0}^{Q} (I − τ∇²_{yy}g)^k] ∇_y f

    vs the unfused pair (``grad_y`` + ``neumann_hypergrad``) this shares
    (a) one ∇_{(x,y)} f call for ∇_x f and the series seed ∇_y f, and
    (b) one forward-over-reverse linearization of ∇_{(x,y)} g for ω together
    with the final ∇²_xy g·ihvp contraction — cutting the full
    weight-streaming passes per oracle point from ~5 to ~3 (the Q series
    HVPs are unchanged).  Mathematically identical on a shared minibatch.
    """
    from repro.core.tree_util import tree_zeros_like

    fx, fy = jax.grad(f, argnums=(0, 1))(x, y, batch)
    ihvp = _neumann_ihvp(g, x, y, batch, fy, q_terms, tau)

    def grads(xx, yy):
        return jax.grad(g, argnums=(0, 1))(xx, yy, batch)

    with annotate(G_SCOPE):
        (_, omega), (txy, _) = jax.jvp(grads, (x, y),
                                       (tree_zeros_like(x), ihvp))
    return omega, tree_sub(fx, txy)


def fused_oracles(g: Callable, f: Callable, x, y, u, batch):
    """All three FedBiO oracle directions (ω, ν-target μ, u-residual p) from
    two linearizations (one of g, one of f) sharing a single minibatch:

        ω = ∇_y g
        μ = ∇_x f − ∇²_xy g·u
        p = ∇²_yy g·u − ∇_y f
    """
    omega, txy, tyy = fused_g_oracles(g, x, y, batch, u)
    fx, fy = jax.grad(f, argnums=(0, 1))(x, y, batch)
    mu = tree_sub(fx, txy)
    p = tree_sub(tyy, fy)
    return omega, mu, p
