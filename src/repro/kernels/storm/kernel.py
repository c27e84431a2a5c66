"""Fused STORM momentum + SGD update — Pallas TPU kernel.

FedBiOAcc triples the elementwise optimizer traffic (three momentum sequences
over the full parameter tree, each needing two oracle values). Unfused, the
update chain

    p_new = p − lr·m                          (variable step, Alg. 2 line 4)
    m_new = g_new + decay·(m − g_old)         (STORM correction, lines 10-12)

reads p, m, g_new, g_old and writes two intermediates plus two outputs — on
TPU this is purely HBM-bandwidth bound. The kernel streams all four inputs
through VMEM once and writes the two outputs: 6 HBM transfers vs 10 for the
naive chain (XLA usually fuses some of it; the kernel makes the floor
explicit and is the §Perf "memory term" optimization for the train step).

Two kernel families live here:

* ``storm_update_flat`` — the original single-sequence update: one (lr,
  decay) pair for the whole buffer.
* ``storm3_update_flat`` / ``storm3_step_flat`` — the **triple-sequence**
  update used by the flat-buffer substrate (``repro.optim.flat``). The three
  FedBiOAcc sequences x/ν, y/ω, u/q are laid out as contiguous,
  tile-aligned segments of ONE flat buffer; per-*block* (lr, decay) scalars
  arrive through SMEM, one pair per grid step, so a single launch streams
  all three sequences with their own hyper-parameters.
  ``storm3_step_flat`` is the half-step variant (variable step + partial
  momentum ``decay·(m − g_old)``) used inside the real train step, where the
  new-iterate oracle — and hence ``g_new`` — only exists after the updated
  variables have been communicated.
* ``momsgd3_step_flat`` / ``sgd3_step_flat`` — the heavy-ball and plain-SGD
  companions used by the sequence-spec engine (``repro.optim.sequences``)
  for the non-STORM algorithms: ``m' = β·m + g`` then ``p' = p − lr·m'``
  (FedAvg applies the *updated* momentum), and the momentum-less
  ``p' = p − lr·g`` (FedBiO / FedBiO-Local — 2 reads + 1 write, no dead
  momentum stream).

Layout: inputs are flattened to [N] and tiled as (BLOCK,) VMEM blocks on a 1D
grid. Scalars (lr, decay) arrive in SMEM: the single pair whole, the
per-block tables one entry per grid step (:func:`tile_scalars` — a
``[T, 1, 1]`` view blocked as ``(None, 1, 1)``), so SMEM holds a few words
whatever the buffer size; a whole ``[T]`` table outgrows the 1 MiB SMEM of
a TPU v5e at published widths with small blocks. ``interpret`` defaults to
auto: Pallas interpreter everywhere except on a real TPU backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

BLOCK = 64 * 1024   # elements per VMEM tile (bf16: 128 KiB/input, 4 inputs
                    # + 2 outputs ≈ 768 KiB of VMEM — comfortably under 16 MiB)


#: a compiled kernel's 1-D blocks must cover whole tiles of the XLA layout
#: of a 1-D array (1024 elements); smaller blocks fail inside Mosaic
TPU_BLOCK_QUANTUM = 1024


def _resolve_block_launch(interpret, block: int):
    """:func:`resolve_interpret` for a launch over ``block``-element tiles,
    refusing up front a block a compiled TPU kernel cannot stream."""
    flag = resolve_interpret(interpret)
    if not flag and block % TPU_BLOCK_QUANTUM:
        raise ValueError(
            f"block={block}: a compiled TPU kernel streams 1-D blocks of a "
            f"multiple of {TPU_BLOCK_QUANTUM} elements (whole tiles of the "
            f"1-D layout); set execution.storm_block to such a multiple")
    return flag


#: one SMEM scalar per grid step of a per-block table viewed as [T, 1, 1]
#: (Mosaic refuses rank-1 blocks smaller than a 128-lane tile; a squeezed
#: leading axis over trailing (1, 1) dims equal to the array's is accepted)
TILE_SMEM = pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM)


def tile_scalars(table):
    """Per-block f32 table [T] → the [T, 1, 1] view whose entries
    :data:`TILE_SMEM` streams one per grid step (read as ``ref[0, 0]``)."""
    return table.astype(jnp.float32).reshape(-1, 1, 1)


def _storm_kernel(scal_ref, p_ref, m_ref, gnew_ref, gold_ref,
                  pout_ref, mout_ref):
    lr = scal_ref[0]
    decay = scal_ref[1]
    p = p_ref[...]
    m = m_ref[...].astype(jnp.float32)
    g_new = gnew_ref[...].astype(jnp.float32)
    g_old = gold_ref[...].astype(jnp.float32)
    # variable step with the *entering* momentum (Alg. 2 ordering)
    pout_ref[...] = (p.astype(jnp.float32) - lr * m).astype(p_ref.dtype)
    mout_ref[...] = (g_new + decay * (m - g_old)).astype(mout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def storm_update_flat(p, m, g_new, g_old, lr, decay, *,
                      interpret: bool | None = None):
    """p, m, g_new, g_old: flat [N] arrays (N a multiple of BLOCK)."""
    n = p.shape[0]
    assert n % BLOCK == 0, n
    grid = (n // BLOCK,)
    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(decay, jnp.float32)])
    block = pl.BlockSpec((BLOCK,), lambda i: (i,))
    return pl.pallas_call(
        _storm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # (lr, decay) scalars
            block, block, block, block,
        ],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct((n,), p.dtype),
                   jax.ShapeDtypeStruct((n,), m.dtype)],
        name="storm_update_flat",
        interpret=resolve_interpret(interpret),
    )(scal, p, m, g_new, g_old)


# ---------------------------------------------------------------------------
# Triple-sequence kernels (flat-buffer substrate)
# ---------------------------------------------------------------------------

def _storm3_kernel(lrs_ref, decays_ref, p_ref, m_ref, gnew_ref, gold_ref,
                   pout_ref, mout_ref):
    lr = lrs_ref[0, 0]
    decay = decays_ref[0, 0]
    m = m_ref[...].astype(jnp.float32)
    g_new = gnew_ref[...].astype(jnp.float32)
    g_old = gold_ref[...].astype(jnp.float32)
    pout_ref[...] = (p_ref[...].astype(jnp.float32) - lr * m).astype(pout_ref.dtype)
    mout_ref[...] = (g_new + decay * (m - g_old)).astype(mout_ref.dtype)


def _storm3_step_kernel(lrs_ref, decays_ref, p_ref, m_ref, gold_ref,
                        pout_ref, mout_ref):
    lr = lrs_ref[0, 0]
    decay = decays_ref[0, 0]
    m = m_ref[...].astype(jnp.float32)
    g_old = gold_ref[...].astype(jnp.float32)
    pout_ref[...] = (p_ref[...].astype(jnp.float32) - lr * m).astype(pout_ref.dtype)
    mout_ref[...] = (decay * (m - g_old)).astype(mout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def storm3_update_flat(p, m, g_new, g_old, lrs, decays, *,
                       block: int = BLOCK, interpret: bool | None = None):
    """Full triple-sequence fused STORM update on one flat buffer.

    p, m, g_new, g_old: [N] with N a multiple of ``block``; segment
    boundaries are block-aligned. lrs, decays: [N // block] per-block
    hyper-parameters (constant within a segment), read from SMEM.
    """
    n = p.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)
    assert lrs.shape == decays.shape == grid, (lrs.shape, grid)
    bspec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _storm3_kernel,
        grid=grid,
        in_specs=[TILE_SMEM, TILE_SMEM, bspec, bspec, bspec, bspec],
        out_specs=[bspec, bspec],
        out_shape=[jax.ShapeDtypeStruct((n,), p.dtype),
                   jax.ShapeDtypeStruct((n,), m.dtype)],
        name="storm3_update_flat",
        interpret=_resolve_block_launch(interpret, block),
    )(tile_scalars(lrs), tile_scalars(decays), p, m, g_new, g_old)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def storm3_step_flat(p, m, g_old, lrs, decays, *,
                     block: int = BLOCK, interpret: bool | None = None):
    """Half-step: p_new = p − lr·m ; m_part = decay·(m − g_old).

    This is the launch the real FedBiOAcc step uses — the new-iterate
    gradient is only available after communication, so the STORM correction
    ``m_part + g_new`` is completed by a single elementwise add later.
    """
    n = p.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)
    assert lrs.shape == decays.shape == grid, (lrs.shape, grid)
    bspec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _storm3_step_kernel,
        grid=grid,
        in_specs=[TILE_SMEM, TILE_SMEM, bspec, bspec, bspec],
        out_specs=[bspec, bspec],
        out_shape=[jax.ShapeDtypeStruct((n,), p.dtype),
                   jax.ShapeDtypeStruct((n,), m.dtype)],
        name="storm3_step_flat",
        interpret=_resolve_block_launch(interpret, block),
    )(tile_scalars(lrs), tile_scalars(decays), p, m, g_old)


# ---------------------------------------------------------------------------
# Heavy-ball / SGD triple-sequence kernels (sequence-spec engine, non-STORM
# algorithms). FedAvg applies the *updated* momentum (m' = β·m + g then
# p' = p − lr·m'), which neither storm3 kernel expresses: storm3_step uses
# the entering momentum for the variable step. Momentum-less specs (FedBiO /
# FedBiO-Local) take the dedicated sgd3 kernel instead — a pallas_call's
# outputs are opaque to XLA DCE, so reusing the heavy-ball kernel at β = 0
# would pay a full-size dead momentum write every step.
# ---------------------------------------------------------------------------

def _momsgd3_kernel(lrs_ref, betas_ref, p_ref, m_ref, g_ref,
                    pout_ref, mout_ref):
    lr = lrs_ref[0, 0]
    beta = betas_ref[0, 0]
    m_new = beta * m_ref[...].astype(jnp.float32) + g_ref[...].astype(jnp.float32)
    pout_ref[...] = (p_ref[...].astype(jnp.float32) - lr * m_new).astype(pout_ref.dtype)
    mout_ref[...] = m_new.astype(mout_ref.dtype)


def _sgd3_kernel(lrs_ref, p_ref, g_ref, pout_ref):
    lr = lrs_ref[0, 0]
    pout_ref[...] = (p_ref[...].astype(jnp.float32)
                     - lr * g_ref[...].astype(jnp.float32)).astype(pout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sgd3_step_flat(p, g, lrs, *,
                   block: int = BLOCK, interpret: bool | None = None):
    """Plain fused SGD step p_new = p − lr·g — the β = 0 fast path of the
    momentum-less specs (FedBiO / FedBiO-Local): 2 reads + 1 write per
    element, no dead momentum output for the pallas_call to keep alive."""
    n = p.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)
    assert lrs.shape == grid, (lrs.shape, grid)
    bspec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _sgd3_kernel,
        grid=grid,
        in_specs=[TILE_SMEM, bspec, bspec],
        out_specs=bspec,
        out_shape=jax.ShapeDtypeStruct((n,), p.dtype),
        name="sgd3_step_flat",
        interpret=_resolve_block_launch(interpret, block),
    )(tile_scalars(lrs), p, g)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def momsgd3_step_flat(p, m, g, lrs, betas, *,
                      block: int = BLOCK, interpret: bool | None = None):
    """Fused heavy-ball step: m_new = β·m + g ; p_new = p − lr·m_new.

    Same layout contract as the storm3 kernels: flat [N] buffers with
    block-aligned segment boundaries and per-block (lr, β) SMEM tables.
    """
    n = p.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)
    assert lrs.shape == betas.shape == grid, (lrs.shape, grid)
    bspec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        _momsgd3_kernel,
        grid=grid,
        in_specs=[TILE_SMEM, TILE_SMEM, bspec, bspec, bspec],
        out_specs=[bspec, bspec],
        out_shape=[jax.ShapeDtypeStruct((n,), p.dtype),
                   jax.ShapeDtypeStruct((n,), m.dtype)],
        name="momsgd3_step_flat",
        interpret=_resolve_block_launch(interpret, block),
    )(tile_scalars(lrs), tile_scalars(betas), p, m, g)


# ---------------------------------------------------------------------------
# jnp lowerings of the triple-sequence updates — the ref.py oracles, jitted.
# The substrate (repro.optim.flat) dispatches here off-TPU: the Pallas
# interpreter exists for kernel validation, not speed, while these compile to
# a handful of fused XLA loops over the flat buffer (still far fewer passes
# than the per-leaf tree-map chain). Delegating keeps ref.py the single
# source of the jnp math, with the Pallas kernel as the independent
# implementation the test sweeps compare against.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block",))
def storm3_update_flat_jnp(p, m, g_new, g_old, lrs, decays, *, block: int):
    from repro.kernels.storm.ref import storm3_update_ref
    return storm3_update_ref(p, m, g_new, g_old, lrs, decays, block)


@functools.partial(jax.jit, static_argnames=("block",))
def storm3_step_flat_jnp(p, m, g_old, lrs, decays, *, block: int):
    from repro.kernels.storm.ref import storm3_step_ref
    return storm3_step_ref(p, m, g_old, lrs, decays, block)


@functools.partial(jax.jit, static_argnames=("block",))
def momsgd3_step_flat_jnp(p, m, g, lrs, betas, *, block: int):
    from repro.kernels.storm.ref import momsgd3_step_ref
    return momsgd3_step_ref(p, m, g, lrs, betas, block)


@functools.partial(jax.jit, static_argnames=("block",))
def sgd3_step_flat_jnp(p, g, lrs, *, block: int):
    from repro.kernels.storm.ref import sgd3_step_ref
    return sgd3_step_ref(p, g, lrs, block)
