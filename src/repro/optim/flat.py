"""Flat-buffer optimizer substrate for the STORM-accelerated train steps.

FedBiOAcc runs STORM variance reduction on *three* parallel sequences — the
body/ν, head/ω and auxiliary/q pairs — over the full parameter tree every
local step. Expressed as per-leaf ``jax.tree.map`` chains that is ~9
elementwise passes per step, each dispatched once per leaf; on accelerators
it is pure HBM-bandwidth traffic and the dominant "memory term" of the step.

This module flattens the (x, y, u) trees and their momenta **once at init**
into contiguous per-dtype buffers and keeps all training state flat across
steps (callers jit with buffer donation). Pytree views are materialized only
at oracle / eval / checkpoint boundaries via ``unflatten_tree``.

Layout (``FlatSpec``):

* Leaves are grouped by dtype → one 1-D buffer per dtype.
* Within a buffer, leaves are ordered by **section** (e.g. ``("x","y","u")``)
  and each section is zero-padded up to the kernel tile size ``block``, so
  every tile belongs to exactly one section. ``_Group.section_ids`` maps
  tile → section; at step time the per-section (lr, decay) scalars are
  gathered into per-tile SMEM tables for the triple-sequence Pallas kernel
  (``storm3_step_flat`` / ``storm3_update_flat``).  The kernel streams a
  buffer in its own layout: an [N] buffer as (block,) VMEM blocks on a 1-D
  grid, an [M, N] one as (M, block) blocks over the tiles with one scalar
  per (tile, client) — never reshaped to 1-D, which on a TPU would relay
  the whole buffer between the 2-D and the 1-D tiling around every launch.
* Leaf offset metadata (``_Leaf``) makes flatten/unflatten pure
  reshape+concat / slice+reshape — no data-dependent work.
* Buffers may carry leading batch dims (``batch_dims=1`` for the federated
  client axis M → buffers are [M, N]); ``client_mean`` on such a buffer is
  ONE reduction per dtype instead of one per leaf.
* ``client_mean_masked`` supports *partial* communication (the local-lower
  algorithms: average x/ν, keep y/ω private): the per-group **section
  extents are precomputed at spec-build time** (``_Group.extents``) and
  adjacent same-mode/same-weight sections coalesce into contiguous element
  runs, so the communicated sections cost one sliced reduction each while
  private sections pass through bit-identical and never enter a reduction.
  Each communicated run is written back **in place**
  (``lax.dynamic_update_slice`` — under buffer donation the private tiles
  are never copied; on CPU large runs are additionally chunked so the
  reduce + broadcast stay cache-resident, which is what makes the sliced
  reduction beat the per-leaf tree-map path off-TPU too).
* **Participation** (``repro.federation.participation``): the same reductions
  take per-client ``weights`` ([M], zero = non-participant) — the mean is
  over participants only (weighted by data size / staleness discounts), and
  non-participant rows pass through bit-identical, exactly like private
  sections do along the section axis.  The weighted mean is computed as
  ``mean(x · w · (M/Σw))`` so that all-ones weights reproduce the unweighted
  path bit-for-bit.  The fused updates take the matching per-client ``mask``:
  a non-participant's tiles get lr = 0 (and STORM decay / heavy-ball β pinned
  to 1), which — together with the engine zeroing its oracle contributions —
  freezes its variable AND momentum buffers bit-exact through the round.

Mesh sharding (``shards`` / ``ShardCtx``)
-----------------------------------------

``make_spec(..., shards=k)`` builds a layout partitionable over a mesh
"model" axis of size k: every section is padded to a multiple of
``block · shards`` and the buffer is laid out **shard-major** — shard j's
contiguous chunk holds the j-th ``1/shards`` slice of *every* section, in
section order.  Consequences:

* a plain contiguous ``NamedSharding(mesh, P("data", "model"))`` on the
  [M, N] buffer gives every model shard the SAME tile-aligned section
  pattern (``_Group.extents`` describes one shard chunk; the global
  ``section_ids`` is that pattern tiled ``shards`` times), so section
  boundaries are tile-aligned to shard boundaries by construction;
* the fused launches and the masked reductions run under ``jax.shard_map``
  (pass ``shard=``, a :class:`ShardCtx`): each device launches the kernel on
  its local [M/d, N/k] chunk with its slice of the per-tile SMEM tables, and
  ``client_mean_masked`` lowers the participant mean to **per-shard partial
  sums + one ``lax.psum`` over "data" per communicated run** (or the
  ``psum_scatter`` + ``all_gather`` decomposition with
  ``ShardCtx.use_scatter`` — the overlap-friendly all-reduce).  Private and
  non-participant tiles never enter the collective.

``shards=1`` (the default) reproduces the original single-chip layout
bit-for-bit.  The padding tiles are zero and stay zero under every substrate
op (the update is elementwise and 0 − lr·0 = 0), so round-trips are exact.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from repro.kernels.storm.kernel import (BLOCK, momsgd3_step_flat,
                                        momsgd3_step_flat_jnp,
                                        sgd3_step_flat, sgd3_step_flat_jnp,
                                        storm3_step_flat,
                                        storm3_step_flat_jnp,
                                        storm3_update_flat,
                                        storm3_update_flat_jnp)
from repro.kernels.storm.quantpack import (quantpack_flat, quantpack_flat_jnp,
                                           quantunpack_flat,
                                           quantunpack_flat_jnp)


class _Leaf(NamedTuple):
    index: int          # position in the spec treedef's leaf order
    shape: tuple        # original leaf shape (without batch dims)
    size: int
    offset: int         # element offset inside the SECTION-CONTIGUOUS layout


class _Group(NamedTuple):
    dtype: Any                  # np.dtype of the buffer
    leaves: tuple               # of _Leaf, ascending offset
    padded: int                 # buffer length — multiple of block·shards
    block: int
    section_ids: np.ndarray     # [padded // block] int32 — tile → section
    extents: tuple = ()         # static per-SHARD-CHUNK section extents:
    #   ((section, start_elem, stop_elem), ...) covering [0, padded/shards)
    #   — the section-run slices every reduction is built from


class FlatSpec(NamedTuple):
    treedef: Any
    num_leaves: int
    sections: tuple             # section names, () when unsectioned
    groups: tuple               # of _Group
    shards: int = 1             # model-axis partition count of the layout


class ShardCtx(NamedTuple):
    """How the flat substrate is partitioned over a device mesh: the client
    axis M over ``data_axis``, the packed parameter axis N over
    ``model_axis`` (which must equal ``FlatSpec.shards``).  ``use_scatter``
    lowers the participant mean to ``psum_scatter`` + ``all_gather`` instead
    of one ``psum`` (the decomposed all-reduce XLA can software-pipeline)."""
    mesh: Any
    data_axis: str = "data"
    model_axis: str = "model"
    use_scatter: bool = False

    @property
    def data_size(self) -> int:
        return self.mesh.shape[self.data_axis]

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def buffer_spec(self) -> PartitionSpec:
        return PartitionSpec(self.data_axis, self.model_axis)


def make_shard_ctx(mesh, *, data_axis: str = "data",
                   model_axis: str = "model",
                   use_scatter: bool = False) -> ShardCtx:
    axes = dict(mesh.shape)
    for a in (data_axis, model_axis):
        if a not in axes:
            raise ValueError(f"mesh axes {tuple(axes)} carry no {a!r} axis")
    return ShardCtx(mesh, data_axis, model_axis, use_scatter)


def _round_up(n: int, block: int) -> int:
    return n + (-n) % block


def make_spec(tree, *, sections: Sequence[str] | None = None,
              block: int = BLOCK, shards: int = 1) -> FlatSpec:
    """Build the flat layout for ``tree`` (arrays or ShapeDtypeStructs).

    ``sections``: top-level dict keys of ``tree`` whose subtrees must occupy
    contiguous, tile-aligned runs of each dtype buffer (the x|y|u segments of
    the triple-sequence kernel). Buffer order follows ``sections``, not the
    treedef's internal key order.

    ``shards``: model-axis partition count — every section is padded to a
    multiple of ``block · shards`` and the buffer is laid out shard-major
    (see the module docstring), so a contiguous 1/shards chunk of the buffer
    holds 1/shards of every section with tile-aligned section boundaries.
    """
    assert shards >= 1, shards
    leaves, treedef = jax.tree.flatten(tree)
    if sections is None:
        sec_names = ()
        sec_of_leaf = [0] * len(leaves)
        n_sections = 1
    else:
        sec_names = tuple(sections)
        # label every leaf with its section index, in treedef leaf order
        # (dict flattening sorts keys the same way for tree and labels)
        sec_of_leaf = jax.tree.leaves(
            {k: jax.tree.map(lambda _, s=i: s, tree[k])
             for i, k in enumerate(sec_names)})
        assert len(sec_of_leaf) == len(leaves), "sections must cover the tree"
        n_sections = len(sec_names)

    # dtype groups, ordered by first appearance in (section, leaf) order
    order = sorted(range(len(leaves)), key=lambda i: (sec_of_leaf[i], i))
    dtypes: list = []
    for i in order:
        dt = np.dtype(leaves[i].dtype)
        if dt not in dtypes:
            dtypes.append(dt)

    quantum = block * shards
    groups = []
    for dt in dtypes:
        lfs, offset = [], 0
        pattern: list = []      # per-shard-chunk tile → section
        extents: list = []      # per-shard-chunk (section, start, stop)
        for s in range(n_sections):
            start = offset
            for i in order:
                if sec_of_leaf[i] != s or np.dtype(leaves[i].dtype) != dt:
                    continue
                shape = tuple(leaves[i].shape)
                size = int(np.prod(shape, dtype=np.int64)) if shape else 1
                lfs.append(_Leaf(i, shape, size, offset))
                offset += size
            if offset > start:     # section present in this dtype group
                offset = _round_up(offset, quantum)
                k = (offset - start) // quantum    # tiles per shard chunk
                a = extents[-1][2] if extents else 0
                extents.append((s, a, a + k * block))
                pattern += [s] * k
        if not lfs:
            continue
        groups.append(_Group(dt, tuple(lfs), offset, block,
                             np.tile(np.asarray(pattern, np.int32), shards),  # analysis: ignore[L303] spec build
                             tuple(extents)))
    return FlatSpec(treedef, len(leaves), sec_names, tuple(groups), shards)


def _interleave(spec: FlatSpec, grp: _Group, cont):
    """Section-contiguous buffer → shard-major layout (reshape/concat only).
    Identity when ``shards == 1``."""
    if spec.shards == 1:
        return cont
    bs = cont.shape[:-1]
    pieces, off = [], 0
    for _, a, b in grp.extents:
        per_shard = b - a
        full = per_shard * spec.shards
        pieces.append(cont[..., off:off + full]
                      .reshape(bs + (spec.shards, per_shard)))
        off += full
    return jnp.concatenate(pieces, axis=-1).reshape(bs + (grp.padded,))


def _deinterleave(spec: FlatSpec, grp: _Group, buf):
    """Shard-major layout → section-contiguous buffer (the inverse)."""
    if spec.shards == 1:
        return buf
    bs = buf.shape[:-1]
    chunked = buf.reshape(bs + (spec.shards, grp.padded // spec.shards))
    pieces = [chunked[..., :, a:b].reshape(bs + ((b - a) * spec.shards,))
              for _, a, b in grp.extents]
    return jnp.concatenate(pieces, axis=-1)


def flatten_tree(spec: FlatSpec, tree, *, batch_dims: int = 0, dtype=None):
    """Pack ``tree`` into the spec's flat buffers (tuple, one per dtype).

    Leaves may carry ``batch_dims`` leading axes (shared across the tree);
    buffers then have shape ``batch_shape + (padded,)``. ``dtype`` overrides
    every buffer's dtype (same layout) — used to keep momenta and oracle
    gradients in f32 buffers alongside low-precision variable buffers.
    """
    leaves = spec.treedef.flatten_up_to(tree)
    bufs = []
    for grp in spec.groups:
        out_dt = dtype if dtype is not None else grp.dtype
        batch_shape = tuple(
            jnp.shape(leaves[grp.leaves[0].index])[:batch_dims])
        parts, cursor = [], 0
        for lf in grp.leaves:
            if lf.offset > cursor:
                parts.append(jnp.zeros(batch_shape + (lf.offset - cursor,),
                                       out_dt))
            x = jnp.asarray(leaves[lf.index], out_dt)
            parts.append(x.reshape(batch_shape + (-1,)))
            cursor = lf.offset + lf.size
        if cursor < grp.padded:
            parts.append(jnp.zeros(batch_shape + (grp.padded - cursor,),
                                   out_dt))
        cont = (parts[0] if len(parts) == 1
                else jnp.concatenate(parts, axis=-1))
        bufs.append(_interleave(spec, grp, cont))
    return tuple(bufs)


def unflatten_tree(spec: FlatSpec, bufs):
    """Materialize the pytree view of flat buffers (slice + reshape only)."""
    leaves = [None] * spec.num_leaves
    for grp, buf in zip(spec.groups, bufs):
        cont = _deinterleave(spec, grp, buf)
        batch_shape = tuple(cont.shape[:-1])
        for lf in grp.leaves:
            seg = cont[..., lf.offset:lf.offset + lf.size]
            leaves[lf.index] = seg.reshape(batch_shape + lf.shape)
    return spec.treedef.unflatten(leaves)


def zeros_buffers(spec: FlatSpec, *, batch_shape: tuple = ()):
    return tuple(jnp.zeros(batch_shape + (g.padded,), g.dtype)
                 for g in spec.groups)


# ---------------------------------------------------------------------------
# Per-tile hyper-parameter tables and fused launches
# ---------------------------------------------------------------------------

def _tile_table(grp: _Group, buf, table):
    """Per-section scalar table → the per-tile table the kernel streams for
    ``buf``: [T] for an [N] buffer, [T, M] (tile-major, one column per
    client row) for an [M, N] one.  Under a P(data, model) sharding of the
    buffer, P(model, data) slices it consistently with the buffer."""
    col = jnp.stack(table)[grp.section_ids]
    if buf.ndim == 1:
        return col
    return jnp.broadcast_to(col[:, None], (col.shape[0], buf.shape[0]))


def _gate(lr_tiles, decay_tiles, mask, frozen_decay: float):
    """Gate per-tile (lr, decay|β) tables [T, M] with the participation mask
    [M]: non-participants get lr = 0 and decay pinned to ``frozen_decay``
    (1.0 freezes STORM/heavy-ball momenta bit-exact once their oracle
    contributions are zeroed)."""
    if mask is None:
        return lr_tiles, decay_tiles
    assert mask.shape == lr_tiles.shape[1:], (mask.shape, lr_tiles.shape)
    row = mask.astype(jnp.float32)[None, :]
    lr_tiles = lr_tiles * row
    if decay_tiles is not None:
        decay_tiles = jnp.where(row > 0, decay_tiles,
                                jnp.float32(frozen_decay))
    return lr_tiles, decay_tiles


def mask_buffers(bufs, mask):
    """Zero non-participant rows of [M, N] buffers (the "oracle skipped"
    half of the freeze: under vmap/SPMD every client computes, but a
    non-participant's gradients must not reach its momentum).  A ``where``
    select, not a multiply: participants pass through bit-identical, and a
    non-finite gradient on a skipped client's batch still zeroes out
    (0 · inf would poison the frozen momentum with NaN)."""
    if mask is None:
        return bufs
    return tuple(jnp.where(mask[:, None] > 0, b, jnp.zeros((), b.dtype))
                 for b in bufs)


def _dispatch(interpret):
    """Pick the lowering for the triple-sequence update.

    ``interpret=None`` (the default) → the Pallas kernel, compiled, on TPU;
    the bit-identical jnp lowering elsewhere (the interpreter validates the
    kernel but is far slower than XLA's fused loops). An explicit True/False
    forces the Pallas kernel with that interpret flag.
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return "pallas", False
        return "jnp", None
    return "pallas", interpret


def _check_shard(spec: FlatSpec, shard: ShardCtx, buf):
    assert buf.ndim == 2, \
        "the sharded substrate needs [M, N] buffers (batch_dims=1)"
    if spec.shards != shard.model_size:
        raise ValueError(
            f"spec was built for shards={spec.shards} but the mesh "
            f"{shard.model_axis} axis has size {shard.model_size}; rebuild "
            f"the spec with make_spec(..., shards={shard.model_size})")
    if buf.shape[0] % shard.data_size:
        raise ValueError(
            f"client axis M={buf.shape[0]} is not divisible by the mesh "
            f"{shard.data_axis} axis size {shard.data_size}")


def _launch(mode, flag, shard, spec, grp, kern_pallas, kern_jnp,
            bufs, tables, n_out: int):
    """One fused kernel launch on one dtype buffer, in the buffer's own
    layout: [N], or [M, N] streamed as (M, block) blocks with its [T, M]
    tables — or per device chunk under ``shard_map`` when ``shard`` is given
    (every device then streams its local [M/d, N/k] chunk with its [T/k,
    M/d] slice of the tables; the launch itself is collective-free)."""
    if mode == "pallas":
        fn = functools.partial(kern_pallas, block=grp.block, interpret=flag)
    else:
        fn = functools.partial(kern_jnp, block=grp.block)

    def body(*ops):
        outs = fn(*ops)
        return outs if n_out > 1 else (outs,)

    if shard is None:
        return body(*bufs, *tables)

    _check_shard(spec, shard, bufs[0])
    pb = shard.buffer_spec
    pt = PartitionSpec(shard.model_axis, shard.data_axis)
    return jax.shard_map(body, mesh=shard.mesh,
                         in_specs=(pb,) * len(bufs) + (pt,) * len(tables),
                         out_specs=(pb,) * n_out,
                         check_vma=False)(*bufs, *tables)


def storm_partial_step(spec: FlatSpec, var_bufs, mom_bufs, g_old_bufs,
                       lrs, decays, *, mask=None,
                       interpret: bool | None = None,
                       shard: ShardCtx | None = None):
    """One fused triple-sequence launch per dtype buffer:

        v_new  = v − lr_sec·m            (variable step, entering momentum)
        m_part = decay_sec·(m − g_old)   (partial STORM momentum)

    ``lrs``/``decays``: one scalar per section (traced OK). The correction
    ``m_part + g_new`` is a single elementwise add once the new-iterate
    oracle exists (after communication).

    ``mask``: optional per-client participation mask [M] — non-participants'
    tiles run with lr = 0 and decay = 1, so (with ``g_old`` zeroed via
    :func:`mask_buffers`) their variable and momentum rows are frozen
    bit-exact inside the same fused launch.

    ``shard``: run each launch under ``shard_map`` on the mesh (elementwise —
    no collective; the spec must have been built with matching ``shards``).
    """
    mode, flag = _dispatch(interpret)
    out_v, out_m = [], []
    for grp, v, m, go in zip(spec.groups, var_bufs, mom_bufs, g_old_bufs):
        lr_t, dc_t = _gate(_tile_table(grp, v, lrs),
                           _tile_table(grp, v, decays), mask, 1.0)
        vn, mn = _launch(mode, flag, shard, spec, grp,
                         storm3_step_flat, storm3_step_flat_jnp,
                         (v, m, go), (lr_t, dc_t), 2)
        out_v.append(vn)
        out_m.append(mn)
    return tuple(out_v), tuple(out_m)


def storm_full_update(spec: FlatSpec, var_bufs, mom_bufs, g_new_bufs,
                      g_old_bufs, lrs, decays, *,
                      interpret: bool | None = None,
                      shard: ShardCtx | None = None):
    """Full fused update (v − lr·m, g_new + decay·(m − g_old)) — usable when
    both oracle values are already in hand (benchmarks, single-shot tests)."""
    mode, flag = _dispatch(interpret)
    out_v, out_m = [], []
    for grp, v, m, gn, go in zip(spec.groups, var_bufs, mom_bufs,
                                 g_new_bufs, g_old_bufs):
        vn, mn = _launch(mode, flag, shard, spec, grp,
                         storm3_update_flat, storm3_update_flat_jnp,
                         (v, m, gn, go),
                         (_tile_table(grp, v, lrs),
                          _tile_table(grp, v, decays)), 2)
        out_v.append(vn)
        out_m.append(mn)
    return tuple(out_v), tuple(out_m)


def momentum_sgd_step(spec: FlatSpec, var_bufs, mom_bufs, g_bufs,
                      lrs, betas, *, mask=None,
                      interpret: bool | None = None,
                      shard: ShardCtx | None = None):
    """One fused heavy-ball launch per dtype buffer:

        m_new = β_sec·m + g        (momentum update — FedAvg ordering)
        v_new = v − lr_sec·m_new   (variable step with the *updated* momentum)

    Momentum-less specs (β = 0 everywhere, no momentum state) should use
    :func:`sgd_step` instead — same variable result without the dead
    momentum stream.

    ``mask``: optional per-client participation mask [M] — non-participants
    run with lr = 0 and β = 1 (identity momentum; pair with zeroed ``g`` via
    :func:`mask_buffers` for a bit-exact freeze).  ``shard``: as in
    :func:`storm_partial_step`.
    """
    mode, flag = _dispatch(interpret)
    out_v, out_m = [], []
    for grp, v, m, gb in zip(spec.groups, var_bufs, mom_bufs, g_bufs):
        lr_t, bt_t = _gate(_tile_table(grp, v, lrs),
                           _tile_table(grp, v, betas), mask, 1.0)
        vn, mn = _launch(mode, flag, shard, spec, grp,
                         momsgd3_step_flat, momsgd3_step_flat_jnp,
                         (v, m, gb), (lr_t, bt_t), 2)
        out_v.append(vn)
        out_m.append(mn)
    return tuple(out_v), tuple(out_m)


def sgd_step(spec: FlatSpec, var_bufs, g_bufs, lrs, *, mask=None,
             interpret: bool | None = None,
             shard: ShardCtx | None = None):
    """One fused plain-SGD launch per dtype buffer: v_new = v − lr_sec·g.

    The β = 0 fast path for momentum-less specs (FedBiO / FedBiO-Local):
    2 reads + 1 write per element — a pallas_call's outputs are opaque to
    XLA DCE, so the heavy-ball kernel would pay a full dead momentum write.

    ``mask``: optional per-client participation mask [M] — non-participants'
    tiles run with lr = 0 (v − 0·g = v, bit-exact freeze).  ``shard``: as in
    :func:`storm_partial_step`.
    """
    mode, flag = _dispatch(interpret)
    out_v = []
    for grp, v, gb in zip(spec.groups, var_bufs, g_bufs):
        lr_t, _ = _gate(_tile_table(grp, v, lrs), None, mask, 1.0)
        (vn,) = _launch(mode, flag, shard, spec, grp,
                        sgd3_step_flat, sgd3_step_flat_jnp,
                        (v, gb), (lr_t,), 1)
        out_v.append(vn)
    return tuple(out_v)


def buffers_add(a, b):
    """Elementwise a + b over buffer tuples (the STORM correction add)."""
    return tuple(x + y for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# Section-masked communication
# ---------------------------------------------------------------------------

def _weight_col(x, w):
    """Per-client weights → a broadcastable [M, 1, ...] column, rescaled so
    the *plain mean* of ``x · col`` is the participation-weighted mean:

        col_m = w_m · (M / Σ w)   ⇒   mean_m(x_m · col_m) = Σ w_m x_m / Σ w

    The rescale-into-the-mean form is what makes all-ones weights a
    bit-identical no-op (col = 1.0 exactly, x · 1.0 = x, then the same
    ``jnp.mean`` as the unweighted path).  Empty groups (Σw = 0) scale to 0;
    callers pass non-participants through with a ``where`` on ``col > 0``.
    """
    wsum = jnp.sum(w, axis=-1, keepdims=True)
    scale = jnp.where(wsum > 0, w.shape[-1] / wsum, 0.0)
    col = (w * scale).astype(x.dtype)
    return col.reshape(col.shape + (1,) * (x.ndim - col.ndim))


def _bcast_mean(x, w=None):
    """Full client mean over the leading axis, broadcast back (the paper's
    communication round — one all-reduce under pjit).  Mirrors
    ``core.tree_util.client_mean`` at array level; importing tree_util here
    would close an import cycle (optim.flat ← core ← optim.sequences).

    ``w``: optional per-client weights [M] (zero = non-participant): the mean
    is over participants only and non-participant rows pass through
    bit-identical (selected *around* the reduction, like private sections).
    """
    if w is None:
        return jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)
    col = _weight_col(x, w)
    m = jnp.broadcast_to(jnp.mean(x * col, axis=0, keepdims=True), x.shape)
    return jnp.where(col > 0, m, x)


def _bcast_mean_grouped(x, num_groups: int, w=None):
    """Pod-local grouped mean over contiguous client groups (hierarchical
    multi-pod schedule — the all-reduce stays on the intra-pod ICI).
    ``w`` as in :func:`_bcast_mean`, applied within each group."""
    M = x.shape[0]
    g = x.reshape((num_groups, M // num_groups) + x.shape[1:])
    if w is None:
        m = jnp.mean(g, axis=1, keepdims=True)
        return jnp.broadcast_to(m, g.shape).reshape(x.shape)
    col = _weight_col(g, w.reshape(num_groups, M // num_groups))
    m = jnp.broadcast_to(jnp.mean(g * col, axis=1, keepdims=True), g.shape)
    return jnp.where(col > 0, m, g).reshape(x.shape)


class RobustCfg(NamedTuple):
    """Robust-reduction policy of :func:`client_mean_masked` (the substrate
    half of ``repro.federation.faults.RobustnessSpec`` — defined here so the
    substrate stays import-free of the federation layer).

    ``screen`` enables the per-client health mask: a participant is healthy
    iff its (as-sent) row is all-finite and its update norm sits within
    ``z_thresh`` standard deviations of the healthy participants' mean norm
    (``z_thresh <= 0`` keeps the finite check only).  ``aggregator``:

    * ``"mean"`` — participants-only weighted mean over healthy rows (the
      same ``_weight_col`` arithmetic as the unguarded path, so an
      all-healthy round reproduces it bit-for-bit);
    * ``"clip"`` — per-client norm clipping to ``clip_factor`` x the healthy
      participants' weighted mean norm before the mean (row-local scaling —
      shard-local under ``shard_map``);
    * ``"trim"`` — coordinate-wise ``trim_frac``-trimmed mean over healthy
      participants (an order statistic: weight-agnostic, and gather-based on
      the sharded path — the one robust reduction that needs whole rows).
    """
    aggregator: str = "mean"
    screen: bool = True
    z_thresh: float = 3.0
    clip_factor: float = 2.0
    trim_frac: float = 0.2


class CompressCfg(NamedTuple):
    """Compressed-reduction policy of :func:`client_mean_masked` (the
    substrate half of ``repro.federation.compression.CompressionSpec`` —
    defined here, like :class:`RobustCfg`, so the substrate stays
    import-free of the federation layer).

    ``quant``: ``None`` | ``"bf16"`` | ``"int8"`` — the dtype the reduction
    moves.  bf16 is a cast; int8 is symmetric per-TILE quantization (one f32
    scale per ``block`` elements, the ``quantpack_flat`` kernel).  On the
    sharded path the per-device partial sums enter the collective in this
    dtype (int8 with a psum-shared per-tile scale), so the ``psum`` /
    ``psum_scatter`` wire itself narrows.

    ``topk_frac``: per-tile top-k sparsification of what each client sends —
    every client keeps the ``ceil(topk_frac · block)`` largest-magnitude
    entries of each tile of (row + error feedback).  Per-tile selection is
    identical on the unsharded buffer and on every ``shard_map`` chunk
    (tiles are the shard quantum), which is what keeps the two paths'
    compression decisions aligned.

    ``error_feedback``: carry the dropped mass per client (f32 buffers
    shaped like the communicated buffers — ``FlatState.ef``) and add it back
    into the next send.  Active only with ``topk_frac > 0``.

    ``sections``: section names to compress; () compresses every
    communicated run.  Composition limits (enforced upstream by
    ``sequences.make_engine`` / ``Experiment.validate``, asserted here):
    no ``corrupt=``/``robust=``, and ``topk_frac > 0`` excludes ``"group"``
    runs — error feedback against two different means is ill-defined, while
    plain quantization composes with the grouped mean.
    """
    quant: str | None = None
    topk_frac: float = 0.0
    error_feedback: bool = True
    sections: tuple = ()

    @property
    def has_ef(self) -> bool:
        return self.topk_frac > 0 and self.error_feedback


def _topk_tiles(x, block: int, frac: float):
    """Per-tile top-k sparsification (in f32): keep the k =
    ceil(frac · block) largest-magnitude entries of every ``block``-sized
    tile, zero the rest.  Threshold-based (``lax.top_k`` on |tile| gives the
    k-th magnitude): ties keep >= k entries — deterministic, and a zero tile
    "keeps" everything (all zeros — nothing is actually sent)."""
    t = x.reshape(x.shape[:-1] + (-1, block))
    k = max(1, int(np.ceil(frac * block)))
    thr = lax.top_k(jnp.abs(t), k)[0][..., -1:]
    return jnp.where(jnp.abs(t) >= thr, t, 0.0).reshape(x.shape)


def _quant_dequant(x, block: int, quant, mode, flag):
    """Round-trip ``x`` (f32) through the reduction dtype: the value error
    every client's send incurs.  int8 dispatches the ``quantpack_flat``
    pack/unpack kernel pair (bit-identical jnp lowering off-TPU)."""
    if quant is None:
        return x
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    assert quant == "int8", quant
    shape = x.shape
    v = x.reshape(-1)
    if mode == "pallas":
        q, s = quantpack_flat(v, block=block, interpret=flag)
        return quantunpack_flat(q, s, block=block, interpret=flag).reshape(shape)
    q, s = quantpack_flat_jnp(v, block=block)
    return quantunpack_flat_jnp(q, s, block=block).reshape(shape)


def _compress_sent(x, block: int, ccfg: CompressCfg, mode, flag):
    """What a client sends into one compressed reduction: per-tile top-k of
    (row + error feedback), then the quantization round-trip — all in f32."""
    if ccfg.topk_frac > 0:
        x = _topk_tiles(x, block, ccfg.topk_frac)
    return _quant_dequant(x, block, ccfg.quant, mode, flag)


def _compressed_mean(seg, eseg, w, ccfg: CompressCfg, block: int, mode, flag):
    """Compressed participant mean of one run (f32): every client sends
    ``_compress_sent(row + EF)``, the server broadcasts the participants'
    weighted mean of the sends, and the residual ``(row + EF) − sent`` goes
    back into the client's EF buffer.  Non-participants (w = 0) pass their
    row through bit-identical AND their EF rows freeze bit-exact — the
    residual is a property of a send, and they sent nothing."""
    acc = seg.astype(jnp.float32)
    if eseg is not None:
        acc = acc + eseg
    sent = _compress_sent(acc, block, ccfg, mode, flag)
    if w is None:
        out = jnp.broadcast_to(jnp.mean(sent, axis=0, keepdims=True),
                               seg.shape)
    else:
        col = _weight_col(sent, w)
        m = jnp.broadcast_to(jnp.mean(sent * col, axis=0, keepdims=True),
                             seg.shape)
        out = jnp.where(col > 0, m, seg.astype(jnp.float32))
    new_e = None
    if eseg is not None:
        new_e = acc - sent
        if w is not None:
            pc = (w > 0).reshape(w.shape + (1,) * (seg.ndim - 1))
            new_e = jnp.where(pc, new_e, eseg)
    return out, new_e


def _compressed_mean_grouped(seg, w, ccfg: CompressCfg, block: int,
                             num_groups: int, mode, flag):
    """Grouped (hierarchical) mean over quantized sends — quantization
    COMPOSES with the pod-local mean (each client's send is quantized once;
    the group mean over the sends is exact), the satellite fix for
    ``_bcast_mean_grouped``.  Top-k/EF is rejected upstream."""
    assert ccfg.topk_frac == 0, \
        "top-k compression does not compose with grouped means"
    sent = _quant_dequant(seg.astype(jnp.float32), block, ccfg.quant,
                          mode, flag)
    M = seg.shape[0]
    g = sent.reshape((num_groups, M // num_groups) + seg.shape[1:])
    if w is None:
        return jnp.broadcast_to(jnp.mean(g, axis=1, keepdims=True),
                                g.shape).reshape(seg.shape)
    col = _weight_col(g, w.reshape(num_groups, M // num_groups))
    m = jnp.broadcast_to(jnp.mean(g * col, axis=1, keepdims=True), g.shape)
    g0 = seg.astype(jnp.float32).reshape(g.shape)
    return jnp.where(col > 0, m, g0).reshape(seg.shape)


def _corrupt_rows(x, corrupt):
    """Apply the round's fault transform to what clients *send* into one
    reduction: ``corrupt = (nan, byz, scale)`` with [M] {0,1} masks — byz
    rows are scaled, nan rows replaced wholesale.  ``where`` selects, so
    unfaulted rows pass through bit-identical (corrupt=None is identity)."""
    if corrupt is None:
        return x
    nan, byz, scale = corrupt
    trail = (1,) * (x.ndim - 1)
    bc = byz.reshape(byz.shape + trail)
    nc = nan.reshape(nan.shape + trail)
    x = jnp.where(bc > 0, x * jnp.asarray(scale, x.dtype), x)
    return jnp.where(nc > 0, jnp.asarray(jnp.nan, x.dtype), x)


def _health_mask(x, p, robust: RobustCfg):
    """Per-client health [M] f32 of one run: participant ∧ all-finite row ∧
    update-norm z-score within ``z_thresh`` of the finite participants'
    stats.  Excluded rows are removed with ``where`` BEFORE the norm sums —
    never by zero weights (0 · NaN = NaN would poison the stats)."""
    red = tuple(range(1, x.ndim))
    h = p & jnp.all(jnp.isfinite(x), axis=red)
    if robust.z_thresh <= 0:
        return h.astype(jnp.float32)
    hc = h.reshape(h.shape + (1,) * (x.ndim - 1))
    sq = jnp.sum(jnp.square(jnp.where(hc, x, 0).astype(jnp.float32)),
                 axis=red)
    n = jnp.sqrt(sq)
    hf = h.astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(hf), 1.0)
    mu = jnp.sum(n * hf) / cnt
    sd = jnp.sqrt(jnp.sum(jnp.square(n - mu) * hf) / cnt)
    # relative tolerance: an all-equal-norm round has sd = 0 and must not
    # screen everyone out over float rounding in |n − mu|
    tol = robust.z_thresh * sd + 1e-4 * mu + 1e-12
    return (h & (jnp.abs(n - mu) <= tol)).astype(jnp.float32)


def _clip_rows(xh, n, w_eff, clip_factor: float):
    """Per-client norm clipping of healthy rows to ``clip_factor`` x the
    healthy participants' weighted mean norm (row-local — no cross-client
    mixing, so the sharded path applies it before its collective)."""
    wsum = jnp.maximum(jnp.sum(w_eff), 1e-12)
    tau = clip_factor * (jnp.sum(n * w_eff) / wsum)
    scale = jnp.minimum(1.0, tau / jnp.maximum(n, 1e-12))
    return xh * scale.reshape(scale.shape + (1,) * (xh.ndim - 1)).astype(
        xh.dtype)


def _trim_keep(M: int, nh, trim_frac: float, ndim: int):
    """(keep mask over sorted positions, trim count k) for a trimmed mean
    over ``nh`` (traced) healthy rows sorted to the front: positions
    [k, nh − k) survive, with k clamped so at least one row always does."""
    k = jnp.minimum(jnp.floor(trim_frac * nh),
                    jnp.maximum(jnp.floor((nh - 1.0) / 2.0), 0.0))
    idx = jnp.arange(M, dtype=jnp.float32).reshape((M,) + (1,) * (ndim - 1))
    return (idx >= k) & (idx < nh - k), k


def _trimmed_mean(xh, hf, trim_frac: float):
    """Coordinate-wise trimmed mean over healthy rows (keepdims row [1, ...]):
    excluded rows sort to the top as +inf and are never selected."""
    M = xh.shape[0]
    hc = hf.reshape(hf.shape + (1,) * (xh.ndim - 1))
    xs = jnp.sort(jnp.where(hc > 0, xh.astype(jnp.float32), jnp.inf), axis=0)
    nh = jnp.sum(hf)
    keep, k = _trim_keep(M, nh, trim_frac, xh.ndim)
    return (jnp.sum(jnp.where(keep, xs, 0.0), axis=0, keepdims=True)
            / jnp.maximum(nh - 2.0 * k, 1.0))


def _robust_bcast_mean(x0, w, corrupt, robust: RobustCfg | None):
    """Fault/robustness-aware participant mean of one communicated run.

    The fault transform applies to the reduction *input* — it models what a
    client sends, so private sections, cadence-skipped rounds and the
    client's own stored row are never corrupted.  With ``robust=None`` this
    is the UNGUARDED faulty mean: corrupted rows enter the sum and poison
    every participant (the failure mode the guards exist for).  With a
    :class:`RobustCfg`, unhealthy senders are screened out of the chosen
    aggregate and then *recovered* — they receive the aggregate instead of
    keeping a corrupted row — while non-participants always pass through
    their original row; if NO healthy weight remains, every row passes
    through unchanged (the round is retried by the rollback guard, not
    zeroed).
    """
    x = _corrupt_rows(x0, corrupt)
    if w is not None:
        # a zero-weight client SENDS nothing: its faults never reach the
        # round (0 x NaN would otherwise poison the unguarded sum)
        sc = (w > 0).reshape(w.shape + (1,) * (x.ndim - 1))
        x = jnp.where(sc, x, x0)
    if robust is None:
        if w is None:
            return jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True),
                                    x.shape)
        col = _weight_col(x, w)
        m = jnp.broadcast_to(jnp.mean(x * col, axis=0, keepdims=True),
                             x.shape)
        return jnp.where(col > 0, m, x0)
    M = x.shape[0]
    wv = jnp.ones((M,), jnp.float32) if w is None else w
    p = wv > 0
    hf = _health_mask(x, p, robust) if robust.screen \
        else p.astype(jnp.float32)
    hc = hf.reshape(hf.shape + (1,) * (x.ndim - 1))
    xh = jnp.where(hc > 0, x, jnp.zeros((), x.dtype))
    w_eff = wv * hf
    if robust.aggregator == "trim":
        m = _trimmed_mean(xh, hf, robust.trim_frac)
    else:
        if robust.aggregator == "clip":
            red = tuple(range(1, x.ndim))
            n = jnp.sqrt(jnp.sum(jnp.square(xh.astype(jnp.float32)),
                                 axis=red))
            xh = _clip_rows(xh, n, w_eff, robust.clip_factor)
        # same _weight_col + jnp.mean arithmetic as the unguarded path: an
        # all-healthy round (hf = 1, w_eff = wv bitwise) reproduces it
        # bit-for-bit
        m = jnp.mean(xh * _weight_col(x, w_eff), axis=0, keepdims=True)
    m = jnp.broadcast_to(m.astype(x0.dtype), x0.shape)
    pc = p.reshape(p.shape + (1,) * (x.ndim - 1))
    return jnp.where(pc & (jnp.sum(w_eff) > 0), m, x0)


def _normalize_weights(spec: FlatSpec, weights):
    n_sections = max(len(spec.sections), 1)
    if isinstance(weights, (tuple, list)):
        assert len(weights) == n_sections, (len(weights), n_sections)
        return tuple(weights)
    return (weights,) * n_sections


def _section_runs(grp: _Group, shards: int, modes, w_of_sec,
                  comp_of_sec=None):
    """Static (mode, weight, start, stop, compressed) element runs covering
    the whole buffer, built from the spec-time section extents; adjacent
    runs merge when the mode, the weight array AND the compression flag all
    coincide (``"none"`` runs merge unconditionally — private tiles are
    never reduced, so neither weight nor compression applies to them),
    including across shard-chunk boundaries."""
    S = grp.padded // shards
    runs: list = []
    for j in range(shards):
        for s, a, b in grp.extents:
            mode, w = modes[int(s)], w_of_sec[int(s)]
            comp = bool(comp_of_sec[int(s)]) if comp_of_sec else False
            start, stop = j * S + a, j * S + b
            if runs and runs[-1][0] == mode and runs[-1][3] == start and (
                    mode == "none" or (runs[-1][1] is w
                                       and runs[-1][4] == comp)):
                runs[-1][3] = stop
            else:
                runs.append([mode, w, start, stop, comp])
    return runs


_CHUNK = 1 << 12    # elements per in-cache chunk of a reduced run (CPU path)


def _chunk_len(n: int) -> int:
    c = n
    while c > _CHUNK and c % 2 == 0:
        c //= 2
    return c


def _update_run(buf, start: int, stop: int, upd, *, chunk: bool = True):
    """Write ``upd(segment)`` back into ``buf`` over the element run
    [start, stop) — a ``dynamic_update_slice``, so under buffer donation the
    reduction happens in place and the tiles outside the run are never
    copied.  On CPU large runs are chunked so each reduce + broadcast stays
    cache-resident (the broadcast re-reads the mean row once per client —
    from L1/L2 instead of RAM), which is what lets the sliced reduction beat
    the per-leaf tree-map path off-TPU.  ``chunk=False`` forces one whole-run
    call — required when ``upd`` computes cross-column row statistics (the
    robust reductions' health norms would otherwise be chunk-local)."""
    nd = buf.ndim
    length = stop - start
    c = (_chunk_len(length)
         if chunk and jax.default_backend() == "cpu" else length)
    if c == length:
        seg = buf[..., start:stop]
        return lax.dynamic_update_slice(buf, upd(seg).astype(buf.dtype),
                                        (0,) * (nd - 1) + (start,))

    def body(j, acc):
        o = start + j * c
        seg = lax.dynamic_slice(acc, (0,) * (nd - 1) + (o,),
                                acc.shape[:-1] + (c,))
        return lax.dynamic_update_slice(acc, upd(seg).astype(acc.dtype),
                                        (0,) * (nd - 1) + (o,))

    return lax.fori_loop(0, length // c, body, buf)


def client_mean_masked(spec: FlatSpec, bufs, modes, *, num_groups: int = 2,
                       weights=None, shard: ShardCtx | None = None,
                       corrupt=None, robust: RobustCfg | None = None,
                       compress: CompressCfg | None = None, ef=None):
    """Section-masked client communication over flat [M, N] buffers.

    ``modes``: one entry per section (aligned with ``spec.sections``; a
    single entry for unsectioned specs), each ``"none"`` (private — the
    section must not be communicated), ``"mean"`` (full client mean) or
    ``"group"`` (pod-local grouped mean over ``num_groups`` groups).

    ``weights``: optional participation weights — one [M] array shared by
    every section, or a tuple of per-section [M] arrays (staleness-discounted
    sequences).  Zero-weight clients are non-participants: the mean is taken
    over participants only and their rows pass through bit-identical.
    Masks compose multiplicatively into the weights upstream — the
    straggler engine's arrival mask and the fault layer's dropout mask
    both multiply in (``repro.optim.sequences._round_ctx``), so a
    deadline-missing or faulted client is just another ``w = 0`` row here
    and no straggler-specific reduction path exists.

    Sections are contiguous tile-aligned element runs of each dtype buffer,
    precomputed at spec-build time (``_Group.extents``) and coalesced across
    adjacent same-mode/same-weight sections: each communicated run is ONE
    sliced reduction written back in place, and ``"none"`` runs are simply
    never touched — private sections are bit-identical by construction and
    never enter a reduction (no wasted cross-client traffic).

    ``shard``: run under ``shard_map`` on the mesh — every device reduces
    its local columns with per-shard partial sums and ONE ``lax.psum`` (or
    ``psum_scatter`` + ``all_gather``) over the data axis per communicated
    run; private and non-participant tiles never enter the collective.

    ``corrupt``: optional ``(nan, byz, scale)`` round fault masks ([M] {0,1}
    arrays + scalar) applied to what clients *send* into each ``"mean"`` run
    (:func:`_corrupt_rows`).  ``robust``: optional :class:`RobustCfg` —
    health-screen the senders and reduce with the robust aggregator
    (:func:`_robust_bcast_mean`).  Both compose with ``"none"`` sections
    (private state is never corrupted or reduced) but not with ``"group"``
    runs — the robust reductions are global (enforced upstream by
    ``Experiment.validate`` / ``sequences.make_engine``).

    ``compress``: optional :class:`CompressCfg` — the named sections'
    reductions move quantized and/or top-k-sparsified sends
    (:func:`_compressed_mean`; on the sharded path the collective itself
    moves the narrow dtype).  With ``compress`` set the call returns
    ``(bufs, ef)`` — ``ef`` being the updated per-client error-feedback
    buffers (pass the current ones via ``ef=``; the empty tuple when
    ``compress.has_ef`` is false).  ``compress=None`` (the default) keeps
    the original signature and a bit-identical trajectory.  Compression
    does not compose with ``corrupt=``/``robust=`` (the guarded reductions
    consume raw client rows — enforced upstream, asserted here).
    """
    n_sections = max(len(spec.sections), 1)
    assert len(modes) == n_sections, (modes, spec.sections)
    assert all(m in ("none", "mean", "group") for m in modes), modes
    guarded = corrupt is not None or robust is not None
    if guarded:
        assert all(m in ("none", "mean") for m in modes), (
            "corrupt=/robust= do not compose with grouped (hierarchical) "
            "means", modes)
    comp_of_sec = None
    if compress is not None:
        assert not guarded, (
            "compress= does not compose with corrupt=/robust= — enforced "
            "upstream by sequences.make_engine / Experiment.validate")
        if compress.topk_frac > 0:
            assert all(m in ("none", "mean") for m in modes), (
                "top-k compression does not compose with grouped "
                "(hierarchical) means — enforced upstream", modes)
        names = spec.sections if spec.sections else ("",)
        comp_of_sec = tuple(
            (not compress.sections) or (nm in compress.sections)
            for nm in names)
    w_of_sec = _normalize_weights(spec, weights)
    if shard is not None:
        return _client_mean_masked_sharded(spec, bufs, modes, num_groups,
                                           w_of_sec, shard,
                                           corrupt=corrupt, robust=robust,
                                           compress=compress,
                                           comp_of_sec=comp_of_sec, ef=ef)
    has_ef = compress is not None and compress.has_ef
    ebufs = tuple(ef) if ef else (None,) * len(spec.groups)
    if has_ef:
        assert len(ebufs) == len(spec.groups), (
            "compress with error feedback needs one f32 EF buffer per "
            "dtype group (pass ef=)", len(ebufs), len(spec.groups))
    kmode, kflag = _dispatch(None)
    out, ef_out = [], []
    for gi, (grp, buf) in enumerate(zip(spec.groups, bufs)):
        assert buf.ndim >= 2, "client_mean_masked needs a leading client axis"
        ebuf = ebufs[gi] if has_ef else None
        for mode, w, start, stop, comp in _section_runs(
                grp, spec.shards, modes, w_of_sec, comp_of_sec):
            if mode == "none":
                continue
            nd = buf.ndim
            if comp:
                # compressed runs are whole-run and pair the buffer write
                # with the EF write (the top-k/quant tiles are block-local;
                # the CPU cache chunking could split a tile)
                seg = buf[..., start:stop]
                eseg = (ebuf[..., start:stop]
                        if (ebuf is not None and mode == "mean") else None)
                if mode == "mean":
                    upd, new_e = _compressed_mean(seg, eseg, w, compress,
                                                  grp.block, kmode, kflag)
                else:
                    upd = _compressed_mean_grouped(seg, w, compress,
                                                   grp.block, num_groups,
                                                   kmode, kflag)
                    new_e = None
                buf = lax.dynamic_update_slice(
                    buf, upd.astype(buf.dtype), (0,) * (nd - 1) + (start,))
                if new_e is not None:
                    ebuf = lax.dynamic_update_slice(
                        ebuf, new_e.astype(ebuf.dtype),
                        (0,) * (nd - 1) + (start,))
                continue
            if mode == "mean":
                if guarded:
                    upd = functools.partial(
                        lambda s, w: _robust_bcast_mean(s, w, corrupt,
                                                        robust), w=w)
                else:
                    upd = functools.partial(lambda s, w: _bcast_mean(s, w),
                                            w=w)
            else:
                upd = functools.partial(
                    lambda s, w: _bcast_mean_grouped(s, num_groups, w), w=w)
            # the guarded reduction's health norms span the whole run — the
            # CPU cache chunking would make them chunk-local
            buf = _update_run(buf, start, stop, upd, chunk=not guarded)
        out.append(buf)
        ef_out.append(ebuf)
    if compress is None:
        return tuple(out)
    return tuple(out), (tuple(ef_out) if has_ef else ())


def _group_index_sets(shard: ShardCtx, num_groups: int):
    """Contiguous device groups along the data axis for the pod-local mean
    (``axis_index_groups`` of the grouped psum)."""
    d = shard.data_size
    if d % num_groups:
        raise ValueError(
            f"hierarchy_groups={num_groups} must divide the mesh "
            f"{shard.data_axis} axis size {d} on the sharded path")
    per = d // num_groups
    return [[g * per + i for i in range(per)] for g in range(num_groups)]


def _allreduce(x, shard: ShardCtx, groups):
    """True all-reduce of per-shard partial sums over the data axis: one
    ``psum`` — or its ``psum_scatter`` + ``all_gather`` decomposition
    (``use_scatter``), the form XLA can software-pipeline with compute."""
    if (shard.use_scatter and groups is None
            and x.shape[-1] % shard.data_size == 0):
        piece = lax.psum_scatter(x, shard.data_axis,
                                 scatter_dimension=x.ndim - 1, tiled=True)
        return lax.all_gather(piece, shard.data_axis, axis=x.ndim - 1,
                              tiled=True)
    return lax.psum(x, shard.data_axis, axis_index_groups=groups)


def _wire_allreduce(partial, quant, block: int, shard: ShardCtx, gidx,
                    nsum: int):
    """All-reduce of per-device f32 partial sums [L] in the WIRE dtype —
    what makes the collective itself cheap, not just the sends.

    * bf16 — cast, reduce, cast back (the collective moves 2 B/elem).
    * int8 — symmetric per-tile quantization on a scale SHARED by the
      ``nsum`` devices being summed: ``s = psum(per-tile absmax) /
      (127 − nsum/2)``.  The headroom term bounds the reduced value away
      from wraparound — each device's |q| <= absmax/s + 1/2 from rounding,
      so Σ|q| <= (127 − nsum/2) + nsum/2 = 127 exactly (XLA integer adds
      WRAP, they do not saturate).  The scale exchange is one tiny f32 psum
      of [L/block] — the 4/block bytes/elem overhead of the bytes model.
    * None (top-k only) — dense f32: sparsity does not shrink a psum.
    """
    if quant is None:
        return _allreduce(partial, shard, gidx)
    if quant == "bf16":
        return _allreduce(partial.astype(jnp.bfloat16), shard,
                          gidx).astype(jnp.float32)
    assert quant == "int8", quant
    t = partial.reshape(-1, block)
    amax = jnp.max(jnp.abs(t), axis=-1)
    gmax = lax.psum(amax, shard.data_axis, axis_index_groups=gidx)
    s = gmax / (127.0 - 0.5 * nsum)
    safe = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(jnp.round(t / safe[:, None]), -127.0, 127.0).astype(jnp.int8)
    totq = _allreduce(q.reshape(partial.shape), shard, gidx)
    return (totq.reshape(t.shape).astype(jnp.float32)
            * s[:, None]).reshape(partial.shape)


def _robust_mean_sharded(seg0, seg, w_l, robust: RobustCfg | None,
                         shard: ShardCtx, M: int):
    """The guarded participant mean of one run inside the ``shard_map`` body
    (the sharded mirror of :func:`_robust_bcast_mean`): per-client row stats
    (finiteness, norms) are completed with a ``psum`` over the MODEL axis —
    each device holds a column slice of every local row — screening and
    aggregation stats with ``psum``s over the DATA axis, clipping stays
    row-local, and the trimmed mean ``all_gather``s rows over the data axis
    (the documented gather-based sharding: an order statistic needs whole
    rows).  ``seg`` is the corrupted (as-sent) local chunk, ``seg0`` the
    original pass-through rows."""
    da, ma = shard.data_axis, shard.model_axis
    wv = (jnp.ones((seg.shape[0],), jnp.float32) if w_l is None else w_l)
    p = wv > 0
    if w_l is not None:
        # a zero-weight client SENDS nothing: its faults never reach the
        # round (0 x NaN would otherwise poison the unguarded sum)
        seg = jnp.where(p[:, None], seg, seg0)
    if robust is None:
        # unguarded faulty mean: corrupted rows enter the sum (and poison it)
        wsum = lax.psum(jnp.sum(wv), da)
        scale = jnp.where(wsum > 0, M / wsum, 0.0)
        col = (wv * scale).astype(seg.dtype)[:, None]
        tot = _allreduce(jnp.sum(seg * col, axis=0), shard, None)
        m = jnp.broadcast_to((tot / M)[None].astype(seg0.dtype), seg0.shape)
        return m if w_l is None else jnp.where(col > 0, m, seg0)
    if robust.screen:
        nonfinite = lax.psum(
            jnp.sum(~jnp.isfinite(seg), axis=1).astype(jnp.float32), ma)
        h = p & (nonfinite == 0)
    else:
        h = p
    sq = lax.psum(jnp.sum(jnp.square(
        jnp.where(h[:, None], seg, 0).astype(jnp.float32)), axis=1), ma)
    n = jnp.sqrt(sq)
    hf = h.astype(jnp.float32)
    if robust.screen and robust.z_thresh > 0:
        cnt = jnp.maximum(lax.psum(jnp.sum(hf), da), 1.0)
        mu = lax.psum(jnp.sum(n * hf), da) / cnt
        sd = jnp.sqrt(lax.psum(jnp.sum(jnp.square(n - mu) * hf), da) / cnt)
        tol = robust.z_thresh * sd + 1e-4 * mu + 1e-12
        h = h & (jnp.abs(n - mu) <= tol)
        hf = h.astype(jnp.float32)
    w_eff = wv * hf
    wsum_eff = lax.psum(jnp.sum(w_eff), da)
    if robust.aggregator == "trim":
        xs = jnp.sort(lax.all_gather(
            jnp.where(h[:, None], seg.astype(jnp.float32), jnp.inf),
            da, axis=0, tiled=True), axis=0)
        nh = lax.psum(jnp.sum(hf), da)
        keep, k = _trim_keep(M, nh, robust.trim_frac, 2)
        m = (jnp.sum(jnp.where(keep, xs, 0.0), axis=0, keepdims=True)
             / jnp.maximum(nh - 2.0 * k, 1.0))
    else:
        xh = jnp.where(h[:, None], seg, jnp.zeros((), seg.dtype))
        if robust.aggregator == "clip":
            tau = robust.clip_factor * (
                lax.psum(jnp.sum(n * w_eff), da)
                / jnp.maximum(wsum_eff, 1e-12))
            sc = jnp.minimum(1.0, tau / jnp.maximum(n, 1e-12))
            xh = xh * sc[:, None].astype(xh.dtype)
        scale = jnp.where(wsum_eff > 0, M / wsum_eff, 0.0)
        col = (w_eff * scale).astype(seg.dtype)[:, None]
        tot = _allreduce(jnp.sum(xh * col, axis=0), shard, None)
        m = (tot / M)[None]
    m = jnp.broadcast_to(m.astype(seg0.dtype), seg0.shape)
    return jnp.where(p[:, None] & (wsum_eff > 0), m, seg0)


def _client_mean_masked_sharded(spec: FlatSpec, bufs, modes, num_groups,
                                w_of_sec, shard: ShardCtx,
                                corrupt=None, robust: RobustCfg | None = None,
                                compress: CompressCfg | None = None,
                                comp_of_sec=None, ef=None):
    guarded = corrupt is not None or robust is not None
    assert compress is None or not guarded
    has_ef = compress is not None and compress.has_ef
    ebufs = tuple(ef) if ef else (None,) * len(spec.groups)
    kmode, kflag = _dispatch(None)
    # the fault masks ride the shard_map as [M]-over-"data" operands, like
    # the participation weights
    cops = () if corrupt is None else (corrupt[0], corrupt[1])
    cscale = None if corrupt is None else corrupt[2]
    out, ef_out = [], []
    for gi, (grp, buf) in enumerate(zip(spec.groups, bufs)):
        _check_shard(spec, shard, buf)
        M = buf.shape[0]
        nds = shard.data_size
        # one run list per SHARD CHUNK (the extents are per-chunk already) —
        # identical on every model shard, so the SPMD program's static
        # slices line up on all devices
        runs = _section_runs(grp, 1, modes, w_of_sec, comp_of_sec)
        if all(r[0] == "none" for r in runs):
            out.append(buf)
            ef_out.append(ebufs[gi] if has_ef else None)
            continue
        groups_idx = (_group_index_sets(shard, num_groups)
                      if any(r[0] == "group" for r in runs) else None)
        # distinct weight arrays become shard_map operands ([M] over "data")
        ws: list = []
        w_idx: list = []
        for mode, w, _, _, _ in runs:
            if w is None or mode == "none":
                w_idx.append(None)
                continue
            for k, a in enumerate(ws):
                if a is w:
                    w_idx.append(k)
                    break
            else:
                ws.append(w)
                w_idx.append(len(ws) - 1)
        ebuf = ebufs[gi] if has_ef else None

        def body(b, *ops, runs=runs, w_idx=w_idx, groups_idx=groups_idx):
            e = ops[0] if has_ef else None
            off = 1 if has_ef else 0
            wloc, cloc = ops[off:off + len(ws)], ops[off + len(ws):]
            for (mode, _, a, stop, comp), wi in zip(runs, w_idx):
                if mode == "none":
                    continue        # private tiles never enter the collective
                seg = b[:, a:stop]
                if guarded and mode == "mean":
                    corr = ((cloc[0], cloc[1], cscale) if cloc else None)
                    upd = _robust_mean_sharded(
                        seg, _corrupt_rows(seg, corr),
                        wloc[wi] if wi is not None else None,
                        robust, shard, M)
                    b = lax.dynamic_update_slice(b, upd.astype(b.dtype),
                                                 (0, a))
                    continue
                gidx = groups_idx if mode == "group" else None
                denom = M // num_groups if mode == "group" else M
                if comp:
                    # compressed run: per-client sends are sparsified +
                    # quantized exactly as on the unsharded path (per-tile —
                    # the shard-major layout keeps tile boundaries aligned),
                    # then the per-device partial sums cross the wire in the
                    # narrow dtype (_wire_allreduce)
                    nsum = nds // num_groups if mode == "group" else nds
                    eseg = (e[:, a:stop]
                            if (e is not None and mode == "mean") else None)
                    acc = seg.astype(jnp.float32)
                    if eseg is not None:
                        acc = acc + eseg
                    sent = _compress_sent(acc, grp.block, compress,
                                          kmode, kflag)
                    if wi is None:
                        col = None
                        partial = jnp.sum(sent, axis=0)
                    else:
                        w_l = wloc[wi]
                        wsum = lax.psum(jnp.sum(w_l), shard.data_axis,
                                        axis_index_groups=gidx)
                        scale = jnp.where(wsum > 0, denom / wsum, 0.0)
                        col = (w_l * scale).astype(jnp.float32)[:, None]
                        partial = jnp.sum(sent * col, axis=0)
                    tot = _wire_allreduce(partial, compress.quant, grp.block,
                                          shard, gidx, nsum)
                    m = (tot / denom)[None]
                    upd = (jnp.broadcast_to(m, seg.shape) if col is None
                           else jnp.where(col > 0, m,
                                          seg.astype(jnp.float32)))
                    b = lax.dynamic_update_slice(b, upd.astype(b.dtype),
                                                 (0, a))
                    if eseg is not None:
                        new_e = acc - sent
                        if wi is not None:
                            new_e = jnp.where((wloc[wi] > 0)[:, None],
                                              new_e, eseg)
                        e = lax.dynamic_update_slice(
                            e, new_e.astype(e.dtype), (0, a))
                    continue
                if wi is None:
                    tot = _allreduce(jnp.sum(seg, axis=0), shard, gidx)
                    upd = jnp.broadcast_to((tot / denom)[None].astype(b.dtype),
                                           seg.shape)
                else:
                    w_l = wloc[wi]
                    wsum = lax.psum(jnp.sum(w_l), shard.data_axis,
                                    axis_index_groups=gidx)
                    scale = jnp.where(wsum > 0, denom / wsum, 0.0)
                    col = (w_l * scale).astype(seg.dtype)[:, None]
                    tot = _allreduce(jnp.sum(seg * col, axis=0), shard, gidx)
                    upd = jnp.where(col > 0, (tot / denom)[None], seg)
                b = lax.dynamic_update_slice(b, upd.astype(b.dtype), (0, a))
            return (b, e) if has_ef else b

        pb = shard.buffer_spec
        pw = PartitionSpec(shard.data_axis)
        ins = ((pb,) + ((pb,) if has_ef else ())
               + (pw,) * (len(ws) + len(cops)))
        res = jax.shard_map(body, mesh=shard.mesh, in_specs=ins,
                            out_specs=((pb, pb) if has_ef else pb),
                            check_vma=False)(
            buf, *((ebuf,) if has_ef else ()), *ws, *cops)
        if has_ef:
            out.append(res[0])
            ef_out.append(res[1])
        else:
            out.append(res)
            ef_out.append(None)
    if compress is None:
        return tuple(out)
    return tuple(out), (tuple(ef_out) if has_ef else ())


# ---------------------------------------------------------------------------
# Telemetry metrics (read-only side outputs — never touch a trajectory)
# ---------------------------------------------------------------------------

def _section_cols(spec: FlatSpec, grp: _Group) -> dict:
    """Absolute column ranges of every section in one (possibly shard-major)
    buffer: ``{section_index: [(start, stop), ...]}``.  Extents cover one
    shard chunk, so a sharded layout repeats them at every chunk offset."""
    chunk = grp.padded // spec.shards
    out: dict = {}
    for s, a, b in grp.extents:
        for j in range(spec.shards):
            out.setdefault(s, []).append((j * chunk + a, j * chunk + b))
    return out


def section_norms(spec: FlatSpec, bufs, *, mask=None, prefix="norm") -> dict:
    """Per-section l2 norms of flat [M, N] buffers (telemetry side output):
    ``{"<prefix>/<section>": scalar}``.  ``mask`` [M] restricts the sum to
    participant rows (selected with ``where`` — a zero row never multiplies
    a NaN).  Section padding is zero by construction and contributes
    nothing; runs at the jit level outside ``shard_map``, so sharded
    buffers reduce through XLA's own partitioning."""
    names = spec.sections or ("all",)
    sq: dict = {}
    for grp, buf in zip(spec.groups, bufs):
        x = buf.astype(jnp.float32)
        if mask is not None:
            mcol = (mask > 0).reshape(mask.shape + (1,) * (x.ndim - 1))
            x = jnp.where(mcol, x, 0.0)
        for s, runs in _section_cols(spec, grp).items():
            for a, b in runs:
                sq[s] = sq.get(s, 0.0) + jnp.sum(jnp.square(x[..., a:b]))
    return {f"{prefix}/{names[s]}": jnp.sqrt(v) for s, v in sorted(sq.items())}


def section_drift(spec: FlatSpec, bufs, *, mask=None,
                  prefix="drift") -> dict:
    """Per-section client-drift dispersion (telemetry side output): the rms
    distance of participant rows to the participants' mean row — the
    non-IID heterogeneity term, measured on the LOCAL iterates before
    averaging.  Same masking/sharding posture as :func:`section_norms`."""
    names = spec.sections or ("all",)
    M = bufs[0].shape[0]
    w = (jnp.ones((M,), jnp.float32) if mask is None
         else (mask > 0).astype(jnp.float32))
    cnt = jnp.maximum(jnp.sum(w), 1.0)
    wcol = w[:, None]
    sq: dict = {}
    for grp, buf in zip(spec.groups, bufs):
        x = jnp.where(wcol > 0, buf.astype(jnp.float32), 0.0)
        for s, runs in _section_cols(spec, grp).items():
            for a, b in runs:
                seg = x[..., a:b]
                m = jnp.sum(seg * wcol, axis=0, keepdims=True) / cnt
                sq[s] = sq.get(s, 0.0) + jnp.sum(
                    jnp.square(seg - m) * wcol)
    return {f"{prefix}/{names[s]}": jnp.sqrt(v / cnt)
            for s, v in sorted(sq.items())}


def quant_roundtrip_err(bufs, block: int, quant) -> jnp.ndarray:
    """l2 norm of the quantization round-trip error over ``bufs`` — the
    value error the next compressed send of these buffers would incur
    (telemetry side output; always the jnp lowering, which is bit-identical
    to the Pallas pack/unpack kernels)."""
    sq = 0.0
    for b in bufs:
        x = b.astype(jnp.float32)
        d = _quant_dequant(x, block, quant, "jnp", False) - x
        sq = sq + jnp.sum(jnp.square(d))
    return jnp.sqrt(sq)


def health_screen(spec: FlatSpec, bufs, mask, corrupt,
                  robust: RobustCfg) -> jnp.ndarray:
    """Recomputed health-screen verdicts for telemetry: [M] f32, 1 where a
    participant would FAIL the screen on what it sends this round.  The
    non-finite component reproduces the reduction's screen exactly; the
    z-score is taken over whole-row norms rather than per section run (an
    audit approximation — the guarded reduction itself is untouched)."""
    x = jnp.concatenate([b.astype(jnp.float32) for b in bufs], axis=-1)
    x = _corrupt_rows(x, corrupt)
    M = x.shape[0]
    p = jnp.ones((M,), bool) if mask is None else mask > 0
    h = _health_mask(x, p, robust)
    return p.astype(jnp.float32) * (1.0 - h)
