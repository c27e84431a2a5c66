"""Sequence-spec engine: every federated algorithm on the flat substrate.

The five federated algorithms (FedBiO, FedBiOAcc, their local-lower-level
variants, FedAvg) are all "advance K named optimizer sequences, then
communicate some of them".  What differs between them is *declarative*, not
structural:

* which **variable sections** exist (x body, y head, u auxiliary — or a
  single ``params`` section for FedAvg);
* whether each section carries a **STORM momentum** (FedBiOAcc family), a
  heavy-ball momentum (FedAvg) or none (FedBiO family);
* which cfg fields hold the **lr / STORM-constant** for each sequence; and
* the **communication policy** of each sequence.

This module captures that as a small datatype — :class:`Sequence` /
:class:`AlgoSpec` — plus a generic fused step engine (:func:`make_engine`)
that compiles a spec into the flat-substrate loop of ``repro.optim.flat``:

    old-iterate oracle  →  fused Pallas partial step  →
    policy-driven communication  →  correction add  (+ momentum comm)

for the STORM kind, and ``oracle → fused heavy-ball/SGD launch →
communication`` for the non-STORM kind.  The per-section (lr, decay|β)
scalars ride the kernels' per-tile SMEM tables, so a *dual*-sequence spec
(Alg. 4: x/ν averaged, y/ω private) runs on the same triple-sequence kernels
as the full FedBiOAcc spec — sections are just tile runs.

Communication policies (per sequence)
-------------------------------------

``PRIVATE``
    Never communicated.  The paper's local-lower-level regime (Eq. 5):
    per-client lower variables y^(m) and their momenta stay on-client.  On
    the flat substrate the section's tiles are sliced *around* the reduction
    (``flat.client_mean_masked``) — bit-identical pass-through, no traffic.
``HIERARCHICAL``
    Averaged every ``cfg.local_steps`` steps, honoring the beyond-paper
    hierarchical multi-pod schedule: with ``cfg.hierarchy_period = k > 0``
    only every k-th round crosses pod groups (pod-local grouped mean
    otherwise, cross-pod traffic ÷ k).  With ``hierarchy_period = 0`` this
    is exactly the paper's flat averaging.  This is the default policy for
    every communicated sequence — all five algorithms now honor the
    hierarchical schedule (previously only fedbio/fedbioacc did).
``AVERAGED``
    Full client mean every ``cfg.local_steps`` steps, *ignoring* the
    hierarchical schedule — for state that must stay globally consistent
    even during pod-local rounds (e.g. a future server-side control state).

The same policies drive the unfused tree paths through :func:`comm_tree`,
so fused and unfused trajectories see identical communication events.

Participation & staleness (``repro.federation.participation``)
--------------------------------------------------------------

:func:`make_engine` takes ``participation=``: a compiled
:class:`~repro.federation.participation.Participation` whose per-round client
mask [M] gates the whole step — non-participants' oracle contributions are
zeroed, their buffers are frozen bit-exact inside the fused launches
(masked lr = 0, decay/β pinned to 1), and the reductions average
*participants only* (``flat.client_mean_masked(..., weights=)``) while
non-participant rows pass through bit-identical.  Per-client staleness
counters (rounds missed since last participation) ride
:class:`FlatState` ``.stale`` and advance at every communication step.

Two per-sequence async knobs decouple the communication cadence:

* ``Sequence.comm_every = k`` — the sequence enters a reduction only every
  k-th communication round (its correction term simply ages in between);
* ``Sequence.staleness = α`` — a returning client's contribution to THIS
  sequence's reduction is discounted by α^staleness (α = 1: no discount;
  ``None`` inherits ``ParticipationSpec.stale_discount``), so stale local
  corrections fade instead of polluting the fresh average.

Mesh sharding & comm/compute overlap
------------------------------------

:func:`make_engine` takes ``shard=`` (a :class:`repro.optim.flat.ShardCtx`):
the spec is built with ``shards =`` the mesh "model"-axis size (tile-aligned
shard-major layout), :class:`FlatState` buffers carry ``NamedSharding``s
built from ``repro.sharding.rules.flat_state_specs`` (client axis M over
"data", packed parameter axis N over "model"), and every fused launch and
masked reduction runs under ``shard_map`` — the participant mean lowers to
per-shard partial sums + true ``lax.psum``/``psum_scatter`` over "data"
instead of the single-device broadcast mean.

``overlap=True`` re-schedules the STORM round as *issue the
variable-section reduction → run the new-iterate oracle → consume the
correction add*: the new-iterate oracle reads the LOCAL (pre-reduction)
iterate, so its output g_new feeds only the correction add and the issued
"data"-axis collective has no consumer until the step returns — XLA's async
collectives can then overlap the all-reduce with oracle compute, at
unchanged communication volume.  Documented deviation (mirroring the
existing "old point" deviation): at communication steps the STORM
correction is evaluated at the pre-averaging local iterate.  Off by
default; ``overlap=False`` is bit-identical to the sequential schedule, and
at non-communication steps the two schedules coincide exactly.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.tree_util import (client_mean, client_mean_grouped,
                                  client_mean_grouped_weighted,
                                  client_mean_weighted)
from repro.optim import flat
from repro.telemetry.trace import annotate

AVERAGED = "averaged"
HIERARCHICAL = "hierarchical"
PRIVATE = "private"
POLICIES = (AVERAGED, HIERARCHICAL, PRIVATE)


class Sequence(NamedTuple):
    """One named optimizer sequence: a variable section and its momentum."""
    section: str            # variable section name ("x", "y", "u", "params")
    momentum: str           # momentum sequence name ("nu", "omega", "q", ...)
    lr: str                 # FederatedConfig field holding the learning rate
    decay: str | None = None  # cfg field of the STORM constant (storm kind)
    comm: str = HIERARCHICAL  # communication policy
    comm_every: int = 1     # reduce only every k-th comm round (async cadence)
    staleness: float | None = None  # α stale-client discount (None → inherit
    #   ParticipationSpec.stale_discount; 1.0 → no discounting)


class AlgoSpec(NamedTuple):
    """Declarative algorithm description the engine compiles."""
    name: str
    kind: str               # "storm" (two-oracle STORM) | "sgd" (heavy-ball)
    sequences: tuple        # of Sequence, in buffer section order
    beta: float = 0.0       # heavy-ball momentum ("sgd" kind; 0 = plain SGD)
    carry_momentum: bool = False  # keep momentum state even at beta == 0
    #   (fedavg's state always carries mom; fedbio's never does)

    @property
    def sections(self):
        return tuple(s.section for s in self.sequences)

    @property
    def policies(self):
        return tuple(s.comm for s in self.sequences)

    @property
    def has_momentum(self) -> bool:
        return self.kind == "storm" or self.beta != 0.0 or self.carry_momentum

    def without_hierarchy(self) -> "AlgoSpec":
        """HIERARCHICAL → AVERAGED: the paper's flat averaging regardless of
        ``cfg.hierarchy_period`` (the core reference loops use this so
        ``fuse_storm`` stays a pure perf switch there — the hierarchical
        schedule is a model-scale trainer feature)."""
        return self._replace(sequences=tuple(
            q._replace(comm=AVERAGED) if q.comm == HIERARCHICAL else q
            for q in self.sequences))


# The five federated algorithms as specs.  FedAvg's β is a caller knob, not
# a cfg field — makers use ``SPECS["fedavg"]._replace(beta=...)``.
SPECS = {
    "fedbio": AlgoSpec("fedbio", "sgd", (
        Sequence("x", "nu", "lr_x"),
        Sequence("y", "omega", "lr_y"),
        Sequence("u", "q", "lr_u"),
    )),
    "fedbioacc": AlgoSpec("fedbioacc", "storm", (
        Sequence("x", "nu", "lr_x", "c_nu"),
        Sequence("y", "omega", "lr_y", "c_omega"),
        Sequence("u", "q", "lr_u", "c_u"),
    )),
    "fedbio_local": AlgoSpec("fedbio_local", "sgd", (
        Sequence("x", "nu", "lr_x"),
        Sequence("y", "omega", "lr_y", comm=PRIVATE),
    )),
    "fedbioacc_local": AlgoSpec("fedbioacc_local", "storm", (
        Sequence("x", "nu", "lr_x", "c_nu"),
        Sequence("y", "omega", "lr_y", "c_omega", comm=PRIVATE),
    )),
    "fedavg": AlgoSpec("fedavg", "sgd", (
        Sequence("params", "mom", "lr_x"),
    ), beta=0.9, carry_momentum=True),
}


def alpha_schedule(cfg, t):
    """The paper's α_t = δ/(u0 + t)^{1/3} STORM schedule."""
    return cfg.alpha_delta / (cfg.alpha_u0 + t.astype(jnp.float32)) ** (1.0 / 3.0)


def with_comm_every(aspec: AlgoSpec, cadences: dict) -> AlgoSpec:
    """Override per-sequence communication cadences by SECTION name (the
    ``Experiment.schedule.comm_every`` knob): ``{"u": 2}`` makes the u
    sequence enter a reduction only every 2nd comm round."""
    unknown = set(cadences) - set(aspec.sections)
    if unknown:
        raise ValueError(f"comm_every names unknown sections "
                         f"{sorted(unknown)} (spec {aspec.name!r} has "
                         f"{aspec.sections})")
    if any(int(k) < 1 for k in cadences.values()):
        raise ValueError(f"comm_every cadences must be >= 1: {cadences}")
    return aspec._replace(sequences=tuple(
        q._replace(comm_every=int(cadences[q.section]))
        if q.section in cadences else q
        for q in aspec.sequences))


# ---------------------------------------------------------------------------
# Policy-driven communication
# ---------------------------------------------------------------------------

def _round_preds(cfg, step):
    is_comm = (step + 1) % cfg.local_steps == 0
    round_idx = (step + 1) // cfg.local_steps
    is_global = round_idx % max(cfg.hierarchy_period, 1) == 0
    return is_comm, is_global


def comm_tree(cfg, step, tree, policy: str, *, weights=None,
              comm_every: int = 1):
    """Apply one sequence's communication policy to a pytree with a leading
    client axis (the unfused train-step paths).

    ``weights``: optional per-client participation weights [M] (zero =
    non-participant — averaged around, passed through bit-identical).
    ``comm_every``: reduce only every k-th communication round (the async
    cadence knob; 1 = every round, the paper's schedule).
    """
    assert policy in POLICIES, policy
    if policy == PRIVATE:
        return tree
    is_comm, is_global = _round_preds(cfg, step)
    if comm_every > 1:
        round_idx = (step + 1) // cfg.local_steps
        is_comm = is_comm & (round_idx % comm_every == 0)
    if weights is None:
        mean = client_mean
        grouped = lambda t: client_mean_grouped(t, cfg.hierarchy_groups)
    else:
        mean = lambda t: client_mean_weighted(t, weights)
        grouped = lambda t: client_mean_grouped_weighted(
            t, cfg.hierarchy_groups, weights)
    if policy == AVERAGED or cfg.hierarchy_period <= 0:
        return lax.cond(is_comm, mean, lambda t: t, tree)

    def do_comm(t):
        return lax.cond(is_global, mean, grouped, t)

    return lax.cond(is_comm, do_comm, lambda t: t, tree)


def comm_buffers(spec: flat.FlatSpec, cfg, step, bufs, policies, **kw):
    """Apply per-section policies to flat [M, N] buffers — one masked
    (sliced) reduction per communicated section run, private sections
    bit-identical (``flat.client_mean_masked``).

    ``weights``: participation weights — a single [M] array shared by every
    section or a per-section tuple (staleness-discounted sequences).
    ``comm_every``: per-section cadence tuple — sections reduce only every
    k-th comm round; sections sharing a cadence share one guarded reduction.
    ``shard``: a :class:`flat.ShardCtx` — the reductions run under
    ``shard_map`` as true ``psum``/``psum_scatter`` collectives over "data".
    ``corrupt`` / ``robust``: the round's fault transform ((nan, byz, scale)
    masks) and the :class:`flat.RobustCfg` guard policy, forwarded into
    every communicated reduction — faults touch only what is actually sent
    (cadence-skipped and private sections stay clean by construction).
    ``compress`` / ``ef``: a :class:`flat.CompressCfg` and the current
    error-feedback buffers — with ``compress`` set the reductions move
    quantized/top-k sends and the call returns ``(bufs, ef)`` (the pair is
    threaded through every cadence cond); ``compress=None`` keeps the
    original single-value return and a bit-identical trajectory.

    Everything it issues lies under the ``fed.reduce`` named scope.
    """
    with annotate("fed.reduce"):
        return _comm_buffers(spec, cfg, step, bufs, policies, **kw)


def _comm_buffers(spec: flat.FlatSpec, cfg, step, bufs, policies, *,
                  weights=None, comm_every=None, shard=None,
                  corrupt=None, robust=None, compress=None, ef=()):
    assert all(p in POLICIES for p in policies), policies
    n = len(policies)
    ce = tuple(comm_every) if comm_every is not None else (1,) * n
    assert len(ce) == n and all(c >= 1 for c in ce), ce
    if compress is not None:
        assert corrupt is None and robust is None, (
            "compress does not compose with corrupt/robust — enforced by "
            "make_engine")
    if isinstance(weights, (tuple, list)):
        assert len(weights) == n, (len(weights), n)
        w_of_sec = tuple(weights)
    else:
        w_of_sec = (weights,) * n
    is_comm, is_global = _round_preds(cfg, step)
    round_idx = (step + 1) // cfg.local_steps
    groups = cfg.hierarchy_groups
    if compress is None:
        for c in sorted(set(ce)):
            live = tuple(i for i in range(n)
                         if ce[i] == c and policies[i] != PRIVATE)
            if not live:
                continue
            due = is_comm if c == 1 else is_comm & (round_idx % c == 0)
            modes_comm = tuple("mean" if i in live else "none"
                               for i in range(n))
            w_c = tuple(w_of_sec[i] if i in live else None for i in range(n))
            if cfg.hierarchy_period <= 0 or not any(
                    policies[i] == HIERARCHICAL for i in live):
                bufs = lax.cond(
                    due,
                    lambda b, mc=modes_comm, wc=w_c:
                        flat.client_mean_masked(spec, b, mc, weights=wc,
                                                shard=shard, corrupt=corrupt,
                                                robust=robust),
                    lambda b: b, bufs)
                continue
            assert corrupt is None and robust is None, (
                "corrupt/robust do not compose with the hierarchical grouped "
                "mean (hierarchy_period > 0) — enforced by make_engine")
            # pod-local rounds: HIERARCHICAL sections take the grouped mean
            # while AVERAGED sections still take the full mean
            modes_local = tuple(
                ("group" if policies[i] == HIERARCHICAL else "mean")
                if i in live else "none" for i in range(n))

            def do_comm(b, mc=modes_comm, ml=modes_local, wc=w_c):
                return lax.cond(
                    is_global,
                    lambda bb: flat.client_mean_masked(spec, bb, mc,
                                                       weights=wc,
                                                       shard=shard),
                    lambda bb: flat.client_mean_masked(spec, bb, ml,
                                                       num_groups=groups,
                                                       weights=wc,
                                                       shard=shard),
                    b)

            bufs = lax.cond(due, do_comm, lambda b: b, bufs)
        return bufs
    # compressed variant: the (buffers, error-feedback) pair rides every
    # cadence cond together, so skipped rounds leave EF bit-identical too
    carry = (tuple(bufs), tuple(ef))
    for c in sorted(set(ce)):
        live = tuple(i for i in range(n)
                     if ce[i] == c and policies[i] != PRIVATE)
        if not live:
            continue
        due = is_comm if c == 1 else is_comm & (round_idx % c == 0)
        modes_comm = tuple("mean" if i in live else "none" for i in range(n))
        w_c = tuple(w_of_sec[i] if i in live else None for i in range(n))
        if cfg.hierarchy_period <= 0 or not any(
                policies[i] == HIERARCHICAL for i in live):
            carry = lax.cond(
                due,
                lambda be, mc=modes_comm, wc=w_c:
                    flat.client_mean_masked(spec, be[0], mc, weights=wc,
                                            shard=shard, compress=compress,
                                            ef=be[1]),
                lambda be: be, carry)
            continue
        modes_local = tuple(
            ("group" if policies[i] == HIERARCHICAL else "mean")
            if i in live else "none" for i in range(n))

        def do_comm_c(be, mc=modes_comm, ml=modes_local, wc=w_c):
            return lax.cond(
                is_global,
                lambda bb: flat.client_mean_masked(spec, bb[0], mc,
                                                   weights=wc, shard=shard,
                                                   compress=compress,
                                                   ef=bb[1]),
                lambda bb: flat.client_mean_masked(spec, bb[0], ml,
                                                   num_groups=groups,
                                                   weights=wc, shard=shard,
                                                   compress=compress,
                                                   ef=bb[1]),
                be)

        carry = lax.cond(due, do_comm_c, lambda be: be, carry)
    return carry


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class FlatState(NamedTuple):
    """Any algorithm's train state on the flat substrate.

    ``vars``/``mom`` are tuples of per-dtype [M, N] buffers holding the
    variable (resp. momentum) sections, tile-padded per ``repro.optim.flat``
    (``mom`` is the empty tuple for momentum-less specs).  ``stale`` carries
    the per-client staleness counters [M] int32 (rounds missed since last
    participation) when a participation engine is attached — the empty tuple
    otherwise (full participation).  ``retry`` carries the rollback retry
    counter (scalar int32) when a fault engine is attached — folded into the
    fault draws so a rolled-back round re-samples its failures — and the
    empty tuple otherwise (zero pytree leaves: pre-fault checkpoints and jit
    caches keep their exact structure).  ``ef`` carries the per-client
    error-feedback buffers of top-k compressed communication — a
    ``(vars_ef, mom_ef)`` pair of f32 buffer tuples shaped exactly like
    ``vars``/``mom`` (so sharding rules, masking and checkpointing inherit
    it, and compressed runs stay resume-bit-exact) — and the empty tuple
    whenever compression is off or feedback-free (same zero-leaf
    convention as ``stale``/``retry``).  ``deadline`` carries the adaptive
    round-deadline scalar (f32) of the straggler engine when one is
    attached — updated at round boundaries by the EMA controller, so
    checkpoints carry it and resume is bit-exact — and the empty tuple
    otherwise (same zero-leaf convention).
    """
    vars: Any
    mom: Any
    step: jnp.ndarray
    stale: Any = ()
    retry: Any = ()
    ef: Any = ()
    deadline: Any = ()


class Engine(NamedTuple):
    """A compiled sequence spec.  All members close over (cfg, aspec, spec).

    * ``init_state(var_trees, mom_trees=None, step=None, stale=None)`` —
      flatten section trees (each [M, ...]) into a :class:`FlatState`;
      momenta default to zeros in f32 buffers (``mom_trees`` is keyed by
      momentum name); staleness counters default to zeros [M] int32 when a
      participation engine is attached (the empty tuple otherwise).
    * ``step(state, batch) -> state`` — one fused local step including
      policy-driven communication (jit/scan it; donate the buffers).
    * ``views(state) -> (var_dict, mom_dict | None)`` — pytree views keyed
      by section (resp. momentum) names, for eval/checkpoint.
    * ``shardings(state) -> NamedSharding pytree | None`` — the mesh
      placement of a :class:`FlatState` (None without a shard context);
      ``init_state`` already applies it to concrete states via
      ``jax.device_put``.
    * ``comm_fn(state) -> state`` — the communication-only subprogram of
      ``step``: the round context plus the policy reductions (vars, then
      momentum), with no oracle and no fused update launch.  Never called
      by training — it is the static audit surface ``repro.analysis``
      lowers alone, so its compiled HLO contains exactly the wire
      collectives and nothing else.
    """
    aspec: AlgoSpec
    spec: flat.FlatSpec
    init_state: Any
    step: Any
    views: Any
    shardings: Any = None
    comm_fn: Any = None


def effective_staleness(aspec: AlgoSpec, participation) -> tuple:
    """Per-sequence staleness discount α (``Sequence.staleness`` overriding
    the participation spec's default; all 1.0 without participation)."""
    base = (participation.spec.stale_discount
            if participation is not None else 1.0)
    return tuple(q.staleness if q.staleness is not None else base
                 for q in aspec.sequences)


def staleness_weights(w, stale, stale_alpha: tuple) -> tuple:
    """Per-sequence α^staleness-aged participation weights — ONE aged array
    per distinct α, shared by the sequences using it (so the flat path's
    ``client_mean_masked`` still merges their tile runs).  The single
    source of the discount arithmetic: the fused engine and the unfused
    tree paths both call this, keeping their trajectories bit-consistent."""
    s = stale.astype(jnp.float32)
    by_alpha = {a: (w if a == 1.0 else w * jnp.float32(a) ** s)
                for a in set(stale_alpha)}
    return tuple(by_alpha[a] for a in stale_alpha)


def advance_stale(cfg, step, mask, stale):
    """Advance per-client staleness counters at communication steps:
    participants reset to 0, absentees age by 1 (shared by the fused
    engine and the unfused tree paths)."""
    is_comm = (step + 1) % cfg.local_steps == 0
    bumped = jnp.where(mask > 0, 0, stale + 1)
    return jnp.where(is_comm, bumped, stale)


def make_engine(cfg, aspec: AlgoSpec, templates: dict, oracle, *,
                block: int | None = None, participation=None,
                shard: flat.ShardCtx | None = None,
                overlap: bool = False, faults=None,
                robustness=None, compression=None,
                telemetry=None, stragglers=None) -> Engine:
    """Compile ``aspec`` into the fused flat-substrate step.

    ``templates``: section name → leaf template tree (arrays or
    ShapeDtypeStructs, WITHOUT the client axis) — the buffer layout.

    ``oracle(views, batch) -> {section: grad tree}``: the already-vmapped
    oracle; ``views`` is a dict of section → [M, ...] pytree.  For the storm
    kind the returned trees are the momentum *targets* of each sequence
    (e.g. μ for x/ν), evaluated twice per step (old/new iterate) with the
    same batch — the STORM correction.  For the sgd kind it is called once.

    ``participation``: a compiled
    :class:`~repro.federation.participation.Participation` — every step
    derives the round's client mask from the step counter (resumable), gates
    the fused launches with it, zeroes non-participants' oracle
    contributions, weights the reductions by participants only, and advances
    the staleness counters on :class:`FlatState` ``.stale``.

    ``shard`` / ``overlap``: mesh partitioning of the substrate and the
    comm/compute overlap schedule — see the module docstring.

    ``faults``: a compiled :class:`~repro.federation.faults.Faults` — every
    step derives the round's (keep, nan, byz) client masks from the step
    counter and the :class:`FlatState` retry slot (resume- and retry-exact):
    dropped clients compose into the participation mask/weights (frozen
    bit-exact, averaged around), and the corruption transform applies to
    what surviving clients *send* into each reduction.  ``robustness``: any
    object carrying the :class:`flat.RobustCfg` fields (e.g. a
    ``federation.faults.RobustnessSpec``) — health-screens senders and
    selects the robust aggregator inside those reductions.  Both are duck-
    typed so this module stays import-free of the federation layer; both
    ``None`` (the default) leaves every trajectory bit-identical.

    ``compression``: any object carrying the :class:`flat.CompressCfg`
    fields (e.g. a ``federation.compression.CompressionSpec``) — the named
    sections' reductions move quantized and/or top-k-sparsified sends
    (``sections=None`` compresses every communicated section).  Top-k
    carries per-client error-feedback buffers on ``FlatState.ef``.
    Duck-typed like ``faults``/``robustness``; ``None`` (the default)
    leaves every trajectory bit-identical.  Rejected combinations (clear
    errors, no silent fallback): compression with faults/robustness, and
    top-k with the hierarchical grouped mean (``cfg.hierarchy_period > 0``)
    — plain quantization DOES compose with the grouped mean.

    ``telemetry``: any object carrying a ``.metrics`` field (e.g. a
    ``telemetry.TelemetrySpec``) — the step additionally returns an
    in-band metrics dict computed from the already-materialized flat
    buffers (per-section update/momentum norms, client-drift dispersion,
    compression error, health verdicts — see ``repro.telemetry.spec``).
    ``None`` (the default) keeps ``step(state, batch) -> state`` with the
    LITERAL pre-telemetry code path: trajectories, jit cache keys and
    state structures are bit-identical to a telemetry-free build.

    ``stragglers``: a compiled
    :class:`~repro.federation.stragglers.Stragglers` — every round derives
    its client compute times and the elastic-round decision (arrival mask,
    effective deadline after quorum extensions, adaptive next deadline)
    from the step counter and the deadline scalar riding
    :class:`FlatState` ``.deadline``.  The reduction averages arrivals
    only (the arrival mask multiplies into the participation weights,
    exactly how fault dropout composes); the late-arrival policy picks the
    launch mask (``"carry"`` lets stragglers keep computing locally) and
    the staleness aging (``"cancel"`` skips it).  Composes with
    participation, faults/robustness and compression.  ``None`` (the
    default) is the literal pre-straggler path — zero deadline leaf,
    bit-identical trajectories.
    """
    rcfg = None
    if robustness is not None:
        rcfg = flat.RobustCfg(
            aggregator=robustness.aggregator, screen=robustness.screen,
            z_thresh=robustness.z_thresh, clip_factor=robustness.clip_factor,
            trim_frac=robustness.trim_frac)
        if rcfg.aggregator not in ("mean", "clip", "trim"):
            raise ValueError(f"unknown robust aggregator "
                             f"{rcfg.aggregator!r} (mean|clip|trim)")
    if (faults is not None or rcfg is not None) and cfg.hierarchy_period > 0:
        raise ValueError(
            "faults=/robustness= do not compose with the hierarchical "
            "grouped mean (cfg.hierarchy_period > 0) — the robust "
            "reductions and the fault model are global; set "
            "hierarchy_period=0")
    if stragglers is not None and cfg.hierarchy_period > 0:
        raise ValueError(
            "stragglers= does not compose with the hierarchical grouped "
            "mean (cfg.hierarchy_period > 0) — the deadline/quorum "
            "decision is global; set hierarchy_period=0")
    ccfg = None
    if compression is not None:
        if faults is not None or rcfg is not None:
            raise ValueError(
                "compression= does not compose with faults=/robustness= — "
                "the guarded reductions consume raw client rows; drop one "
                "layer")
        quant = compression.quant
        if quant not in (None, "bf16", "int8"):
            raise ValueError(f"unknown compression quant {quant!r} "
                             f"(None | 'bf16' | 'int8')")
        frac = float(compression.topk_frac)
        if not 0.0 <= frac < 1.0:
            raise ValueError(
                f"compression topk_frac={frac} must be in [0, 1)")
        if quant is None and frac == 0.0:
            raise ValueError(
                "compression enabled but no compressor selected — set "
                "quant ('bf16' | 'int8') and/or topk_frac > 0")
        comm_secs = tuple(q.section for q in aspec.sequences
                          if q.comm != PRIVATE)
        csecs = (tuple(compression.sections) if compression.sections
                 else comm_secs)
        unknown = set(csecs) - set(aspec.sections)
        if unknown:
            raise ValueError(
                f"compression.sections names unknown sections "
                f"{sorted(unknown)} (spec {aspec.name!r} has "
                f"{aspec.sections})")
        private = [s for s in csecs if s not in comm_secs]
        if private:
            raise ValueError(
                f"compression.sections names private sections {private} — "
                f"private state is never communicated, so it cannot be "
                f"compressed")
        if frac > 0 and cfg.hierarchy_period > 0:
            raise ValueError(
                "top-k compression (topk_frac > 0) does not compose with "
                "the hierarchical grouped mean (cfg.hierarchy_period > 0) "
                "— error feedback against two different means is "
                "ill-defined; use quant-only compression or set "
                "hierarchy_period=0")
        ccfg = flat.CompressCfg(
            quant=quant, topk_frac=frac,
            error_feedback=bool(compression.error_feedback),
            sections=csecs)
    has_ef = ccfg is not None and ccfg.has_ef
    sections = aspec.sections
    spec = flat.make_spec({s: templates[s] for s in sections},
                          sections=sections,
                          block=block if block else flat.BLOCK,
                          shards=shard.model_size if shard else 1)
    policies = aspec.policies
    has_mom = aspec.has_momentum
    part = participation
    strag = stragglers
    # staleness counters exist for absence of either kind: a round missed
    # by the sampler OR a deadline missed by a straggler
    need_stale = part is not None or strag is not None
    late_carry = strag is not None and strag.spec.late_policy == "carry"
    late_cancel = strag is not None and strag.spec.late_policy == "cancel"
    if strag is not None:
        from repro.federation.stragglers import arrival_histogram
    cadence = tuple(q.comm_every for q in aspec.sequences)
    stale_alpha = effective_staleness(aspec, part)
    discounted = any(a != 1.0 for a in stale_alpha)

    tel_groups = ()
    if telemetry is not None:
        from repro.telemetry.spec import resolve_metric_groups
        tel_groups = resolve_metric_groups(
            getattr(telemetry, "metrics", None),
            compressed=ccfg is not None,
            guarded=faults is not None or rcfg is not None,
            sampled=part is not None,
            straggled=strag is not None)
        if "stragglers" in tel_groups and strag is None:
            raise ValueError(
                "telemetry metrics group 'stragglers' needs stragglers= — "
                "there is no deadline or arrival set to report")
        if "compression" in tel_groups and ccfg is None:
            raise ValueError(
                "telemetry metrics group 'compression' needs compression= "
                "— there is no EF residual or quantization error to report")
        if "health" in tel_groups and (part is None and faults is None
                                       and rcfg is None):
            raise ValueError(
                "telemetry metrics group 'health' needs participation "
                "sampling, faults= or robustness= — there is nothing to "
                "screen")

    def _oracle_flat(vars_bufs, batch, mask):
        """The oracle at the iterate ``vars_bufs``, its gradients written
        into flat f32 buffers with non-participants' rows zeroed; each
        stage under its own named scope (profiler/HLO metadata only)."""
        with annotate("fed.unflatten"):
            views = flat.unflatten_tree(spec, vars_bufs)
        with annotate("fed.oracle"):
            gdict = oracle(views, batch)
        with annotate("fed.flatten"):
            g = flat.flatten_tree(spec, {s: gdict[s] for s in sections},
                                  batch_dims=1, dtype=jnp.float32)
            return flat.mask_buffers(g, mask)

    def _round_ctx(state: FlatState):
        """(mask, per-section comm weights, corrupt transform, staleness
        mask, straggler info) of the round ``state.step`` belongs to — pure
        in the step counter (plus the retry counter for the fault draws and
        the deadline scalar for the arrival set), so resume and
        rollback-retry are bit-exact."""
        if part is None:
            mask, w = None, None
        else:
            mask, w = part.round_weights(state.step // cfg.local_steps)
        s_info, stale_base = None, None
        if strag is not None:
            sampled = (jnp.ones((strag.num_clients,), jnp.float32)
                       if mask is None else mask)
            arrivals, eff, ext, next_dl = strag.round_decision(
                state.step // cfg.local_steps, sampled, state.deadline)
            # launch mask: "carry" keeps stragglers computing locally;
            # "drop"/"cancel" freeze them bit-exact like non-participants
            mask = sampled if late_carry else arrivals
            # the round's mean always averages ARRIVALS only
            w = arrivals if w is None else w * arrivals
            # staleness: "cancel" treats the straggler as served (no
            # aging); "drop"/"carry" age it so a stale_discount < 1
            # re-weights its return by α^staleness
            stale_base = sampled if late_cancel else arrivals
            s_info = (arrivals, eff, ext, next_dl, sampled)
        corrupt = None
        if faults is not None:
            keep, nan, byz = faults.round_masks(
                state.step // cfg.local_steps, state.retry)
            # a dropped client behaves exactly like a non-participant:
            # frozen bit-exact in the launches, averaged around in comm
            mask = keep if mask is None else mask * keep
            w = keep if w is None else w * keep
            if stale_base is not None:
                stale_base = stale_base * keep
            corrupt = (nan, byz, faults.spec.byzantine_scale)
        stale_mask = mask if stale_base is None else stale_base
        if w is not None and discounted:
            w = staleness_weights(w, state.stale, stale_alpha)
        return mask, w, corrupt, stale_mask, s_info

    def _next_stale(state: FlatState, stale_mask):
        if not need_stale:
            return state.stale
        return advance_stale(cfg, state.step, stale_mask, state.stale)

    def _next_deadline(state: FlatState, s_info):
        """The adaptive deadline advances ONCE per round, at the comm step
        — every local step inside a round sees the same scalar, so the
        arrival set is constant within the round."""
        if strag is None:
            return state.deadline
        is_comm = (state.step + 1) % cfg.local_steps == 0
        return jnp.where(is_comm, s_info[3], state.deadline)

    def _tel_metrics(state: FlatState, new: FlatState, mask, corrupt,
                     local_vars, s_info=None) -> dict:
        """In-band metrics of one step, read off the already-materialized
        flat buffers (``tel_groups`` is a static Python value, so with
        telemetry off this is never traced and the step's jaxpr is the
        pre-telemetry one).  ``local_vars`` are the round's LOCAL
        (pre-reduction) iterates — what drift measures."""
        m = {}
        if "norms" in tel_groups:
            diff = tuple(n - o for n, o in zip(new.vars, state.vars))
            m.update(flat.section_norms(spec, diff, mask=mask,
                                        prefix="upd_norm"))
            if new.mom:
                m.update(flat.section_norms(spec, new.mom, mask=mask,
                                            prefix="mom_norm"))
        if "drift" in tel_groups:
            m.update(flat.section_drift(spec, local_vars, mask=mask))
        if "compression" in tel_groups:
            if new.ef:
                efv, _ = new.ef
                m.update(flat.section_norms(spec, efv, prefix="ef_norm"))
            if ccfg.quant is not None:
                m["quant_err"] = flat.quant_roundtrip_err(
                    local_vars, spec.groups[0].block, ccfg.quant)
        if "health" in tel_groups:
            if mask is not None:
                m["participants"] = jnp.sum((mask > 0).astype(jnp.float32))
            if need_stale:
                m["stale_hist"] = jnp.sum(
                    jax.nn.one_hot(jnp.clip(new.stale, 0, 7), 8), axis=0)
            if corrupt is not None:
                nan, byz, _ = corrupt
                m["injected_nan"] = jnp.sum(
                    jnp.asarray(nan, jnp.float32))
                m["injected_byz"] = jnp.sum(
                    jnp.asarray(byz, jnp.float32))
            if rcfg is not None:
                m["screened"] = flat.health_screen(spec, local_vars, mask,
                                                   corrupt, rcfg)
        if "stragglers" in tel_groups and s_info is not None:
            arrivals, eff, ext, next_dl, sampled = s_info
            rt = strag.round_times(state.step // cfg.local_steps)
            m["deadline"] = eff
            m["deadline_next"] = next_dl
            m["arrivals"] = jnp.sum((arrivals > 0).astype(jnp.float32))
            m["quorum"] = strag.quorum_count(sampled).astype(jnp.float32)
            m["extensions"] = ext.astype(jnp.float32)
            m["arrival_hist"] = arrival_histogram(rt, eff, sampled)
        return m

    def state_shardings(state: FlatState):
        """NamedSharding pytree for ``state`` (None without a mesh): [M, N]
        buffers client-sharded over "data" and packed-axis-sharded over
        "model", stale counters over "data" (``rules.flat_state_specs``)."""
        if shard is None:
            return None
        from repro.sharding.rules import flat_state_specs
        pspecs = flat_state_specs(state, data_axis=shard.data_axis,
                                  model_axis=shard.model_axis)
        return jax.tree.map(
            lambda p: jax.sharding.NamedSharding(shard.mesh, p), pspecs,
            is_leaf=lambda p: isinstance(p, jax.sharding.PartitionSpec))

    def _concrete_on_mesh(tree) -> bool:
        # False for an abstract init (eval_shape): its caller places it
        return shard is not None and not any(
            isinstance(l, jax.core.Tracer) for l in jax.tree.leaves(tree))

    def _placed(state: FlatState) -> FlatState:
        if not _concrete_on_mesh(state):
            return state
        return jax.device_put(state, state_shardings(state))

    def _flatten(trees, dtype=None):
        """``flat.flatten_tree`` of [M, ...] trees, built in its shards on
        a mesh: on one device the shard-major interleave is a relayout
        whose TPU compile takes minutes at published widths."""
        def fn(t):
            return flat.flatten_tree(spec, t, batch_dims=1, dtype=dtype)
        if not _concrete_on_mesh(trees):
            return fn(trees)
        on_mesh = functools.partial(jax.sharding.NamedSharding, shard.mesh)
        trees = jax.device_put(
            trees, on_mesh(jax.sharding.PartitionSpec(shard.data_axis)))
        return jax.jit(fn, out_shardings=on_mesh(shard.buffer_spec))(trees)

    def init_state(var_trees, mom_trees=None, step=None, stale=None,
                   retry=None, ef=None, deadline=None):
        vars_b = _flatten({s: var_trees[s] for s in sections})
        if not has_mom:
            mom_b = ()
        elif mom_trees is None:
            # momenta live in f32 buffers regardless of the variable dtype —
            # the unfused arithmetic promotes them the same way, and the
            # STORM correction g_new − g_old is a small difference bf16
            # would largely destroy
            mom_b = tuple(jnp.zeros(b.shape, jnp.float32) for b in vars_b)
        else:
            mom_b = _flatten({q.section: mom_trees[q.momentum]
                              for q in aspec.sequences}, dtype=jnp.float32)
        if not need_stale:
            stale_b = ()
        elif stale is None:
            n_stale = part.num_clients if part is not None else strag.num_clients
            stale_b = jnp.zeros((n_stale,), jnp.int32)
        else:
            stale_b = stale
        if faults is None:
            retry_b = ()
        elif retry is None:
            retry_b = jnp.zeros((), jnp.int32)
        else:
            retry_b = jnp.asarray(retry, jnp.int32)
        if not has_ef:
            ef_b = ()
        elif ef is None:
            # one f32 zero buffer per communicated buffer — the dropped
            # top-k mass accumulates here between rounds
            ef_b = (tuple(jnp.zeros(b.shape, jnp.float32) for b in vars_b),
                    tuple(jnp.zeros(b.shape, jnp.float32) for b in mom_b))
        else:
            ef_b = ef
        if strag is None:
            dl_b = ()
        elif deadline is None:
            dl_b = jnp.asarray(strag.spec.deadline, jnp.float32)
        else:
            dl_b = jnp.asarray(deadline, jnp.float32)
        return _placed(FlatState(
            vars_b, mom_b,
            jnp.zeros((), jnp.int32) if step is None else step,
            stale_b, retry_b, ef_b, dl_b))

    def _storm_step(state: FlatState, batch) -> FlatState:
        t = state.step
        mask, wts, corrupt, stale_mask, s_info = _round_ctx(state)
        a = alpha_schedule(cfg, t)
        lrs = tuple(getattr(cfg, q.lr) * a for q in aspec.sequences)
        decays = tuple(1.0 - getattr(cfg, q.decay) * a * a
                       for q in aspec.sequences)
        # 1) old-iterate oracle on transient pytree views (reads only the
        #    entering iterate — lets the variable step and the partial
        #    momentum share a single fused launch); non-participants'
        #    contributions are zeroed (their oracle is "skipped")
        g_old = _oracle_flat(state.vars, batch, mask)
        # 2+3) partial momentum + variable step: ONE gated launch per dtype
        with annotate("fed.update"):
            vars_b, mom_b = flat.storm_partial_step(
                spec, state.vars, state.mom, g_old, lrs, decays, mask=mask,
                shard=shard)
        efv, efm = state.ef if state.ef else ((), ())
        # issue the variable-section reduction ...
        if ccfg is None:
            vars_c = comm_buffers(spec, cfg, t, vars_b, policies,
                                  weights=wts, comm_every=cadence,
                                  shard=shard, corrupt=corrupt, robust=rcfg)
        else:
            vars_c, efv = comm_buffers(spec, cfg, t, vars_b, policies,
                                       weights=wts, comm_every=cadence,
                                       shard=shard, compress=ccfg, ef=efv)
        # 4) ... run the new-iterate oracle, same batch; the STORM correction
        #    is one add.  overlap=True evaluates the oracle at the LOCAL
        #    (pre-reduction) iterate: g_new then feeds only the correction
        #    add and the issued "data"-axis collective has no consumer until
        #    the step returns, so XLA can overlap it with oracle compute
        #    (documented deviation at comm rounds; identical elsewhere).
        g_new = _oracle_flat(vars_b if overlap else vars_c, batch, mask)
        with annotate("fed.update"):
            mom_b = flat.buffers_add(mom_b, g_new)
        if ccfg is None:
            mom_b = comm_buffers(spec, cfg, t, mom_b, policies,
                                 weights=wts, comm_every=cadence, shard=shard,
                                 corrupt=corrupt, robust=rcfg)
        else:
            mom_b, efm = comm_buffers(spec, cfg, t, mom_b, policies,
                                      weights=wts, comm_every=cadence,
                                      shard=shard, compress=ccfg, ef=efm)
        new = state._replace(vars=vars_c, mom=mom_b, step=t + 1,
                             stale=_next_stale(state, stale_mask),
                             ef=(efv, efm) if state.ef else (),
                             deadline=_next_deadline(state, s_info))
        if not tel_groups:
            return new
        return new, _tel_metrics(state, new, mask, corrupt, vars_b, s_info)

    def _sgd_step(state: FlatState, batch) -> FlatState:
        t = state.step
        mask, wts, corrupt, stale_mask, s_info = _round_ctx(state)
        lrs = tuple(getattr(cfg, q.lr) for q in aspec.sequences)
        g = _oracle_flat(state.vars, batch, mask)
        efv, efm = state.ef if state.ef else ((), ())
        if has_mom:
            betas = (aspec.beta,) * len(aspec.sequences)
            with annotate("fed.update"):
                vars_b, mom_b = flat.momentum_sgd_step(
                    spec, state.vars, state.mom, g, lrs, betas, mask=mask,
                    shard=shard)
            if ccfg is None:
                mom_b = comm_buffers(spec, cfg, t, mom_b, policies,
                                     weights=wts, comm_every=cadence,
                                     shard=shard, corrupt=corrupt,
                                     robust=rcfg)
            else:
                mom_b, efm = comm_buffers(spec, cfg, t, mom_b, policies,
                                          weights=wts, comm_every=cadence,
                                          shard=shard, compress=ccfg, ef=efm)
        else:
            # momentum-less: the plain-SGD launch (no dead momentum stream)
            with annotate("fed.update"):
                vars_b = flat.sgd_step(spec, state.vars, g, lrs, mask=mask,
                                       shard=shard)
            mom_b = ()
        vars_local = vars_b         # pre-reduction local iterates (drift)
        if ccfg is None:
            vars_b = comm_buffers(spec, cfg, t, vars_b, policies,
                                  weights=wts, comm_every=cadence,
                                  shard=shard, corrupt=corrupt, robust=rcfg)
        else:
            vars_b, efv = comm_buffers(spec, cfg, t, vars_b, policies,
                                       weights=wts, comm_every=cadence,
                                       shard=shard, compress=ccfg, ef=efv)
        new = state._replace(vars=vars_b, mom=mom_b, step=t + 1,
                             stale=_next_stale(state, stale_mask),
                             ef=(efv, efm) if state.ef else (),
                             deadline=_next_deadline(state, s_info))
        if not tel_groups:
            return new
        return new, _tel_metrics(state, new, mask, corrupt, vars_local,
                                 s_info)

    def comm_fn(state: FlatState) -> FlatState:
        """Communication-only subprogram of one step — the exact
        ``comm_buffers`` calls of ``_storm_step``/``_sgd_step`` (vars
        reduction, then the momentum reduction when the spec carries one)
        driven by the same ``_round_ctx``, with no oracle and no fused
        update launch.  Training never calls this; ``repro.analysis``
        compiles it alone so the lowered HLO holds exactly the wire
        collectives of one step."""
        t = state.step
        _, wts, corrupt, _, _ = _round_ctx(state)
        efv, efm = state.ef if state.ef else ((), ())
        if ccfg is None:
            vars_c = comm_buffers(spec, cfg, t, state.vars, policies,
                                  weights=wts, comm_every=cadence,
                                  shard=shard, corrupt=corrupt, robust=rcfg)
        else:
            vars_c, efv = comm_buffers(spec, cfg, t, state.vars, policies,
                                       weights=wts, comm_every=cadence,
                                       shard=shard, compress=ccfg, ef=efv)
        mom_c = state.mom
        if has_mom:
            if ccfg is None:
                mom_c = comm_buffers(spec, cfg, t, state.mom, policies,
                                     weights=wts, comm_every=cadence,
                                     shard=shard, corrupt=corrupt,
                                     robust=rcfg)
            else:
                mom_c, efm = comm_buffers(spec, cfg, t, state.mom, policies,
                                          weights=wts, comm_every=cadence,
                                          shard=shard, compress=ccfg, ef=efm)
        return state._replace(vars=vars_c, mom=mom_c, step=t + 1,
                              ef=(efv, efm) if state.ef else ())

    step = _storm_step if aspec.kind == "storm" else _sgd_step
    # what the step actually computes in-band (() = bare-state contract) —
    # the trainer wrapper branches on this, not on telemetry's presence
    step.telemetry_groups = tel_groups

    def views(state: FlatState):
        vt = flat.unflatten_tree(spec, state.vars)
        if not state.mom:
            return vt, None
        mt = flat.unflatten_tree(spec, state.mom)
        return vt, {q.momentum: mt[q.section] for q in aspec.sequences}

    return Engine(aspec, spec, init_state, step, views, state_shardings,
                  comm_fn)
