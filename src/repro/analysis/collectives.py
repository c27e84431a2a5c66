"""Collective/wire auditor (the ``W1xx`` rules).

Two surfaces, one expected model:

* **jaxpr** — trace the built ``run.step`` on abstract state/batch and
  collect every collective primitive (recursing through ``cond``/``scan``/
  ``shard_map`` sub-jaxprs).  The expected multiset of
  ``(primitive, axes, grouped, dtype, operand elems)`` entries is derived
  from the SAME section-extent merge the reduction uses
  (``flat._section_runs``) — one sliced reduction per communicated merged
  run per reduction event, plus the policy's stats collectives (weighted
  ``wsum``, int8 scale exchange, robust screen/clip/trim).  Nothing here
  is combined or DCE'd, so counts and operand sizes are exact (W101), and
  an unexplained operand whose size matches a private run is private
  state on the wire (W102).

* **HLO** — lower and compile the engine's communication-only subprogram
  (``run.step.comm_fn`` — no oracle, no fused update) and parse its
  collectives (``launch.hlo_stats.collective_bytes``).  XLA may combine
  all-reduces, so the HLO contract is byte-exact per dtype (W104), the
  narrow-dtype coverage of a quantized policy (W103 — the audit dryrun
  previously carried as ``_check_compressed_collectives``, now shared from
  here), and zero resharding ops between oracle and fused update (W105).
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

from repro.analysis.rules import Finding

#: jaxpr primitive names treated as collectives (both spellings of the
#: scatter reduction, and ``psum_invariant`` — what ``lax.psum`` binds inside
#: a ``check_vma=True`` shard_map, where its operand varies over the axis)
COLLECTIVE_PRIMS = {"psum", "psum_invariant", "all_gather", "all_to_all",
                    "ppermute", "psum_scatter", "reduce_scatter"}
_CANON = {"reduce_scatter": "psum_scatter", "psum_invariant": "psum"}

#: jax dtype name -> HLO dtype token (the hlo_stats keying)
_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
              "int8": "s8", "uint8": "u8", "int32": "s32",
              "float64": "f64", "bool": "pred"}
_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
                "uint8": 1, "int32": 4, "float64": 8, "bool": 1}

#: one expected-collective entry: (prim, axes, grouped, dtype, elems)
Entry = Tuple[str, tuple, bool, str, int]


# ---------------------------------------------------------------------------
# jaxpr side: what the traced step actually binds
# ---------------------------------------------------------------------------

def _sub_jaxprs(params: dict):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            inner = getattr(u, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(u, "eqns"):
                yield u


def _walk(jaxpr, acc: Counter) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            p = eqn.params
            axes = p.get("axes", p.get("axis_name", ()))
            if not isinstance(axes, tuple):
                axes = (axes,)
            grouped = p.get("axis_index_groups") is not None
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                elems = 1
                for d in aval.shape:
                    elems *= int(d)
                acc[(_CANON.get(name, name), tuple(str(a) for a in axes),
                     grouped, str(aval.dtype), elems)] += 1
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, acc)


def collect_collectives(fn, *abstract_args) -> Counter:
    """The multiset of collective-primitive operands in ``fn``'s jaxpr."""
    import jax
    acc: Counter = Counter()
    _walk(jax.make_jaxpr(fn)(*abstract_args).jaxpr, acc)
    return acc


# ---------------------------------------------------------------------------
# expected side: mirror of flat._client_mean_masked_sharded
# ---------------------------------------------------------------------------

class _Expect:
    """Accumulates expected entries while mirroring one reduction call."""

    def __init__(self, *, data_axis: str, model_axis: str, data_size: int,
                 use_scatter: bool, m_local: int):
        self.c: Counter = Counter()
        self.da, self.ma = (data_axis,), (model_axis,)
        self.nds = data_size
        self.use_scatter = use_scatter
        self.m_local = m_local

    def psum(self, elems: int, dtype: str = "float32", *, axes=None,
             grouped: bool = False) -> None:
        self.c[("psum", axes or self.da, grouped, dtype, elems)] += 1

    def allreduce(self, elems: int, dtype: str = "float32", *,
                  grouped: bool = False) -> None:
        # flat._allreduce: psum_scatter + all_gather iff use_scatter, no
        # groups and the run tiles evenly over the data axis
        if self.use_scatter and not grouped and elems % self.nds == 0:
            self.c[("psum_scatter", self.da, False, dtype, elems)] += 1
            self.c[("all_gather", self.da, False, dtype,
                    elems // self.nds)] += 1
        else:
            self.c[("psum", self.da, grouped, dtype, elems)] += 1

    def mean_run(self, L: int, dtype: str, *, weighted: bool, comp: bool,
                 grouped: bool, block: int, compress, robust,
                 guarded: bool) -> None:
        """One communicated merged run of ``L`` elements — the collectives
        of flat._client_mean_masked_sharded's body for it."""
        if guarded:                       # _robust_mean_sharded
            if robust is None:
                self.psum(1)              # wsum (faulty unguarded mean)
                self.allreduce(L, dtype)
                return
            if robust.screen:
                self.psum(self.m_local, axes=self.ma)      # nonfinite
            self.psum(self.m_local, axes=self.ma)          # row norms sq
            if robust.screen and robust.z_thresh > 0:
                self.psum(1)              # cnt
                self.psum(1)              # mu
                self.psum(1)              # sd
            self.psum(1)                  # wsum_eff
            if robust.aggregator == "trim":
                self.c[("all_gather", self.da, False, "float32",
                        self.m_local * L)] += 1
                self.psum(1)              # nh
            elif robust.aggregator == "clip":
                self.psum(1)              # tau
                self.allreduce(L, dtype)
            else:
                self.allreduce(L, dtype)
            return
        if comp and compress is not None:
            if weighted:
                self.psum(1, grouped=grouped)              # wsum
            if compress.quant == "int8":
                self.psum(L // block, grouped=grouped)     # scale exchange
                self.allreduce(L, "int8", grouped=grouped)
            elif compress.quant == "bf16":
                self.allreduce(L, "bfloat16", grouped=grouped)
            else:                         # top-k only: dense f32 wire
                self.allreduce(L, "float32", grouped=grouped)
            return
        if weighted:
            self.psum(1, grouped=grouped)                  # wsum
        self.allreduce(L, dtype, grouped=grouped)


def _weight_sentinels(aspec, participation, weighted: bool) -> tuple:
    """Per-section weight identities mirroring sequences.staleness_weights
    (one shared array per distinct discount α — what _section_runs merges
    on)."""
    from repro.optim import sequences as seqs
    n = len(aspec.sequences)
    if not weighted:
        return (None,) * n
    alphas = seqs.effective_staleness(aspec, participation)
    if all(a == 1.0 for a in alphas):
        w = object()
        return (w,) * n
    by_alpha = {a: object() for a in set(alphas)}
    return tuple(by_alpha[a] for a in alphas)


def expected_step_collectives(run) -> Tuple[Counter, Dict[str, Any]]:
    """(expected multiset, info) for the full step of a built sharded run
    — empty off-mesh (the unsharded reduction is collective-free).

    ``info`` carries ``private_elems`` (merged private-run lengths, for
    W102 classification), ``comm_elems`` (per-event communicated payload
    elems, one shard chunk) and ``events`` (reduction events per step)."""
    from repro.optim import flat
    from repro.optim.sequences import HIERARCHICAL, PRIVATE

    step = run.step
    flat_spec, aspec = step.spec, step.aspec
    exp = run.spec
    info: Dict[str, Any] = {"events": 0, "comm_elems": 0,
                            "private_elems": set()}
    if run.mesh is None:
        return Counter(), info
    # the default axis names of make_shard_ctx — custom-named prebuilt
    # ShardCtx meshes are not reachable from an Experiment spec
    data_axis, model_axis = "data", "model"
    data_size = int(run.mesh.shape[data_axis])
    m_local = exp.problem.num_clients // data_size
    use_scatter = bool(exp.execution.scatter_comm)
    weighted = (step.participation is not None or step.faults is not None
                or step.stragglers is not None)
    guarded = step.faults is not None or step.robustness is not None
    robust = None
    if step.robustness is not None:
        robust = flat.RobustCfg(
            aggregator=step.robustness.aggregator,
            screen=step.robustness.screen,
            z_thresh=step.robustness.z_thresh,
            clip_factor=step.robustness.clip_factor,
            trim_frac=step.robustness.trim_frac)
    compress = step.compression
    comm_secs = tuple(q.section for q in aspec.sequences
                      if q.comm != PRIVATE)
    comp_of_sec = None
    if compress is not None:
        csecs = set(compress.sections or comm_secs)
        comp_of_sec = tuple(nm in csecs for nm in flat_spec.sections)
    w_of_sec = _weight_sentinels(aspec, step.participation, weighted)
    policies = aspec.policies
    cadence = tuple(q.comm_every for q in aspec.sequences)
    n = len(policies)
    hier_on = exp.schedule.hierarchy_period > 0
    events = 2 if aspec.has_momentum else 1
    info["events"] = events

    exp_c = _Expect(data_axis=data_axis, model_axis=model_axis,
                    data_size=data_size, use_scatter=use_scatter,
                    m_local=m_local)

    def one_call(modes):
        for grp in flat_spec.groups:
            dtype = str(grp.dtype)
            for mode, w, a, stop, comp in flat._section_runs(
                    grp, 1, modes, w_of_sec, comp_of_sec):
                L = stop - a
                if mode == "none":
                    info["private_elems"].add(L)
                    continue
                exp_c.mean_run(L, dtype, weighted=w is not None, comp=comp,
                               grouped=(mode == "group"), block=grp.block,
                               compress=compress, robust=robust,
                               guarded=guarded)

    for _ in range(events):
        for c in sorted(set(cadence)):
            live = tuple(i for i in range(n)
                         if cadence[i] == c and policies[i] != PRIVATE)
            if not live:
                continue
            modes_comm = tuple("mean" if i in live else "none"
                               for i in range(n))
            one_call(modes_comm)
            if hier_on and any(policies[i] == HIERARCHICAL for i in live):
                modes_local = tuple(
                    ("group" if policies[i] == HIERARCHICAL else "mean")
                    if i in live else "none" for i in range(n))
                one_call(modes_local)

    # per-event communicated payload elems (cadence-1 view, one chunk)
    modes_all = tuple("mean" if p != PRIVATE else "none" for p in policies)
    for grp in flat_spec.groups:
        for mode, _, a, stop, _ in flat._section_runs(
                grp, 1, modes_all, w_of_sec, comp_of_sec):
            if mode != "none":
                info["comm_elems"] += stop - a
    return exp_c.c, info


def _fmt_entry(e: Entry, k: int) -> str:
    prim, axes, grouped, dtype, elems = e
    g = " grouped" if grouped else ""
    return f"{k}x {prim}[{dtype} x{elems} over {'/'.join(axes)}{g}]"


def audit_step_collectives(run) -> List[Finding]:
    """W101/W102 on the full-step jaxpr of a built run, cross-checked
    against the analytic telemetry.comm plan."""
    import jax

    from repro.telemetry.comm import comm_plan

    where = f"spec {run.spec.algorithm.name}"
    state = jax.eval_shape(run.init, jax.random.PRNGKey(0))
    batch = jax.eval_shape(run.batch_fn, jax.random.PRNGKey(0))
    actual = collect_collectives(run.step, state, batch)
    expected, info = expected_step_collectives(run)
    findings: List[Finding] = []

    plan = comm_plan(run.step.spec, run.step.aspec, run.spec.compression)
    if plan is not None and run.mesh is not None:
        shards = run.step.spec.shards
        plan_elems = sum(e for _, e, _, _ in plan.sections)
        if plan.reductions != info["events"] or \
                plan_elems != info["comm_elems"] * shards:
            findings.append(Finding(
                "W101", where,
                f"analytic comm plan disagrees with the section-extent "
                f"walk: plan {plan.reductions} reductions x {plan_elems} "
                f"elems vs {info['events']} events x "
                f"{info['comm_elems'] * shards} elems"))

    if actual == expected:
        return findings
    extra = actual - expected
    missing = expected - actual
    for e, k in sorted(extra.items()):
        prim, axes, grouped, dtype, elems = e
        if elems in info["private_elems"]:
            findings.append(Finding(
                "W102", where,
                f"collective operand matches a PRIVATE section run: "
                f"{_fmt_entry(e, k)}"))
        else:
            findings.append(Finding(
                "W101", where,
                f"unplanned collective in the step jaxpr: "
                f"{_fmt_entry(e, k)}"))
    for e, k in sorted(missing.items()):
        findings.append(Finding(
            "W101", where,
            f"planned collective missing from the step jaxpr: "
            f"{_fmt_entry(e, k)}"))
    return findings


# ---------------------------------------------------------------------------
# HLO side: the compiled comm-only subprogram
# ---------------------------------------------------------------------------

def check_compressed_collectives(exp, flat_spec,
                                 coll: Dict[str, Any]) -> Dict[str, Any]:
    """Audit a compressed spec's compiled collectives against the analytic
    wire model: a quantized policy must move the reduction bytes in the
    narrow dtype.  Raises ``RuntimeError`` if it lowered to f32 collectives
    instead (fail LOUDLY — that is a silent 4x comm regression).

    The comparison is per-dtype, not total: model-parallel compute
    collectives (activation all-reduces, all-to-alls, permutes)
    legitimately stay f32, so the criterion is that the narrow-dtype bytes
    cover what the compressed reductions analytically move — the
    per-shard-chunk extents of every compressed section at the quant's
    value width, for BOTH the variables and the momentum reduction of each
    comm event.  Shared by ``launch.dryrun`` (full-step HLO) and the W103
    audit here (comm-subprogram HLO) — one byte model, no drift."""
    from repro.optim.sequences import PRIVATE, SPECS
    from repro.telemetry.comm import compressed_chunk_elems
    cp = exp.compression
    narrow = {"bf16": ("bf16",), "int8": ("s8", "u8")}[cp.quant]
    aspec = SPECS[exp.algorithm.name]
    elems = compressed_chunk_elems(flat_spec, aspec, cp)
    vbytes = {"bf16": 2, "int8": 1}[cp.quant]
    reductions = 2 if aspec.has_momentum else 1
    expected = reductions * elems * vbytes      # one shard chunk each
    by_dtype = coll.get("bytes_by_dtype", {})
    narrow_b = sum(by_dtype.get(d, 0) for d in narrow)
    if narrow_b < 0.9 * expected:
        hint = ""
        if cp.quant == "bf16":
            hint = (" (note: the host CPU backend has no native bf16 "
                    "reduce and re-widens bf16 all-reduces to f32 — the "
                    "bf16 wire guarantee holds on TPU only; int8 moves "
                    "integer collectives, which no backend promotes)")
        raise RuntimeError(
            f"compressed spec (quant={cp.quant!r}) lowered to f32 "
            f"collectives: the narrow-dtype collective bytes "
            f"({narrow_b} B in {narrow}) do not cover the analytic wire "
            f"model of the compressed reductions ({expected} B = "
            f"{reductions} reductions x {elems} elems x {vbytes} B) — "
            f"dtype breakdown: {by_dtype}{hint}")
    return {"ok": True, "narrow_bytes": narrow_b,
            "expected_bytes": expected, "bytes_by_dtype": by_dtype}


def expected_wire_bytes(expected: Counter, data_size: int) -> Dict[str, int]:
    """HLO bytes-by-dtype the expected multiset implies.  Entries carry
    jaxpr OPERAND elems; ``hlo_stats`` sums RESULT bytes, so the scattered
    reduction shrinks by the axis size and the gather grows by it."""
    out: Dict[str, int] = {}
    for (prim, _, _, dtype, elems), k in expected.items():
        n = elems
        if prim == "psum_scatter":
            n = elems // data_size
        elif prim == "all_gather":
            n = elems * data_size
        hd = _HLO_DTYPE.get(dtype, dtype)
        out[hd] = out.get(hd, 0) + k * n * _DTYPE_BYTES[dtype]
    return out


def audit_wire(run, coll: Optional[Dict[str, Any]] = None) -> List[Finding]:
    """W103/W104/W105 on the compiled communication-only subprogram.

    ``coll`` injects precomputed ``hlo_stats.collective_bytes`` output
    (tests); otherwise the comm subprogram is lowered and compiled here."""
    import jax

    where = f"spec {run.spec.algorithm.name}"
    comm_fn = getattr(run.step, "comm_fn", None)
    if run.mesh is None or comm_fn is None:
        return []
    expected, _ = expected_step_collectives(run)
    if coll is None:
        from repro.launch.hlo_stats import collective_bytes
        state = jax.eval_shape(run.init, jax.random.PRNGKey(0))
        sh = run.shardings(state)
        with run.mesh:
            compiled = jax.jit(comm_fn, in_shardings=(sh,),
                               out_shardings=sh).lower(state).compile()
        coll = collective_bytes(compiled.as_text())
    findings: List[Finding] = []
    counts = coll.get("counts", {})

    # W105: resharding ops have no business between oracle and update
    for op in ("all-to-all", "collective-permute"):
        if counts.get(op, 0):
            findings.append(Finding(
                "W105", where,
                f"{counts[op]} {op} op(s) in the comm subprogram "
                f"({coll['bytes'][op]} B) — the reduction path resharded"))
    exp_gathers = sum(k for (p, *_), k in expected.items()
                      if p == "all_gather")
    if exp_gathers == 0 and counts.get("all-gather", 0):
        findings.append(Finding(
            "W105", where,
            f"{counts['all-gather']} all-gather op(s) in the comm "
            f"subprogram but the plan has none (no scatter-comm, no "
            f"trimmed mean)"))

    # W103: quantized policies must keep the narrow dtype on the wire
    cp = run.spec.compression
    if cp is not None and cp.quant is not None:
        try:
            check_compressed_collectives(run.spec, run.step.spec, coll)
        except RuntimeError as e:
            findings.append(Finding("W103", where, str(e)))

    # W104: byte-exact per dtype (XLA may combine ops, never bytes).
    # CPU re-widens bf16 reduces (documented W103 hint) — skip exactness
    # there for bf16 policies.
    if not (cp is not None and cp.quant == "bf16"
            and jax.default_backend() == "cpu"):
        want = expected_wire_bytes(expected,
                                   int(run.mesh.shape["data"]))
        got = dict(coll.get("bytes_by_dtype", {}))
        if want != got:
            findings.append(Finding(
                "W104", where,
                f"compiled comm-subprogram collective bytes "
                f"{got} != analytic model {want}"))
    return findings
