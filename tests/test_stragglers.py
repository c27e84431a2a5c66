"""Straggler-resilient elastic rounds (repro.federation.stragglers + the
engine integration): deterministic heavy-tailed compute-time draws, the
deadline/quorum/backoff round decision (arrivals >= quorum on EVERY
accepted round), late-arrival policy semantics on the flat substrate,
over-provisioned sampling, the adaptive deadline riding FlatState,
stragglers-off bit-identity, EF freezing for non-arrivals, the declarative
spec surface, and the quorum-miss telemetry trail end to end."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (AlgorithmSpec, Experiment, ProblemSpec, ScheduleSpec,
                       SpecError, StragglerSpec)
from repro.config import FederatedConfig
from repro.federation.participation import (ParticipationSpec,
                                            make_participation)
from repro.federation.stragglers import (ARRIVAL_HIST_BINS,
                                         arrival_histogram,
                                         expected_arrival_fraction,
                                         make_stragglers, over_provision,
                                         simulate_rounds)
from repro.optim import flat
from repro.optim import sequences as seqs

M = 4

# a heavy tail around a 1.0 deadline: round 0's seed-0 draws put some of
# the M=4 clients past the deadline and the rest before it (the tests that
# need a mixed round derive who is late from the draws — :func:`_late0`)
_MIXED = StragglerSpec(base_time=1.0, tail=1.0, deadline=1.0, quorum=0.25,
                       max_extensions=0, adapt_rate=0.0, seed=0,
                       over_provision=0)


def _late0(spec=_MIXED):
    """[M] bool: the clients whose round-0 draw misses ``spec.deadline``,
    asserted to make a mixed round — at least one late client, and enough
    on-time ones for quorum without an extension."""
    strag = make_stragglers(spec, M)
    late = np.asarray(strag.round_times(0)) > spec.deadline
    assert late.any(), "round 0 must have a late client"
    assert (~late).sum() >= int(strag.quorum_count(jnp.ones(M))), \
        "round 0's on-time clients must make quorum"
    return late


# ---------------------------------------------------------------------------
# compute-time draws: determinism, resume, tails
# ---------------------------------------------------------------------------

def test_times_deterministic_and_resumable():
    """Same (seed, round, client) ⇒ same times across independent engines
    and regardless of evaluation order (resume safety), incl. under jit."""
    spec = StragglerSpec(tail=1.0, seed=5)
    s1, s2 = make_stragglers(spec, 8), make_stragglers(spec, 8)
    seq1 = [np.asarray(s1.round_times(r)) for r in range(10)]
    for r in (0, 4, 9):                     # s2 jumps straight to round r
        np.testing.assert_array_equal(seq1[r], np.asarray(s2.round_times(r)))
    jt = jax.jit(s1.round_times)
    np.testing.assert_array_equal(seq1[3], np.asarray(jt(jnp.int32(3))))
    s3 = make_stragglers(spec._replace(seed=6), 8)
    assert any(not np.array_equal(seq1[r], np.asarray(s3.round_times(r)))
               for r in range(10))
    # rounds are independent draws, not a shared permutation
    assert not np.array_equal(seq1[0], seq1[1])


def test_times_lognormal_shape():
    """tail=0 collapses to base_time exactly; a heavy tail spreads the
    distribution around the base_time median."""
    s0 = make_stragglers(StragglerSpec(base_time=2.5, tail=0.0), 16)
    np.testing.assert_allclose(np.asarray(s0.round_times(0)),
                               np.full(16, 2.5), rtol=1e-6)
    s1 = make_stragglers(StragglerSpec(base_time=1.0, tail=1.0, seed=3), 256)
    t = np.asarray(s1.round_times(0))
    assert t.min() > 0.0
    assert np.median(t) == pytest.approx(1.0, rel=0.35)
    assert t.max() / t.min() > 10.0         # heavy heterogeneity


def test_make_stragglers_validation():
    assert make_stragglers(None, 4) is None
    for bad in ({"late_policy": "defer"}, {"base_time": 0.0},
                {"tail": -1.0}, {"deadline": 0.0}, {"over_provision": -1},
                {"quorum": 0.0}, {"quorum": 1.5}, {"backoff": 0.5},
                {"max_extensions": -1}, {"target_percentile": 0.0},
                {"adapt_rate": 1.5}, {"start_round": -1}):
        with pytest.raises(ValueError, match=next(iter(bad))):
            make_stragglers(StragglerSpec(**bad), 4)


# ---------------------------------------------------------------------------
# the round decision: deadline, quorum, backoff ladder, fallback
# ---------------------------------------------------------------------------

def _decide(strag, r, sampled, dl):
    arr, eff, ext, nd = strag.round_decision(r, jnp.asarray(sampled,
                                                            jnp.float32),
                                             jnp.float32(dl))
    return np.asarray(arr), float(eff), int(ext), float(nd)


def test_round_decision_generous_deadline():
    """A deadline beating every sampled time accepts the whole sample with
    zero extensions at the deadline itself."""
    strag = make_stragglers(_MIXED, M)
    arr, eff, ext, _ = _decide(strag, 0, np.ones(M), 100.0)
    np.testing.assert_array_equal(arr, np.ones(M))
    assert eff == 100.0 and ext == 0


def test_round_decision_mixed_round():
    """The seed-0 round: exactly the clients whose draw misses the 1.0
    deadline are late, quorum holds without extensions, non-sampled
    clients never arrive."""
    strag = make_stragglers(_MIXED, M)
    late = _late0()
    arr, eff, ext, _ = _decide(strag, 0, np.ones(M), 1.0)
    np.testing.assert_array_equal(arr, np.where(late, 0.0, 1.0))
    assert eff == 1.0 and ext == 0
    # a non-sampled client is not an arrival even if its draw is fast
    arr2, _, _, _ = _decide(strag, 0, [0.0, 1.0, 1.0, 1.0], 100.0)
    np.testing.assert_array_equal(arr2, [0.0, 1.0, 1.0, 1.0])


def test_round_decision_extension_ladder():
    """A deadline below the quorum order statistic extends through
    deadline * backoff**k; ext reports the first rung that makes quorum."""
    strag = make_stragglers(_MIXED._replace(quorum=0.75, backoff=2.0,
                                            max_extensions=3), M)
    t = np.sort(np.asarray(strag.round_times(0)))    # q = 3 → t[2] ≈ 0.79
    dl = 0.9 * t[2]                                  # rung 0 misses quorum
    arr, eff, ext, _ = _decide(strag, 0, np.ones(M), dl)
    assert ext >= 1 and eff == pytest.approx(dl * 2.0 ** ext, rel=1e-6)
    assert arr.sum() >= 3


def test_round_decision_full_miss_falls_back_to_quorum():
    """When the ENTIRE sampled set misses every rung of the ladder the
    round falls back to the quorum-th order statistic: arrivals == quorum
    exactly, ext == max_extensions + 1 (the exhausted marker) — so
    arrivals >= quorum holds on every accepted round by construction."""
    strag = make_stragglers(_MIXED._replace(max_extensions=1, backoff=1.5), M)
    t = np.sort(np.asarray(strag.round_times(0)))
    dl = 0.01                       # dl and dl*1.5 both below min time
    assert dl * 1.5 < t[0]
    arr, eff, ext, _ = _decide(strag, 0, np.ones(M), dl)
    q = int(strag.quorum_count(jnp.ones(M)))
    assert int(arr.sum()) == q == 1
    assert ext == 2                 # max_extensions + 1
    assert eff == pytest.approx(t[q - 1], rel=1e-6)


def test_round_decision_quorum_always_met():
    """Fuzz the invariant across rounds, deadlines and sample sizes."""
    strag = make_stragglers(StragglerSpec(tail=1.5, quorum=0.6, seed=9,
                                          max_extensions=1), 8)
    for r in range(6):
        for dl in (0.05, 0.5, 2.0):
            sampled = np.asarray(
                jax.random.bernoulli(jax.random.fold_in(
                    jax.random.PRNGKey(r), int(dl * 100)), 0.7, (8,)),
                np.float32)
            if sampled.sum() == 0:
                sampled[0] = 1.0
            arr, _, _, _ = _decide(strag, r, sampled, dl)
            q = int(strag.quorum_count(jnp.asarray(sampled)))
            assert int(arr.sum()) >= q >= 1
            assert np.all(arr <= sampled)


def test_round_decision_warmup_and_adaptive_ema():
    """Rounds before start_round stay synchronous (everyone sampled
    arrives, deadline untouched); after it the next deadline follows
    d' = (1-rate) d + rate * t_p with the target-percentile statistic."""
    spec = _MIXED._replace(start_round=2, adapt_rate=0.5,
                           target_percentile=0.75)
    strag = make_stragglers(spec, M)
    for r in (0, 1):
        arr, eff, ext, nd = _decide(strag, r, np.ones(M), 1.0)
        np.testing.assert_array_equal(arr, np.ones(M))
        assert eff == 0.0 and ext == 0 and nd == 1.0
    t = np.sort(np.asarray(strag.round_times(2)))
    t_p = t[int(np.ceil(0.75 * M)) - 1]
    _, _, _, nd = _decide(strag, 2, np.ones(M), 1.0)
    assert nd == pytest.approx(0.5 * 1.0 + 0.5 * t_p, rel=1e-6)
    # adapt_rate=0 keeps the deadline static
    s0 = make_stragglers(spec._replace(adapt_rate=0.0, start_round=0), M)
    _, _, _, nd0 = _decide(s0, 0, np.ones(M), 1.7)
    assert nd0 == pytest.approx(1.7)


def test_over_provision_and_histogram():
    sg = StragglerSpec(over_provision=2)
    p = ParticipationSpec(sampler="uniform", clients_per_round=4)
    assert over_provision(sg, p, 8).clients_per_round == 6
    assert over_provision(sg, p._replace(clients_per_round=7),
                          8).clients_per_round == 8     # capped at M
    full = ParticipationSpec(sampler="full")
    assert over_provision(sg, full, 8) is full          # no count to bump
    assert over_provision(sg._replace(over_provision=0), p, 8) is p
    assert over_provision(sg, None, 8) is None
    # histogram: one entry per SAMPLED client; arrivals land in bins 0-3
    t = jnp.array([0.2, 0.9, 1.7, 5.0])
    h = np.asarray(arrival_histogram(t, jnp.float32(1.0),
                                     jnp.array([1.0, 1.0, 1.0, 0.0])))
    assert h.shape == (ARRIVAL_HIST_BINS,)
    assert h.sum() == 3.0                               # client 3 unsampled
    assert h[:4].sum() == 2.0 and h[-1] == 0.0


def test_simulate_rounds_elastic_beats_barrier():
    """The simulated clock: wall_clock <= wait_for_slowest on every round
    (rounds close early once everyone is in), strictly cheaper in sum
    under a heavy tail, and arrivals >= quorum throughout."""
    strag = make_stragglers(StragglerSpec(tail=1.0, deadline=1.5,
                                          quorum=0.5, seed=1), 8)
    part = make_participation(
        ParticipationSpec(sampler="uniform", clients_per_round=6), 8)
    rows = simulate_rounds(strag, part, 24)
    assert len(rows) == 24
    for r in rows:
        assert r["wall_clock"] <= r["wait_for_slowest"] + 1e-9
        assert r["arrivals"] >= r["quorum"] >= 1
        assert r["arrivals"] <= r["sampled"] == 6
    assert (sum(r["wall_clock"] for r in rows)
            < 0.9 * sum(r["wait_for_slowest"] for r in rows))
    frac = expected_arrival_fraction(strag, part, 24)
    assert 0.0 < frac <= 1.0
    assert expected_arrival_fraction(None, part) == 1.0


# ---------------------------------------------------------------------------
# engine integration on the toy flat substrate
# ---------------------------------------------------------------------------

_SHAPES = {"x": {"w": (3, 5)}, "y": {"h": (7,)}, "u": {"v": (11,)},
           "params": {"w": (3, 5), "h": (7,)}}
ALGOS = ("fedbio", "fedbioacc", "fedbio_local", "fedbioacc_local", "fedavg")


def _make(algo, **kw):
    cfg = FederatedConfig(algorithm=algo, num_clients=M, local_steps=2,
                          lr_x=0.05, lr_y=0.05, lr_u=0.05, c_nu=1.0,
                          c_omega=1.0, c_u=1.0, alpha_delta=1.0,
                          alpha_u0=4.0, hierarchy_period=0,
                          hierarchy_groups=2)
    aspec = seqs.SPECS[algo]
    tmpl = {s: {k: jax.ShapeDtypeStruct(shape, jnp.float32)
                for k, shape in _SHAPES[s].items()} for s in aspec.sections}

    def one(v, b):
        return {s: jax.tree.map(lambda t: jnp.tanh(t) + 0.01 * b, v[s])
                for s in v}

    eng = seqs.make_engine(cfg, aspec, tmpl, jax.vmap(one), block=8, **kw)
    key, i, vt = jax.random.PRNGKey(0), 0, {}
    for s in aspec.sections:
        vt[s] = {}
        for k, shape in _SHAPES[s].items():
            vt[s][k] = jax.random.normal(jax.random.fold_in(key, i),
                                         (M,) + shape)
            i += 1
    return eng, eng.init_state(vt)


def _batches(steps):
    key = jax.random.PRNGKey(7)
    return [jax.random.normal(jax.random.fold_in(key, t), (M,))
            for t in range(steps)]


def _bits(a, b):
    np.testing.assert_array_equal(np.ravel(np.asarray(a)).view(np.uint8),
                                  np.ravel(np.asarray(b)).view(np.uint8))


@pytest.mark.parametrize("algo", ALGOS)
def test_stragglers_off_is_the_synchronous_engine(algo):
    """Stragglers OFF keeps the zero-leaf deadline convention and — with a
    warmup-only straggler layer attached next to the same sampler — the
    vars/mom/stale trajectory stays BIT-identical to the synchronous
    engine: the warmup path is the literal pre-straggler round."""
    pspec = ParticipationSpec(sampler="uniform", clients_per_round=3)
    part = make_participation(pspec, M)
    eng_off, s_off = _make(algo, participation=part)
    assert s_off.deadline == ()
    strag = make_stragglers(StragglerSpec(start_round=10 ** 6), M)
    eng_on, s_on = _make(algo, participation=make_participation(pspec, M),
                         stragglers=strag)
    assert float(s_on.deadline) == strag.spec.deadline
    for b in _batches(4):
        s_off = eng_off.step(s_off, b)
        s_on = eng_on.step(s_on, b)
    for a, b in zip(s_off.vars, s_on.vars):
        _bits(a, b)
    for a, b in zip(s_off.mom, s_on.mom):
        _bits(a, b)
    _bits(s_off.stale, s_on.stale)
    assert float(s_on.deadline) == strag.spec.deadline   # warmup: untouched


def test_late_policy_semantics_on_engine():
    """One elastic round with known misses (the seed-0 draws past the
    deadline, :func:`_late0`): `drop` freezes each straggler's row
    bit-exact and ages its staleness, `carry` lets the rows advance
    locally while still aging, `cancel` freezes the rows and treats the
    clients as served (no aging)."""
    late = _late0()
    on_time = int(np.flatnonzero(~late)[0])
    states = {}
    for policy in ("drop", "carry", "cancel"):
        strag = make_stragglers(_MIXED._replace(late_policy=policy), M)
        eng, s = _make("fedbioacc", stragglers=strag)
        init_vars = s.vars
        for b in _batches(2):                      # exactly one round
            s = eng.step(s, b)
        states[policy] = (init_vars, s)
    for policy in ("drop", "cancel"):
        init_vars, s = states[policy]
        for v0, v1 in zip(init_vars, s.vars):
            _bits(v0[late], v1[late])              # straggler rows frozen
            assert not np.array_equal(np.asarray(v0[on_time]),
                                      np.asarray(v1[on_time]))
    init_vars, s = states["carry"]
    for i in np.flatnonzero(late):                 # kept computing
        assert any(not np.array_equal(np.asarray(v0[i]), np.asarray(v1[i]))
                   for v0, v1 in zip(init_vars, s.vars))
    np.testing.assert_array_equal(np.asarray(states["drop"][1].stale),
                                  late.astype(int))
    np.testing.assert_array_equal(np.asarray(states["carry"][1].stale),
                                  late.astype(int))
    np.testing.assert_array_equal(np.asarray(states["cancel"][1].stale),
                                  [0, 0, 0, 0])
    # drop and cancel aggregate the same arrivals-only mean: the arrived
    # rows of the communicated section match bit-exactly
    _bits(states["drop"][1].vars[0][on_time],
          states["cancel"][1].vars[0][on_time])


def test_deadline_rides_state_and_updates_at_comm_steps(tmp_path):
    """The adaptive deadline is a FlatState leaf: constant within a round,
    stepped through the EMA exactly at comm steps, carried bit-exactly
    through a checkpoint round-trip (the resume path), and seedable
    through init_state(deadline=...)."""
    from repro.checkpoint import load_checkpoint, save_checkpoint
    strag = make_stragglers(_MIXED._replace(adapt_rate=0.5), M)
    eng, s = _make("fedbioacc", stragglers=strag)
    assert float(s.deadline) == 1.0
    bs = _batches(4)
    s1 = eng.step(s, bs[0])
    assert float(s1.deadline) == 1.0              # mid-round: unchanged
    s2 = eng.step(s1, bs[1])                      # comm step closes round 0
    _, _, _, nd = strag.round_decision(0, jnp.ones(M), jnp.float32(1.0))
    assert float(s2.deadline) == pytest.approx(float(nd), rel=1e-6)
    assert float(s2.deadline) != 1.0
    # resume: the checkpointed state (deadline scalar included) continues
    # bit-exactly against the uninterrupted trajectory
    d = str(tmp_path / "ck")
    save_checkpoint(d, s2, {"step": 2})
    r2 = load_checkpoint(d, jax.eval_shape(lambda: s2))
    _bits(r2.deadline, s2.deadline)
    s3, r3 = eng.step(s2, bs[2]), eng.step(r2, bs[2])
    _bits(s3.deadline, r3.deadline)
    for a, b in zip(s3.vars, r3.vars):
        _bits(a, b)
    # and init_state can seed a custom deadline outright
    _, fresh = _make("fedbioacc", stragglers=strag)
    assert float(fresh.deadline) == 1.0


def test_ef_rows_freeze_for_non_arrivals():
    """Stragglers x compression at the substrate level: a client whose
    weight is zeroed by the arrival mask leaves its error-feedback row
    bit-exactly frozen, and a re-drawn mask from the SAME snapshot leaves
    the new non-participants' rows frozen instead (the retry contract)."""
    tree = {"x": jnp.zeros((6,), jnp.float32)}
    spec = flat.make_spec(jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), tree),
        sections=("x",), block=8)
    key = jax.random.PRNGKey(0)
    bufs = flat.flatten_tree(
        spec, {"x": jax.random.normal(key, (M, 6))}, batch_dims=1)
    ef = tuple(jax.random.normal(jax.random.fold_in(key, 9), b.shape)
               for b in bufs)
    ccfg = flat.CompressCfg(quant="int8", topk_frac=0.5)
    w1 = jnp.array([1.0, 0.0, 1.0, 1.0])          # client 1 missed
    _, ef1 = flat.client_mean_masked(spec, bufs, ("mean",), weights=w1,
                                     compress=ccfg, ef=ef)
    _bits(ef1[0][1], ef[0][1])                    # non-arrival frozen
    assert not np.array_equal(np.asarray(ef1[0][0]), np.asarray(ef[0][0]))
    w2 = jnp.array([0.0, 1.0, 1.0, 1.0])          # retry re-draw: client 0
    _, ef2 = flat.client_mean_masked(spec, bufs, ("mean",), weights=w2,
                                     compress=ccfg, ef=ef)
    _bits(ef2[0][0], ef[0][0])
    assert not np.array_equal(np.asarray(ef2[0][1]), np.asarray(ef[0][1]))


def test_rollback_guard_restores_ef_stale_and_deadline():
    """Satellite contract: a rollback retry restores FlatState.ef, .stale
    and .deadline bit-exactly from the snapshot, bumping only the retry
    slot (so fault masks re-draw against identical buffers)."""
    from repro.federation.faults import RobustnessSpec, RollbackGuard
    key = jax.random.PRNGKey(0)
    good = seqs.FlatState(
        vars=(jax.random.normal(key, (M, 8)),),
        mom=(jax.random.normal(jax.random.fold_in(key, 1), (M, 8)),),
        step=jnp.int32(2), stale=jnp.array([0, 2, 1, 0], jnp.int32),
        retry=jnp.zeros((), jnp.int32),
        ef=((jax.random.normal(jax.random.fold_in(key, 2), (M, 8)),), ()),
        deadline=jnp.float32(1.25))
    guard = RollbackGuard(RobustnessSpec(retry_budget=2, ring=2))
    assert guard.observe(2, good, key, 1.0) is None
    bad = good._replace(step=jnp.int32(4),
                        vars=(jnp.full((M, 8), jnp.nan),))
    step, restored, _ = guard.observe(4, bad, key, float("nan"))
    assert step == 2 and int(restored.retry) == 1
    _bits(restored.vars[0], good.vars[0])
    _bits(restored.mom[0], good.mom[0])
    _bits(restored.stale, good.stale)
    _bits(restored.ef[0][0], good.ef[0][0])
    _bits(restored.deadline, good.deadline)


# ---------------------------------------------------------------------------
# declarative surface: Experiment round-trip, validation, edit sweeps
# ---------------------------------------------------------------------------

def _exp(**edits):
    base = Experiment(
        algorithm=AlgorithmSpec("fedbioacc"),
        problem=ProblemSpec(arch="mamba2-130m", reduced=True, num_clients=8,
                            per_client=1, seq_len=16),
        schedule=ScheduleSpec(steps=4, local_steps=2, lr_x=0.05, lr_y=0.05,
                              lr_u=0.05, neumann_q=2, neumann_tau=0.3))
    base = base.edit(**{"execution.fuse_storm": True,
                        "execution.storm_block": 128})
    return base.edit(**edits) if edits else base


def test_spec_roundtrip_and_edit_promotion():
    exp = _exp(**{"participation.sampler": "uniform",
                  "participation.clients_per_round": 4,
                  "stragglers.deadline": 1.5, "stragglers.quorum": 0.5,
                  "stragglers.late_policy": "carry"})
    assert exp.stragglers == StragglerSpec(deadline=1.5, quorum=0.5,
                                           late_policy="carry")
    exp.validate()
    back = Experiment.from_json(exp.to_json())
    assert back == exp
    plain = Experiment.from_json(_exp().to_json())
    assert plain.stragglers is None
    d = json.loads(exp.to_json())
    assert d["stragglers"]["deadline"] == 1.5
    assert d["stragglers"]["late_policy"] == "carry"


def test_spec_validation_errors():
    with pytest.raises(SpecError, match="fuse_storm"):
        _exp(**{"execution.fuse_storm": False,
                "stragglers.deadline": 1.0}).validate()
    with pytest.raises(SpecError, match="hierarch"):
        _exp(**{"schedule.hierarchy_period": 2,
                "stragglers.deadline": 1.0}).validate()
    with pytest.raises(SpecError, match="late_policy"):
        _exp(**{"stragglers.late_policy": "defer"}).validate()
    with pytest.raises(SpecError, match="quorum"):
        _exp(**{"stragglers.quorum": 1.5,
                "stragglers.over_provision": 0}).validate()
    # over-provisioning needs a counted sampler (default is "full")
    with pytest.raises(SpecError, match="over_provision"):
        _exp(**{"stragglers.over_provision": 2}).validate()
    _exp(**{"stragglers.over_provision": 2,
            "participation.sampler": "uniform",
            "participation.clients_per_round": 4}).validate()
    with pytest.raises(SpecError, match="stragglers"):
        _exp(**{"telemetry.metrics": ["stragglers"]}).validate()


def test_build_composes_over_provisioned_sampler():
    """build() hands the trainer the over-provisioned sampler: the engine's
    recorded participation requests m + b clients."""
    from repro.api import build
    exp = _exp(**{"participation.sampler": "uniform",
                  "participation.clients_per_round": 4,
                  "stragglers.over_provision": 2,
                  "stragglers.deadline": 1.5})
    run = build(exp)
    assert run.step.stragglers is not None
    assert run.step.participation.spec.clients_per_round == 6
    state = run.init(jax.random.PRNGKey(0))
    assert float(state.deadline) == 1.5


# ---------------------------------------------------------------------------
# launch.train: quorum-miss telemetry end to end (subprocess)
# ---------------------------------------------------------------------------

def _train_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    root = os.path.join(os.path.dirname(__file__), "..")
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    return env


@pytest.mark.timeout(900)
def test_quorum_miss_round_emits_events(tmp_path):
    """An impossible deadline (every sampled client misses every backoff
    rung) drives the fallback each round: the run completes on quorum-sized
    arrival sets and the stream carries deadline + quorum_miss events with
    exhausted extension counts — and passes the validate CLI's straggler
    invariants."""
    exp = Experiment.load(os.path.join(os.path.dirname(__file__), "..",
                                       "experiments",
                                       "fedbioacc_straggler.json"))
    exp = exp.edit(**{"stragglers.deadline": 0.01,
                      "stragglers.adapt_rate": 0.0,
                      "stragglers.max_extensions": 1,
                      "schedule.steps": 4})
    path = str(tmp_path / "exp.json")
    exp.save(path)
    sink = str(tmp_path / "events.jsonl")
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--experiment", path,
         "--log-every", "2", "--telemetry-sink", sink],
        env=_train_env(), capture_output=True, text=True, timeout=850)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    from repro.telemetry import validate_events
    s = validate_events(sink, expect=("run_start", "metrics", "deadline",
                                      "quorum_miss", "run_end"))
    assert s["deadlines_checked"] >= 2
    from repro.telemetry import read_events
    evs = [e for e in read_events(sink) if e.get("event") == "deadline"]
    for e in evs:
        assert e["extensions"] == 2          # max_extensions + 1: exhausted
        assert e["arrivals"] >= e["quorum"] >= 1
        assert e["deadline"] > 0.01          # fallback order statistic
