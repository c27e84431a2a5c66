"""Round-level telemetry (repro.telemetry): the telemetry-off path is the
LITERAL pre-telemetry engine (uint8 bit-identity across all five
algorithms, and telemetry-ON trajectories match it too), in-band metrics
are present/finite, the event log repairs crashed tails and stays
append-safe, and the validate CLI reconciles comm bytes against the
analytic model (catching tampered streams)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FederatedConfig
from repro.optim import sequences as seqs
from repro.telemetry import (EventLog, TelemetrySpec, read_events,
                             resolve_metric_groups, validate_events)
from repro.telemetry.events import TelemetryError

ALGOS = ("fedbio", "fedbioacc", "fedbio_local", "fedbioacc_local", "fedavg")
M = 4

_SHAPES = {"x": {"w": (3, 5)}, "y": {"h": (7,)}, "u": {"v": (11,)},
           "params": {"w": (3, 5), "h": (7,)}}


def _make(algo, telemetry=None, **kw):
    """Toy engine on the flat substrate: templates WITHOUT the client axis,
    init var trees WITH it."""
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

    eng = seqs.make_engine(cfg, aspec, tmpl, jax.vmap(one), block=8,
                           telemetry=telemetry, **kw)
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


def _assert_bit_identical(sa, sb):
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(
            np.ravel(np.asarray(a)).view(np.uint8),
            np.ravel(np.asarray(b)).view(np.uint8))


@pytest.mark.parametrize("algo", ALGOS)
def test_telemetry_off_on_bit_identity(algo):
    """Telemetry OFF keeps the bare step(state, batch) -> state contract
    (the literal pre-telemetry path), and turning metrics ON leaves the
    trajectory BIT-identical (uint8 view) — metrics are read off the
    already-materialized buffers, never fed back."""
    eng_off, s_off = _make(algo)
    eng_on, s_on = _make(algo, telemetry=TelemetrySpec())
    assert eng_off.step.telemetry_groups == ()
    assert eng_on.step.telemetry_groups == ("norms", "drift")
    mets = None
    for b in _batches(4):
        s_off = eng_off.step(s_off, b)          # bare state: not a tuple
        s_on, mets = eng_on.step(s_on, b)
    _assert_bit_identical(s_off, s_on)
    aspec = seqs.SPECS[algo]
    sec = aspec.sections[0]
    keys = [f"upd_norm/{sec}", f"drift/{sec}"]
    if aspec.has_momentum:          # fedbio/fedbio_local carry no momentum
        keys.append(f"mom_norm/{sec}")
    for k in keys:
        assert k in mets and np.isfinite(float(mets[k])), (k, mets)


def test_storm_step1_update_norm_is_zero():
    """STORM sequences update with the ENTERING momentum — the very first
    step's update norm is exactly 0 (the leading zero the validate CLI's
    trend check drops)."""
    eng, s = _make("fedbioacc", telemetry=TelemetrySpec())
    b = _batches(1)[0]
    _, mets = eng.step(s, b)
    assert float(mets["upd_norm/u"]) == 0.0
    assert float(mets["mom_norm/u"]) > 0.0


def test_metric_group_resolution_and_rejections():
    assert resolve_metric_groups(None) == ("norms", "drift")
    assert resolve_metric_groups(None, compressed=True, guarded=True) == (
        "norms", "drift", "compression", "health")
    with pytest.raises(ValueError, match="unknown telemetry metric"):
        resolve_metric_groups(("norms", "bogus"))
    # explicit groups whose inputs the run doesn't have: clear errors
    with pytest.raises(ValueError, match="'compression' needs"):
        _make("fedbioacc", telemetry=TelemetrySpec(metrics=("compression",)))
    with pytest.raises(ValueError, match="'health' needs"):
        _make("fedbioacc", telemetry=TelemetrySpec(metrics=("health",)))


def test_trainer_rejects_unfused_inband_metrics():
    from repro.federation.trainer import _telemetry_setup
    with pytest.raises(ValueError, match="fuse_storm"):
        _telemetry_setup(TelemetrySpec(metrics=("norms",)), False)
    # events-only spec is fine unfused; fused passes through
    assert _telemetry_setup(TelemetrySpec(metrics=()), False) is None
    t = TelemetrySpec()
    assert _telemetry_setup(t, True) is t


def test_eventlog_append_resume_and_tail_repair(tmp_path):
    p = str(tmp_path / "events.jsonl")
    with EventLog(p, experiment=None) as log:
        log.emit("metrics", step=1, val_loss=2.5)
    # a crashed writer leaves a partial tail line — the next open repairs it
    with open(p, "a") as f:
        f.write('{"event": "metrics", "seq": 2, "ts": 0, "st')
    with pytest.raises(TelemetryError, match="unterminated"):
        read_events(p)
    with EventLog(p, experiment=None) as log:      # repair + new segment
        log.emit("run_end", step=1, status="ok")
    evs = read_events(p)
    assert [e["event"] for e in evs] == ["run_start", "metrics",
                                         "run_start", "run_end"]
    s = validate_events(p)
    assert s["segments"] == 2 and s["events"] == 4


def test_eventlog_rejects_missing_required_keys(tmp_path):
    with EventLog(str(tmp_path / "e.jsonl"), experiment=None) as log:
        with pytest.raises(TelemetryError, match="missing required"):
            log.emit("comm", step=2, round=1)      # no elems/bytes_wire


def test_validate_reconciles_comm_bytes(tmp_path):
    """Exact comm: bytes_wire = reductions x elems x 4 B; a tampered byte
    count fails reconciliation against the embedded spec's model."""
    p = str(tmp_path / "e.jsonl")
    with EventLog(p, experiment={"compression": None}) as log:
        log.emit("comm", step=2, round=1, elems=1000, reductions=2,
                 bytes_wire=8000)
    assert validate_events(p)["comm_reconciled"] == 1
    with EventLog(p, experiment={"compression": None}) as log:
        log.emit("comm", step=4, round=2, elems=1000, reductions=2,
                 bytes_wire=16000)                  # tampered: doubled
    with pytest.raises(TelemetryError, match="disagrees with the analytic"):
        validate_events(p)


def test_validate_reconciles_compressed_comm(tmp_path):
    from repro.federation.compression import (CompressionSpec,
                                              wire_bytes_per_elem)
    cp = CompressionSpec(quant="int8", topk_frac=0.10)
    wire = wire_bytes_per_elem(cp, 256)
    p = str(tmp_path / "e.jsonl")
    with EventLog(p, experiment={"compression": cp._asdict()}) as log:
        log.emit("comm", step=2, round=1, elems=4096, reductions=2,
                 block=256, bytes_wire=int(2 * 4096 * wire))
    assert validate_events(p)["comm_reconciled"] == 1


def test_validate_expect_and_trend(tmp_path):
    p = str(tmp_path / "e.jsonl")
    with EventLog(p, experiment=None) as log:
        for t, v in enumerate((0.0, 3.0, 2.0, 1.0)):   # leading zero dropped
            log.emit("metrics", step=t + 1, **{"mom_norm/u": v})
    validate_events(p, trend_decreasing=("mom_norm/u",))
    with pytest.raises(TelemetryError, match="expected at least one"):
        validate_events(p, expect=("rollback",))
    with pytest.raises(TelemetryError, match="does not trend down"):
        validate_events(p, trend_decreasing=("step",))


def test_telemetry_spec_experiment_roundtrip():
    from repro.api import Experiment
    exp = Experiment().edit(**{
        "execution.fuse_storm": True, "execution.fuse_oracles": True,
        "telemetry.metrics": ["norms", "drift"],
        "telemetry.sink": "events.jsonl"})
    exp.validate()
    back = Experiment.from_json(exp.to_json())
    assert back.telemetry == exp.telemetry
    assert back.telemetry.metrics == ("norms", "drift")
    with pytest.raises(ValueError, match="telemetry"):
        exp.edit(**{"telemetry.metrics": ["bogus"]}).validate()
    with pytest.raises(ValueError, match="fuse_storm"):
        exp.edit(**{"execution.fuse_storm": False}).validate()


@pytest.mark.timeout(900)
def test_faulty_run_emits_rollback_events(tmp_path):
    """A run whose NaNs reach the unscreened mean rolls back, exhausts the
    retry budget, and the event stream records the whole audit trail:
    rollback events with (step, retry, bad_loss), retry_budget_exhausted,
    and a run_end carrying that status — asserted end-to-end through the
    train driver (which must exit non-zero)."""
    from repro.api import Experiment
    root = os.path.join(os.path.dirname(__file__), "..")
    exp = Experiment.load(
        os.path.join(root, "experiments", "fedbioacc_faulty.json")).edit(**{
            "faults.nan_rate": 1.0, "schedule.steps": 6,
            "robustness.screen": False, "robustness.aggregator": "mean"})
    spec = tmp_path / "faulty.json"
    spec.write_text(exp.to_json())
    sink = str(tmp_path / "events.jsonl")
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--experiment",
         str(spec), "--telemetry-sink", sink, "--log-every", "1"],
        env=env, capture_output=True, text=True, timeout=850)
    assert res.returncode != 0, res.stdout[-2000:]
    validate_events(sink, expect=("rollback", "retry_budget_exhausted"))
    evs = read_events(sink)
    rb = [e for e in evs if e["event"] == "rollback"]
    assert len(rb) == exp.robustness.retry_budget
    assert {"step", "retry", "bad_loss"} <= set(rb[0])
    assert [e["retry"] for e in rb] == [1, 2]
    assert evs[-1]["event"] == "run_end"
    assert evs[-1]["status"] == "retry_budget_exhausted"


def test_comm_plan_matches_flat_spec():
    """The analytic plan counts exactly the communicated elements of the
    engine's flat layout (padded, shard-replicated extents), doubled for
    the momentum reduction; cadence-skipped rounds return None."""
    from repro.telemetry import comm_plan, round_bytes
    eng, _ = _make("fedbioacc_local")   # y PRIVATE: only x communicates
    plan = comm_plan(eng.spec, eng.aspec, None)
    assert plan is not None and plan.reductions == 2   # storm: vars + mom
    assert [s[0] for s in plan.sections] == ["x"]      # private y excluded
    b1 = round_bytes(plan, 1)
    assert b1 is not None and b1["bytes_wire"] == pytest.approx(
        plan.reductions * b1["elems"] * 4.0)
    # the x group extent covers the padded x run: elems >= 3*5, < 2 blocks
    assert 15 <= b1["elems"] <= 16


@pytest.mark.timeout(600)
def test_train_loop_emits_summed_host_spans(tmp_path):
    """With telemetry.trace on, ``repro.launch.train`` sums its host phases in
    memory and writes one ``span`` per phase at log steps (``dur_s``
    summed, ``count``); ``repro.launch.metrics`` adds them up over the run."""
    from repro.launch.metrics import summarize
    root = os.path.join(os.path.dirname(__file__), "..")
    sink = str(tmp_path / "events.jsonl")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--experiment",
         os.path.join(root, "experiments", "fedbioacc_telemetry.json"),
         "--telemetry-sink", sink, "--steps", "4", "--log-every", "2",
         "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "2"],
        env=env, capture_output=True, text=True, timeout=550)
    assert res.returncode == 0, res.stderr[-2000:]
    evs = read_events(sink)
    spans = [e for e in evs if e["event"] == "span"]
    # logs at steps 1, 2 and 4; checkpoints at 2 and 4
    assert [e["step"] for e in spans] == [1] * 3 + [2] * 4 + [4] * 4
    s = summarize(evs)
    assert s["span_counts"] == {"checkpoint": 2, "eval": 3,
                                "train/batch": 4, "train/dispatch": 4}
    for name, total in s["span_seconds"].items():
        assert total == pytest.approx(
            sum(e["dur_s"] for e in spans if e["name"] == name), abs=1e-3)
        assert total > 0


SCOPES = ("fed.oracle", "fed.oracle_g", "fed.unflatten", "fed.flatten",
          "fed.update", "fed.reduce")


def _products_op_names(hlo_text):
    """The full ``op_name`` of every ``dot``/``convolution`` of a lowered
    (not yet optimized) module's HLO text with debug info.  An outlined
    function's ops name themselves from the function down, so each is
    prefixed with its call sites' names; loop and branch bodies name
    themselves from their function already."""
    import re
    head = re.compile(r"^(ENTRY )?%?([\w.-]+) .*\{$")
    bodies, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        m = head.match(line)
        if m:
            cur = m.group(2)
            entry = cur if m.group(1) else entry
            bodies[cur] = []
        elif cur is not None and " = " in line:
            bodies[cur].append(line)
    out = []

    def walk(comp, prefix):
        for line in bodies[comp]:
            op = re.search(r'op_name="([^"]*)"', line)
            name = "/".join(filter(None, (prefix, op.group(1) if op else "")))
            if re.search(r"\s(dot|convolution)\(", line):
                out.append(name)
            for callee in re.findall(r"to_apply=%?([\w.-]+)", line):
                walk(callee, name)
            for callee in re.findall(
                    r"(?:body|condition|true_computation|false_computation)="
                    r"%?([\w.-]+)", line):
                walk(callee, prefix)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                for callee in re.findall(r"[\w.-]+", group):
                    walk(callee, prefix)

    walk(entry, "")
    return out


@pytest.mark.timeout(600)
@pytest.mark.parametrize("algo", ["fedbioacc", "fedbioacc_local"])
def test_compiled_step_names_its_layers(algo):
    """A tiny step compiled on the CPU carries every ``fed.`` scope in its
    ops' metadata, as the benchmark's reader (``bench/scopes.py``) parses
    it, and every matrix product the program issues lies under
    ``fed.oracle``, in the lowered module and in the compiled one."""
    from bench import scopes
    from bench.trace import matmul_ops
    from repro.api import Experiment, build
    root = os.path.join(os.path.dirname(__file__), "..")
    run = build(Experiment.load(os.path.join(root, "experiments",
                                             f"{algo}.json")))
    state = jax.eval_shape(run.init, jax.random.PRNGKey(0))
    batch = jax.eval_shape(run.batch_fn, jax.random.PRNGKey(1))
    lowered = jax.jit(run.step).lower(state, batch)
    after = lowered.compile().as_text()
    compiled = scopes.scope_map(after)
    assert set(SCOPES) <= {s for path in compiled.values() for s in path}
    products = _products_op_names(
        lowered.as_text(dialect="hlo", debug_info=True))
    assert len(products) > 10
    for name in products:
        assert "fed.oracle" in scopes.scopes(name), name
    for name in matmul_ops(after):
        assert "fed.oracle" in compiled[name], (name, compiled[name])
