"""Self-tests of ``repro.analysis`` — every rule demonstrated to FIRE.

A static verifier earns trust the same way a test suite does: by failing
on seeded violations.  Each rule here gets a mutation that must trip it —
an extra psum smuggled into the step, an f32 wire under an int8 policy, an
`np.random` call in source, a non-frozen spec dataclass — and the clean
builds/tree must stay silent.  Mutations enter through the public seams
only (a wrapped step function, a fabricated HLO-stats record, a
monkeypatched module boundary); the engine source is never edited.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import LINT_RULES, RULES
from repro.analysis import collectives as coll
from repro.analysis import structure as struct
from repro.analysis.lint import lint_paths, lint_source
from repro.api import Experiment, build

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _spec(name):
    return Experiment.load(os.path.join(_ROOT, "experiments", name))


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# rule registry
# ---------------------------------------------------------------------------

def test_registry_complete():
    assert set(LINT_RULES) == {r for r in RULES if r.startswith("L")}
    for r, rule in RULES.items():
        assert rule.id == r
        assert rule.proves and rule.fixit, r


# ---------------------------------------------------------------------------
# L3xx: the source lint, one seeded violation per rule
# ---------------------------------------------------------------------------

def test_l301_wall_clock_fires():
    src = "import time\nt = time.perf_counter()\n"
    assert _rules(lint_source(src, "x.py")) == {"L301"}


def test_l301_pragma_waives():
    src = ("import time\n"
           "t = time.time()  # analysis: ignore[L301] driver\n")
    assert lint_source(src, "x.py") == []


def test_l302_np_random_fires():
    src = "import numpy as np\nv = np.random.rand(3)\n"
    assert _rules(lint_source(src, "x.py")) == {"L302"}


def test_l302_stdlib_random_fires():
    src = "import random\nv = random.random()\n"
    fs = lint_source(src, "x.py")
    assert _rules(fs) == {"L302"} and len(fs) == 2   # import + call


def test_l303_host_sync_fires_in_engine_only():
    src = ("import jax.numpy as jnp\n"
           "def f(x):\n"
           "    return float(jnp.mean(x)) + x.sum().item()\n")
    assert _rules(lint_source(src, "x.py", engine=True)) == {"L303"}
    assert lint_source(src, "x.py", engine=False) == []


def test_l304_key_chain_fires_in_round_loop():
    src = ("import jax\n"
           "def f(key):\n"
           "    k1, k2 = jax.random.split(key)\n"
           "    return k1\n")
    assert _rules(lint_source(src, "x.py", round_loop=True)) == {"L304"}
    assert lint_source(src, "x.py", round_loop=False) == []
    # fold_in-pure and spec-seeded forms pass
    ok = ("import jax\n"
          "def g(spec, r):\n"
          "    return jax.random.fold_in(jax.random.PRNGKey(spec.seed), r)\n")
    assert lint_source(ok, "x.py", round_loop=True) == []


def test_l305_unfrozen_spec_fires():
    src = ("from dataclasses import dataclass\n"
           "@dataclass\n"
           "class FooSpec:\n"
           "    a: int = 0\n")
    assert _rules(lint_source(src, "x.py")) == {"L305"}
    assert lint_source(src.replace("@dataclass",
                                   "@dataclass(frozen=True)"),
                       "x.py") == []


def test_l306_mutable_default_fires():
    src = "def f(xs=[]):\n    return xs\n"
    assert _rules(lint_source(src, "x.py")) == {"L306"}


def test_committed_tree_lints_clean():
    assert lint_paths([os.path.join(_ROOT, "src", "repro")]) == []


# ---------------------------------------------------------------------------
# W1xx: collectives — a (1, 1) debug mesh traces the true sharded step
# (psum survives in the jaxpr at axis size 1) on the single CPU device
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def local_run():
    exp = _spec("fedbioacc_local.json").edit(
        **{"execution.mesh": (1, 1), "schedule.steps": 2})
    return build(exp)


@pytest.fixture(scope="module")
def int8_run():
    exp = _spec("fedbioacc_int8_topk.json").edit(
        **{"execution.mesh": (1, 1), "schedule.steps": 2})
    return build(exp)


def _seed_psum(run, elems):
    """run with one extra psum of ``elems`` f32 smuggled into the step."""
    def bad_step(state, batch):
        leak = jax.shard_map(lambda x: jax.lax.psum(x, "data"),
                             mesh=run.mesh, in_specs=P(), out_specs=P())(
                             jnp.zeros((elems,), jnp.float32))
        s, aux = run.step(state, batch)
        z = (0 * leak.sum()).astype(jnp.float32)
        return s._replace(vars=jax.tree.map(lambda v: v + z, s.vars)), aux
    bad_step.__dict__.update(run.step.__dict__)
    return run._replace(step=bad_step)


def test_clean_step_has_exactly_planned_collectives(local_run):
    assert coll.audit_step_collectives(local_run) == []


def test_w101_extra_psum_fires(local_run):
    assert _rules(coll.audit_step_collectives(
        _seed_psum(local_run, 7))) == {"W101"}


def test_w102_private_section_on_wire_fires(local_run):
    _, info = coll.expected_step_collectives(local_run)
    private = sorted(info["private_elems"])
    assert private, "fedbioacc_local must carry private sections"
    fs = coll.audit_step_collectives(_seed_psum(local_run, private[0]))
    assert _rules(fs) == {"W102"}
    assert "PRIVATE" in fs[0].message


def _wire(run):
    expected, _ = coll.expected_step_collectives(run)
    return coll.expected_wire_bytes(expected, int(run.mesh.shape["data"]))


def test_wire_model_accepts_exact_bytes(int8_run):
    ok = {"bytes": {}, "counts": {}, "bytes_by_dtype": _wire(int8_run)}
    assert coll.audit_wire(int8_run, coll=ok) == []


def test_w103_f32_wire_under_int8_fires(int8_run):
    want = _wire(int8_run)
    bad = {"bytes": {}, "counts": {},
           "bytes_by_dtype": {"f32": sum(want.values())}}
    fs = coll.audit_wire(int8_run, coll=bad)
    assert "W103" in _rules(fs)
    # the shared dryrun audit raises the same diagnosis directly
    with pytest.raises(RuntimeError, match="int8"):
        coll.check_compressed_collectives(int8_run.spec, int8_run.step.spec,
                                          bad)


def test_w104_byte_mismatch_fires(int8_run):
    want = _wire(int8_run)
    off = dict(want, f32=want.get("f32", 0) + 4)   # one f32 element extra
    fs = coll.audit_wire(int8_run,
                         coll={"bytes": {}, "counts": {},
                               "bytes_by_dtype": off})
    assert _rules(fs) == {"W104"}


def test_w105_resharding_op_fires(int8_run):
    fs = coll.audit_wire(int8_run, coll={
        "bytes": {"all-to-all": 64}, "counts": {"all-to-all": 1},
        "bytes_by_dtype": _wire(int8_run)})
    assert "W105" in _rules(fs)


# ---------------------------------------------------------------------------
# S2xx: structure — slot, jaxpr-identity, and telemetry-inertness seeds
# ---------------------------------------------------------------------------

def test_s201_fires_both_directions(local_run):
    assert struct.audit_state_slots(local_run) == []
    # participation on, stale leaf dropped -> "expects a stale leaf"
    missing = local_run._replace(
        init=lambda key: local_run.init(key)._replace(stale=()))
    fs = struct.audit_state_slots(missing)
    assert _rules(fs) == {"S201"} and "stale" in fs[0].message

    # featureless spec, stale leaf present -> "zero-leaf contract broken"
    run = build(_spec("fedavg.json"))
    assert struct.audit_state_slots(run) == []
    extra = run._replace(
        init=lambda key: run.init(key)._replace(
            stale=jnp.zeros((4,), jnp.int32)))
    fs = struct.audit_state_slots(extra)
    assert _rules(fs) == {"S201"} and "zero-leaf" in fs[0].message


def test_s202_leaked_feature_fires(monkeypatch):
    # re-seed the exact regression the rule exists for: a bare form whose
    # edits miss a normalize() promotion trigger, so the "feature-off"
    # build still carries the uniform sampler's mask/stale machinery
    edits = {k: v for k, v in struct.BARE_EDITS.items()
             if k != "participation.clients_per_round"}
    monkeypatch.setattr(struct, "BARE_EDITS", edits)
    fs = struct.audit_bare_jaxpr(_spec("fedbioacc_local.json"))
    assert _rules(fs) == {"S202"}
    assert "not the pre-feature baseline" in fs[0].message


def test_s203_noninert_telemetry_fires(monkeypatch):
    # force events-only telemetry (metrics=()) to resolve to the default
    # metric groups — exactly the "telemetry stopped compiling away" bug
    from repro.telemetry import spec as tspec
    orig = tspec.resolve_metric_groups
    monkeypatch.setattr(tspec, "resolve_metric_groups",
                        lambda metrics, **kw: orig(None, **kw))
    fs = struct.audit_telemetry_inert(_spec("fedbioacc_telemetry.json"))
    assert _rules(fs) == {"S203"}
