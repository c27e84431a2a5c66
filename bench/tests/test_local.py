"""FedBiOAcc-Local under uniform 2-of-4 participation: its plain reference
against the program at a tiny size on the CPU, the participants both
draw, the sections the check reads, and a tiny cell of the committed mix
run by the unedited harness."""
import jax
import jax.numpy as jnp
import pytest

from bench import cell as cells
from bench import check
from bench import run as R
from bench.calibrate import batches, readings
from bench.reference import fedbioacc
from bench.reference.common import Arith
from bench.tests import tiny


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("cell")), mix=tiny.LOCAL,
                      limits=tiny.LOCAL_LIMITS, cell="local")


def test_local_same_steps_in_float32():
    """With float32 parameters, where rounding cannot hide a difference in
    the mathematics, the check's three steps (two rounds, one of them
    communicating) agree with the reference in every number."""
    config = dict(tiny.SSM, param_dtype="float32")
    c = cells.Cell("t", 1, config, tiny.traffic(mix=tiny.LOCAL), {}, {})
    run = R.build(c)
    prog, finite, batch = readings(c, run, 5)
    nums = check.numbers(prog, R.reference(c, batch, 5))
    assert finite
    for name, (value, leaf) in nums.items():
        assert value < 2e-5, (name, value, leaf)


@pytest.mark.parametrize("seed", [0, 3])
def test_participants_match_the_program(seed):
    from repro.federation.participation import (ParticipationSpec,
                                                make_participation)
    part = make_participation(ParticipationSpec(
        sampler="uniform", clients_per_round=2, seed=seed), 4)
    hp = {"participation": {"sampler": "uniform", "clients_per_round": 2,
                            "seed": seed}}
    drawn = set()
    for rnd in range(8):
        mask = jax.device_get(part.mask_fn(jnp.int32(rnd)))
        live = fedbioacc.participants(hp, 4, rnd)
        assert [i for i in range(4) if mask[i] > 0] == live
        drawn.add(tuple(live))
    assert len(drawn) > 1
    full = {"participation": {"sampler": "full", "clients_per_round": 0,
                              "seed": 0}}
    assert fedbioacc.participants(full, 4, 1) == [0, 1, 2, 3]


@pytest.mark.parametrize("mix,want", [
    (tiny.GLOBAL, [("x", "nu", True), ("y", "omega", True),
                   ("u", "q", True)]),
    (tiny.LOCAL, [("x", "nu", True), ("y", "omega", False)])])
def test_sections_follow_the_algorithm(mix, want):
    run = R.build(cells.Cell("t", 1, tiny.SSM, tiny.traffic(mix=mix), {}, {}))
    assert check.sections(run) == want
    views = jax.eval_shape(lambda k: run.views(run.init(k)),
                           jax.random.PRNGKey(0))
    for s, m, _ in want:
        assert getattr(views, s) is not None and getattr(views, m) is not None


def test_local_cell_from_files_runs_and_is_correct(manifest):
    c = cells.load(manifest, "tiny-ssm.local")
    out = R.run_cell(c, 2 ** 33 + 9, 0.5, False, jax.devices()[:1],
                     tiny.PEAKS)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["check"]) == {"nonfinite_leaves", *tiny.LOCAL_LIMITS}
    for k, v in out["check"].items():
        assert v["value"] <= v["limit"], k


@pytest.mark.parametrize("operands_only", [False, True])
def test_local_controls_fail_the_check(manifest, operands_only):
    """The reference in float8 (or with float8 operands alone), put in the
    program's place: over three seeds it exceeds a limit every time."""
    c = cells.load(manifest, "tiny-ssm.local")
    ar = Arith(control=True, operands_only=operands_only)
    for seed in (1, 2, 3):
        batch = batches(c, seed)
        ref = R.reference(c, batch, seed)
        nums = check.numbers(R.reference(c, batch, seed, ar), ref)
        assert any(v > tiny.LOCAL_LIMITS[k] for k, (v, _) in nums.items()
                   if k in tiny.LOCAL_LIMITS), nums
