"""A cell added from files alone, run by the unedited harness on the CPU
past its look for a chip; and the control, which the check must fail."""
import jax
import pytest

from bench import cell as cells
from bench import check
from bench import run as R
from bench.calibrate import readings
from bench.reference.common import Arith
from bench.tests import tiny


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("cell")))


def test_cell_from_files_runs_and_is_correct(manifest):
    c = cells.load(manifest, "tiny-ssm.t")
    out = R.run_cell(c, 2 ** 33 + 7, 0.5, False, jax.devices()[:1],
                     tiny.PEAKS)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {m["name"] for m in
                                   c.metrics["end_to_end"]}
    assert all(v["value"] > 0 for k, v in out["metrics"].items()
               if k != "peak_hbm_gb")
    for k, v in out["check"].items():
        assert v["value"] <= v["limit"], k


def fails_the_check(manifest, ar):
    c = cells.load(manifest, "tiny-ssm.t")
    run = R.build(c)
    for seed in (1, 2, 3):
        _, _, batch = readings(c, run, seed)
        ref = R.reference(c, batch, seed)
        nums = check.numbers(R.reference(c, batch, seed, ar), ref)
        assert any(v > tiny.LIMITS[k] for k, (v, _) in nums.items()), nums


def test_control_fails_the_check(manifest):
    """The reference in float8, put in the program's place, against the
    float32 reference: over three seeds it exceeds a limit every time."""
    fails_the_check(manifest, Arith(control=True))


def test_operand_control_fails_the_check(manifest):
    """The same with float8 operands alone, each product summed in
    float32."""
    fails_the_check(manifest, Arith(control=True, operands_only=True))


def test_nonfinite_leaves_are_counted():
    import jax.numpy as jnp
    tree = {"a": jnp.array([1.0, jnp.nan]), "b": jnp.ones(3),
            "c": jnp.array([jnp.inf]), "step": jnp.int32(3)}
    assert R.nonfinite_leaves(tree) == 2
    assert R.nonfinite_leaves({"b": jnp.ones(3)}) == 0
