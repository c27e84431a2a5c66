"""The harness with the timed path broken underneath: every fault the cell
can have turns ``correct`` false."""
import jax
import pytest

from bench import cell as cells
from bench import faults
from bench import run as R
from bench.tests import tiny


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return cells.load(tiny.write(str(tmp_path_factory.mktemp("cell"))),
                      "tiny-ssm.t")


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_is_not_correct(cell, fault):
    with faults.planted(fault) as build_fn:
        out = R.run_cell(cell, 11, 0.3, False, jax.devices()[:1],
                         tiny.PEAKS, build_fn=build_fn)
    assert not out["correct"], out["check"]
