"""The harness on a FedBiOAcc-Local cell with the timed path broken
underneath: every fault the cell can have turns ``correct`` false."""
import jax
import pytest

from bench import cell as cells
from bench import faults
from bench import run as R
from bench.tests import tiny


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return cells.load(tiny.write(str(tmp_path_factory.mktemp("cell")),
                                 mix=tiny.LOCAL, limits=tiny.LOCAL_LIMITS,
                                 cell="local"), "tiny-ssm.local")


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_local_fault_is_not_correct(cell, fault):
    with faults.planted(fault) as build_fn:
        out = R.run_cell(cell, 11, 0.3, False, jax.devices()[:1],
                         tiny.PEAKS, build_fn=build_fn)
    assert not out["correct"], out["check"]
