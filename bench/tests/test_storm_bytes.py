"""The STORM byte model against the operands the kernel really streams."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import storm_bytes
from bench.reference import moe, ssm
from bench.tests import tiny


@pytest.mark.parametrize("config,family", [(tiny.SSM, ssm), (tiny.MOE, moe)])
def test_bytes_match_kernel_operands(config, family):
    from repro.optim import flat
    sizes, m = config["sizes"], 2
    params = jax.eval_shape(lambda k: family.init(k, sizes, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    spec = flat.make_spec({"x": params["body"], "y": params["head"],
                           "u": params["head"]}, sections=("x", "y", "u"))
    # storm3_step_flat(p, m, g_old, ...) per dtype group: p read and
    # written in its dtype, m read and written and g_old read in float32,
    # over every client's unpadded elements
    want = 0
    for grp in spec.groups:
        n = sum(leaf.size for leaf in grp.leaves)
        want += m * n * (2 * np.dtype(grp.dtype).itemsize + 3 * 4)
        assert grp.padded >= n
    assert storm_bytes.bytes_per_step(params, m) == want
    # both dtypes are there: bf16 weights and the float32 SSM constants
    assert len(spec.groups) == (2 if family is ssm else 1)
