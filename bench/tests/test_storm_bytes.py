"""The STORM byte model against the operands the kernel really streams."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import storm_bytes
from bench.reference import moe, ssm
from bench.tests import tiny


def kernel_operand_bytes(params, m, sections):
    """``storm3_step_flat(p, m, g_old, ...)`` per dtype group of the flat
    state the program lays out for ``sections``: p read and written in its
    dtype, m read and written and g_old read in float32, over every
    client's unpadded elements; and the number of groups."""
    from repro.optim import flat
    spec = flat.make_spec({s: params[storm_bytes.PARAMS[s]]
                           for s in sections}, sections=sections)
    want = 0
    for grp in spec.groups:
        n = sum(leaf.size for leaf in grp.leaves)
        want += m * n * (2 * np.dtype(grp.dtype).itemsize + 3 * 4)
        assert grp.padded >= n
    return want, len(spec.groups)


@pytest.mark.parametrize("config,family", [(tiny.SSM, ssm), (tiny.MOE, moe)])
def test_bytes_match_kernel_operands(config, family):
    sizes, m = config["sizes"], 2
    params = jax.eval_shape(lambda k: family.init(k, sizes, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    want, groups = kernel_operand_bytes(params, m, ("x", "y", "u"))
    assert storm_bytes.bytes_per_step(params, m, ("x", "y", "u")) == want
    # both dtypes are there: bf16 weights and the float32 SSM constants
    assert groups == (2 if family is ssm else 1)


def test_local_sections_bytes_match_kernel_operands():
    """FedBiOAcc-Local's x|y state, every one of four clients streamed
    (a gated launch reads and writes the rows it freezes)."""
    params = jax.eval_shape(
        lambda k: ssm.init(k, tiny.SSM["sizes"], jnp.bfloat16),
        jax.random.PRNGKey(0))
    want, _ = kernel_operand_bytes(params, 4, ("x", "y"))
    assert storm_bytes.bytes_per_step(params, 4, ("x", "y")) == want
    assert want < storm_bytes.bytes_per_step(params, 4, ("x", "y", "u"))
