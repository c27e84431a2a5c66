"""The plain references against the program at a tiny size on the CPU:
the same weights from the same key, the same loss, and — with float32
parameters, where rounding cannot hide a difference in the mathematics —
the same first three FedBiOAcc steps."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cell as cells
from bench import check
from bench import run as R
from bench.calibrate import readings
from bench.reference import common, moe, ssm
from bench.tests import tiny
from bench.traffic import make_batch_fn

CASES = [(tiny.SSM, ssm), (tiny.MOE, moe)]


def program_model(config, dtype):
    from repro.configs import get_config
    from repro.models import build_model
    with cells.registered(config) as name:
        return build_model(get_config(name), dtype=dtype)


@pytest.mark.parametrize("config,family", CASES)
def test_same_weights_from_the_same_key(config, family):
    key = jax.random.PRNGKey(3)
    prog = program_model(config, jnp.bfloat16).init(key)
    ref = family.init(key, config["sizes"], jnp.bfloat16)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("config,family", CASES)
def test_same_loss(config, family):
    model = program_model(config, jnp.float32)
    params = model.init(jax.random.PRNGKey(1))
    batch = make_batch_fn(tiny.traffic(), config["sizes"]["vocab_size"],
                          jax.random.PRNGKey(2))(0)["train"]
    row = jax.tree.map(lambda a: a[0], batch)
    want = model.loss(params, row)[0]
    got = family.loss(params, row, config["sizes"], common.Arith())
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


@pytest.mark.parametrize("config,family", CASES)
def test_same_steps_in_float32(config, family):
    config = dict(config, param_dtype="float32")
    c = cells.Cell("t", 1, config, tiny.traffic(), {}, {})
    run = R.build(c)
    prog, finite, batch = readings(c, run, 5)
    nums = check.numbers(prog, R.reference(c, batch, 5))
    assert finite
    for name, (value, leaf) in nums.items():
        assert value < 2e-5, (name, value, leaf)


@pytest.mark.parametrize("operands_only", [False, True])
def test_float8_controls_round_what_they_state(operands_only):
    a = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    ar = common.Arith(control=True, operands_only=operands_only)
    got = np.asarray(ar.mm(a, b))
    inputs = jnp.matmul(common.fp8(a), common.fp8(b),
                        precision=common.HIGHEST)
    want = inputs if operands_only else common.fp8(inputs)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert not np.array_equal(np.asarray(inputs), np.asarray(a @ b))
    assert np.array_equal(np.asarray(ar.q(a)), np.asarray(a)) == operands_only
