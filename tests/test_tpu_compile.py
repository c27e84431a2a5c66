"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers one kernel at the width the chip sees —
the storm/momsgd/sgd updates and the int8 pack pair at the flat size of
``mamba2-130m``'s published widths with M=2 clients, flash attention at
head_dim 256 with a 4096 window, the LRU scan at width 2560 — and compiles
it with the TPU compiler for one chip of a ``v5e:2x2`` topology. That is
where Mosaic refuses unaligned blocks and SMEM or VMEM overflows that the
interpreter never sees. Every compiled program must hold the kernel
(``tpu_custom_call``), not a fallback lowering. One more test compiles the
sharded engine's init flatten for a (2, 2) mesh of the same topology.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
The tests skip only where no TPU compiler is installed.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import build_model
from repro.optim import flat

M = 2


# every compile in this file shares the one described topology: keep the
# module on one test worker, so one process loads the TPU library
pytestmark = pytest.mark.xdist_group("tpu_compile")


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) is installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory on one described chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one)


@pytest.fixture(scope="module")
def flat_n():
    """(elements of the M-client parameter buffer, block) at published
    widths — the bf16 group of the fedbioacc x|y|u layout."""
    model = build_model(get_config("mamba2-130m"), dtype=jnp.bfloat16)
    tmpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    spec = flat.make_spec({"x": tmpl["body"], "y": tmpl["head"],
                           "u": tmpl["head"]}, sections=("x", "y", "u"))
    grp = spec.groups[0]
    assert grp.dtype == jnp.bfloat16
    return M * grp.padded, grp.block


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_storm3_step_compiles(shape, flat_n):
    from repro.kernels.storm.kernel import storm3_step_flat
    n, block = flat_n
    t = shape((n // block,), jnp.float32)
    _compiled_text(
        lambda p, m, g, lr, dc: storm3_step_flat(p, m, g, lr, dc, block=block,
                                                 interpret=False),
        shape((n,), jnp.bfloat16), shape((n,), jnp.float32),
        shape((n,), jnp.float32), t, t)


def test_storm3_step_custom_call_keeps_its_name(shape, flat_n):
    """The benchmark finds the STORM launch in a profiler trace by its
    instruction's name, ``%storm3_step_flat*``: the kernel's ``name=``
    holds under a caller's jit and named scope."""
    import re
    from repro.kernels.storm.kernel import storm3_step_flat
    from repro.telemetry import annotate
    n, block = flat_n
    t = shape((n // block,), jnp.float32)

    def train_step(p, m, g, lr, dc):
        with annotate("fed.update"):
            return storm3_step_flat(p, m, g, lr, dc, block=block,
                                    interpret=False)

    text = _compiled_text(train_step, shape((n,), jnp.bfloat16),
                          shape((n,), jnp.float32), shape((n,), jnp.float32),
                          t, t)
    calls = re.findall(r"^\s*(?:ROOT )?%?([\w.-]+) = .* custom-call\(",
                       text, re.M)
    assert calls and all(c.startswith("storm3_step_flat") for c in calls), (
        calls)


def test_storm3_step_small_block_fits_smem(shape, flat_n):
    """A 1024-element block makes ~400k tiles: a whole per-tile table would
    need 1.6 MB of the 1 MiB SMEM, one entry per grid step fits."""
    from repro.kernels.storm.kernel import storm3_step_flat
    n, _ = flat_n
    block = 1024
    n -= n % block
    t = shape((n // block,), jnp.float32)
    _compiled_text(
        lambda p, m, g, lr, dc: storm3_step_flat(p, m, g, lr, dc, block=block,
                                                 interpret=False),
        shape((n,), jnp.bfloat16), shape((n,), jnp.float32),
        shape((n,), jnp.float32), t, t)


def test_momsgd3_step_compiles(shape, flat_n):
    from repro.kernels.storm.kernel import momsgd3_step_flat
    n, block = flat_n
    t = shape((n // block,), jnp.float32)
    _compiled_text(
        lambda p, m, g, lr, b: momsgd3_step_flat(p, m, g, lr, b, block=block,
                                                 interpret=False),
        shape((n,), jnp.bfloat16), shape((n,), jnp.float32),
        shape((n,), jnp.float32), t, t)


def test_sgd3_step_compiles(shape, flat_n):
    from repro.kernels.storm.kernel import sgd3_step_flat
    n, block = flat_n
    _compiled_text(
        lambda p, g, lr: sgd3_step_flat(p, g, lr, block=block,
                                        interpret=False),
        shape((n,), jnp.bfloat16), shape((n,), jnp.float32),
        shape((n // block,), jnp.float32))


def test_quantpack_pair_compiles(shape, flat_n):
    from repro.kernels.storm.quantpack import quantpack_flat, quantunpack_flat
    n, block = flat_n
    _compiled_text(lambda x: quantpack_flat(x, block=block, interpret=False),
                   shape((n,), jnp.float32))
    _compiled_text(
        lambda q, s: quantunpack_flat(q, s, block=block, interpret=False),
        shape((n,), jnp.int8), shape((n // block,), jnp.float32))


def test_flash_attention_compiles(shape):
    from repro.kernels.flash.ops import flash_attention
    q = shape((1, 8192, 8, 256), jnp.bfloat16)
    _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=4096,
                                        softcap=50.0, interpret=False),
        q, q, q)


def test_lru_scan_compiles(shape):
    from repro.kernels.lru.ops import lru_scan
    a = shape((1, 4096, 2560), jnp.float32)
    _compiled_text(lambda a, b: lru_scan(a, b, interpret=False), a, a)


def test_sharded_init_flatten_compiles(topo):
    """The sharded engine's init flattens the M-client trees straight into
    a (data 2, model 2) mesh's shards. Built on one device instead, the
    shard-major interleave is a relayout of the whole buffer: compiling it
    took 424 s and 227 MB of code for a described v5e at these widths."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("data", "model"))
    model = build_model(get_config("mamba2-130m"), dtype=jnp.float32)
    tmpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tree = {"x": tmpl["body"], "y": tmpl["head"], "u": tmpl["head"]}
    spec = flat.make_spec(tree, sections=("x", "y", "u"), shards=2)
    by_client = NamedSharding(mesh, P("data"))
    trees = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (M,) + a.shape, a.dtype, sharding=by_client), tree)
    c = jax.jit(lambda t: flat.flatten_tree(spec, t, batch_dims=1),
                out_shardings=NamedSharding(mesh, P("data", "model"))
                ).lower(trees).compile()
    assert c.memory_analysis().generated_code_size_in_bytes < 32 * 2 ** 20
