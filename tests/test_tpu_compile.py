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
import numpy as np
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
def flat_spec():
    """The fedbioacc x|y|u layout of ``mamba2-130m`` at published widths:
    the bf16 group first, then one tile of f32 leaves."""
    model = build_model(get_config("mamba2-130m"), dtype=jnp.bfloat16)
    tmpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    spec = flat.make_spec({"x": tmpl["body"], "y": tmpl["head"],
                           "u": tmpl["head"]}, sections=("x", "y", "u"))
    assert spec.groups[0].dtype == jnp.bfloat16
    return spec


@pytest.fixture(scope="module")
def flat_n(flat_spec):
    """(elements of the M-client parameter buffer, block) at published
    widths — the bf16 group of the fedbioacc x|y|u layout."""
    grp = flat_spec.groups[0]
    return M * grp.padded, grp.block


def _partial_step(spec):
    """The real step's gated STORM launches on [M, N] client buffers, one
    per dtype group: variables in the group's dtype, f32 momenta and
    old-iterate gradients, a participation mask."""
    k = len(spec.groups)

    def step(*args):
        v, m, g, (mask,) = (args[i * k:(i + 1) * k] for i in range(4))
        return flat.storm_partial_step(spec, v, m, g, (0.02, 0.05, 0.05),
                                       (0.9, 0.9, 0.9), mask=mask,
                                       interpret=False)
    return step


def _client_buffers(shape, spec):
    """(variables..., momenta..., gradients..., mask) for :func:`_partial_step`."""
    v = [shape((M, g.padded), g.dtype) for g in spec.groups]
    f32 = [shape((M, g.padded), jnp.float32) for g in spec.groups]
    return (*v, *f32, *f32, shape((M,), jnp.float32))


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


def test_storm3_step_custom_call_keeps_its_name(shape, flat_n, flat_spec):
    """The benchmark finds the STORM launch in a profiler trace by its
    instruction's name, ``%storm3_step_flat*``: the kernel's ``name=``
    holds under a caller's jit and named scope, for the 1-D launch and for
    the substrate's launch on [M, N] client buffers."""
    import re
    from repro.kernels.storm.kernel import storm3_step_flat
    from repro.telemetry import annotate
    n, block = flat_n
    t = shape((n // block,), jnp.float32)

    def train_step(p, m, g, lr, dc):
        with annotate("fed.update"):
            return storm3_step_flat(p, m, g, lr, dc, block=block,
                                    interpret=False)

    def client_step(*args):
        with annotate("fed.update"):
            return _partial_step(flat_spec)(*args)

    for text in (
            _compiled_text(train_step, shape((n,), jnp.bfloat16),
                           shape((n,), jnp.float32),
                           shape((n,), jnp.float32), t, t),
            _compiled_text(client_step, *_client_buffers(shape, flat_spec))):
        calls = re.findall(r"^\s*(?:ROOT )?%?([\w.-]+) = .* custom-call\(",
                           text, re.M)
        assert calls and all(c.startswith("storm3_step_flat")
                             for c in calls), calls


def test_storm_partial_step_reads_client_rows_in_place(shape, flat_spec):
    """At the real flat group (M=2 rows of 206 M bf16 elements) the gated
    launch takes the [M, N] buffers in their own 2-D layout and updates the
    donated ones in place: no loop, no buffer-sized copy or reshape around
    the kernel, and no buffer-sized temporary (a reshape to 1-D relays every
    buffer through the 1-D tiling, 4.13 GB of temporaries at this size)."""
    import re
    n = flat_spec.groups[0].padded
    args = _client_buffers(shape, flat_spec)
    # the train step donates its state, as here the variables and momenta
    c = jax.jit(_partial_step(flat_spec),
                donate_argnums=range(2 * len(flat_spec.groups))).lower(
        *args).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"\bwhile\(", text)
    for dims, op in re.findall(
            r"= \w+\[([\d,]*)\]\S* (copy|reshape)\(", text):
        assert np.prod([int(d) for d in dims.split(",") if d]) < n, (dims, op)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == len(flat_spec.groups)
    for grp in flat_spec.groups:
        dt = "bf16" if grp.dtype == jnp.bfloat16 else "f32"
        v, m = f"{dt}[{M},{grp.padded}]{{1,0", f"f32[{M},{grp.padded}]{{1,0"
        # outputs and operands both in the buffers' own rank-2 layout
        assert any(f"= ({v}" in ln and f", {m}" in ln and v + "}" in ln
                   for ln in calls), (v, calls)
    assert c.memory_analysis().temp_size_in_bytes < M * n * 2


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
