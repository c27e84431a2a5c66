"""Pallas kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp ref.py oracles, in interpret mode.

The deterministic sweeps run everywhere; only the property tests need
hypothesis (skipped with a pointer to requirements-dev.txt when absent)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # keep the non-property tests runnable
    given = settings = st = None

needs_hypothesis = pytest.mark.skip(reason="hypothesis not installed "
                                    "(pip install -r requirements-dev.txt)")

from repro.kernels.flash.ops import flash_attention
from repro.kernels.flash.ref import flash_attention_ref
from repro.kernels.lru.ops import lru_scan
from repro.kernels.lru.ref import lru_scan_ref
from repro.kernels.storm.ops import storm_update
from repro.kernels.storm.ref import storm_update_ref

# ---------------------------------------------------------------------------
# storm
# ---------------------------------------------------------------------------

STORM_SHAPES = [(64,), (1000, 33), (3, 5, 7), (70000,)]
STORM_DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", STORM_SHAPES)
@pytest.mark.parametrize("dtype", STORM_DTYPES)
def test_storm_shapes_dtypes(shape, dtype, rng):
    ks = jax.random.split(rng, 4)
    p = jax.random.normal(ks[0], shape).astype(dtype)
    m = jax.random.normal(ks[1], shape)
    gn = jax.random.normal(ks[2], shape)
    go = jax.random.normal(ks[3], shape)
    pn, mn = storm_update({"p": p}, {"p": m}, {"p": gn}, {"p": go}, 0.05, 0.9)
    prn, mrn = storm_update_ref(p, m, gn, go, 0.05, 0.9)
    np.testing.assert_allclose(np.asarray(pn["p"], np.float32),
                               np.asarray(prn, np.float32), rtol=2e-2, atol=1e-4)
    np.testing.assert_allclose(np.asarray(mn["p"]), np.asarray(mrn),
                               rtol=1e-5, atol=1e-6)


if st is not None:
    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(1, 4000), lr=st.floats(0.0, 1.0),
           decay=st.floats(0.0, 1.0), seed=st.integers(0, 2**30))
    def test_storm_property(n, lr, decay, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        p, m, gn, go = (jax.random.normal(k, (n,)) for k in ks)
        pn, mn = storm_update({"x": p}, {"x": m}, {"x": gn}, {"x": go},
                              lr, decay)
        prn, mrn = storm_update_ref(p, m, gn, go, lr, decay)
        np.testing.assert_allclose(pn["x"], prn, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(mn["x"], mrn, rtol=1e-5, atol=1e-6)
else:
    @needs_hypothesis
    def test_storm_property():
        pass


def test_storm_decay_one_is_plain_momentum_carry(rng):
    """decay=1, g_new=g_old ⇒ momentum unchanged (STORM telescoping)."""
    p = jax.random.normal(rng, (256,))
    m = jax.random.normal(jax.random.fold_in(rng, 1), (256,))
    g = jax.random.normal(jax.random.fold_in(rng, 2), (256,))
    _, mn = storm_update({"x": p}, {"x": m}, {"x": g}, {"x": g}, 0.1, 1.0)
    np.testing.assert_allclose(mn["x"], m, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", STORM_DTYPES)
def test_storm3_matches_per_segment_ref(dtype, rng):
    """Triple-sequence kernel: per-block (lr, decay) scalars from SMEM must
    reproduce the per-segment reference exactly."""
    from repro.kernels.storm.kernel import storm3_step_flat, storm3_update_flat
    from repro.kernels.storm.ref import storm3_update_ref
    block, ntiles = 1024, 6
    n = block * ntiles
    ks = jax.random.split(rng, 4)
    p = jax.random.normal(ks[0], (n,)).astype(dtype)
    m, gn, go = (jax.random.normal(k, (n,)) for k in ks[1:])
    lrs = jnp.asarray([0.1, 0.1, 0.2, 0.2, 0.3, 0.3])
    decays = jnp.asarray([0.9, 0.9, 0.8, 0.8, 0.7, 0.7])
    pn, mn = storm3_update_flat(p, m, gn, go, lrs, decays, block=block)
    prn, mrn = storm3_update_ref(p, m, gn, go, lrs, decays, block)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(pn, np.float32),
                               np.asarray(prn, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(mrn),
                               rtol=1e-5, atol=1e-6)
    # half-step variant == full update with g_new = 0
    pn2, mp = storm3_step_flat(p, m, go, lrs, decays, block=block)
    pn0, mn0 = storm3_update_flat(p, m, jnp.zeros_like(gn), go, lrs, decays,
                                  block=block)
    np.testing.assert_array_equal(np.asarray(pn2, np.float32),
                                  np.asarray(pn0, np.float32))
    np.testing.assert_array_equal(np.asarray(mp), np.asarray(mn0))


@pytest.mark.parametrize("dtype", STORM_DTYPES)
def test_momsgd3_matches_ref_and_degenerates_to_sgd(dtype, rng):
    """Heavy-ball companion kernel: m' = β·m + g then p' = p − lr·m' (the
    *updated* momentum — FedAvg ordering); β = 0 is the plain SGD step."""
    from repro.kernels.storm.kernel import momsgd3_step_flat
    from repro.kernels.storm.ref import momsgd3_step_ref
    block, ntiles = 1024, 6
    n = block * ntiles
    ks = jax.random.split(rng, 3)
    p = jax.random.normal(ks[0], (n,)).astype(dtype)
    m, gv = (jax.random.normal(k, (n,)) for k in ks[1:])
    lrs = jnp.asarray([0.1, 0.1, 0.2, 0.2, 0.3, 0.3])
    betas = jnp.asarray([0.9, 0.9, 0.5, 0.5, 0.0, 0.0])
    pn, mn = momsgd3_step_flat(p, m, gv, lrs, betas, block=block)
    prn, mrn = momsgd3_step_ref(p, m, gv, lrs, betas, block)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(pn, np.float32),
                               np.asarray(prn, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(mrn),
                               rtol=1e-5, atol=1e-6)
    # β = 0 tiles: m operand irrelevant, p' = p − lr·g exactly
    pn0, mn0 = momsgd3_step_flat(p, jnp.zeros_like(m), gv, lrs, betas,
                                 block=block)
    sl = slice(4 * block, n)
    np.testing.assert_array_equal(np.asarray(pn[sl], np.float32),
                                  np.asarray(pn0[sl], np.float32))
    np.testing.assert_array_equal(np.asarray(mn0[sl]), np.asarray(gv[sl]))
    # the dedicated momentum-less kernel == heavy-ball at β = 0 everywhere
    from repro.kernels.storm.kernel import sgd3_step_flat
    from repro.kernels.storm.ref import sgd3_step_ref
    ps = sgd3_step_flat(p, gv, lrs, block=block)
    np.testing.assert_array_equal(np.asarray(ps[sl], np.float32),
                                  np.asarray(pn0[sl], np.float32))
    np.testing.assert_allclose(np.asarray(ps, np.float32),
                               np.asarray(sgd3_step_ref(p, gv, lrs, block),
                                          np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("kernel", ["storm3_step", "quantpack"])
def test_compiled_kernel_refuses_block_below_tile(kernel):
    """A compiled launch over blocks that are not whole 1024-element tiles
    is refused with a named bound before Mosaic sees it; the interpreter
    (and hence every CPU test) still takes any block."""
    from repro.kernels.storm.kernel import storm3_step_flat
    from repro.kernels.storm.quantpack import quantpack_flat
    block, n = 256, 256 * 4
    x = jnp.ones((n,), jnp.float32)
    t = jnp.ones((n // block,), jnp.float32)
    call = {"storm3_step": lambda i: storm3_step_flat(
                x, x, x, t, t, block=block, interpret=i),
            "quantpack": lambda i: quantpack_flat(x, block=block,
                                                  interpret=i)}[kernel]
    with pytest.raises(ValueError, match="multiple of 1024"):
        call(False)
    call(True)


# The triple-sequence kernels stream an [R, N] buffer in its own layout as
# (R, block) blocks with one scalar per (tile, row).  Each must give the
# bits of the same kernel on the buffer reshaped to [R·N] (the launch it
# replaces) and of its jnp lowering; a gated row (lr 0, decay|β 1, zeroed
# gradients) must come out frozen bit-exact.

RANK2_KERNELS = {
    # name: (kernel, the operands after p, how many tables)
    "storm3_step": ("storm3_step_flat", ("m", "g"), 2),
    "storm3_update": ("storm3_update_flat", ("m", "g", "g"), 2),
    "momsgd3": ("momsgd3_step_flat", ("m", "g"), 2),
    "sgd3": ("sgd3_step_flat", ("g",), 1),
}


def _rank2_case(kernel, rows, block, data_tiles, gate, key):
    """(kernel fn, jnp fn, [R, N] operands, [T, R] tables, frozen rows):
    ``data_tiles`` tiles of random data and one zero padding tile; with
    ``gate`` the last row is a non-participant's (lr 0, decay|β 1, zero
    gradients), as ``flat._gate`` and ``flat.mask_buffers`` make it."""
    from repro.kernels.storm import kernel as K
    name, kinds, n_tables = RANK2_KERNELS[kernel]
    tiles = data_tiles + 1
    n = tiles * block
    ks = jax.random.split(key, len(kinds) + 2)
    live = (jnp.arange(n) < data_tiles * block)[None, :]
    rand = lambda k, dt=jnp.float32: jnp.where(
        live, jax.random.normal(k, (rows, n)), 0).astype(dt)
    frozen = jnp.zeros((rows,), bool).at[-1].set(gate)
    ops = [rand(ks[0], jnp.bfloat16)]
    for k, kind in zip(ks[1:], kinds):
        x = rand(k)
        ops.append(jnp.where(frozen[:, None], 0.0, x) if kind == "g" else x)
    lrs = jax.random.uniform(ks[-1], (tiles, rows), minval=0.01, maxval=0.5)
    tables = [jnp.where(frozen[None, :], 0.0, lrs),
              jnp.where(frozen[None, :], 1.0, 1.0 - lrs)][:n_tables]
    return (getattr(K, name), getattr(K, name + "_jnp"), ops, tables,
            np.asarray(frozen))


@pytest.mark.parametrize("rows", [1, 2, 4])
@pytest.mark.parametrize("kernel", sorted(RANK2_KERNELS))
def test_rank2_kernel_matches_1d_launch_and_jnp(kernel, rows, rng):
    block = 256
    fn, fn_jnp, ops, tables, frozen = _rank2_case(
        kernel, rows, block, 3, rows > 1, jax.random.fold_in(rng, rows))
    outs = fn(*ops, *tables, block=block, interpret=True)
    outs = outs if isinstance(outs, tuple) else (outs,)
    flat_outs = fn(*[o.reshape(-1) for o in ops],
                   *[t.T.reshape(-1) for t in tables],
                   block=block, interpret=True)
    flat_outs = flat_outs if isinstance(flat_outs, tuple) else (flat_outs,)
    ref = fn_jnp(*ops, *tables, block=block)
    ref = ref if isinstance(ref, tuple) else (ref,)
    bits = lambda x: np.asarray(x).view(np.uint8)
    for o, fo, r, src in zip(outs, flat_outs, ref, ops):
        assert o.shape == src.shape and o.dtype == src.dtype
        np.testing.assert_array_equal(bits(o), bits(fo.reshape(o.shape)))
        np.testing.assert_array_equal(bits(o), bits(r))
        np.testing.assert_array_equal(bits(o[:, -block:]),
                                      bits(jnp.zeros_like(o[:, -block:])))
        np.testing.assert_array_equal(bits(o[frozen]), bits(src[frozen]))
    assert not np.array_equal(bits(outs[0]), bits(ops[0]))


def test_rank2_kernel_steps_over_row_blocks(rng):
    """16 rows of 64 Ki f32 tiles outgrow the VMEM budget: the launch steps
    over row blocks of 8 and still gives the bits of one row at a time."""
    from repro.kernels.storm import kernel as K
    block, rows = 64 * 1024, 16
    assert K._row_block(rows, block, 16)[0] == K.ROW_BLOCK
    fn, _, ops, tables, frozen = _rank2_case(
        "storm3_step", rows, block, 1, True, rng)
    outs = fn(*ops, *tables, block=block, interpret=True)
    for r in (0, 9, rows - 1):
        one = fn(*[o[r] for o in ops], *[t[:, r] for t in tables],
                 block=block, interpret=True)
        for o, x in zip(outs, one):
            np.testing.assert_array_equal(np.asarray(o[r]).view(np.uint8),
                                          np.asarray(x).view(np.uint8))
    np.testing.assert_array_equal(np.asarray(outs[1][-1]),
                                  np.asarray(ops[1][-1]))


def test_storm_partial_step_rank2_under_shard_ctx(rng):
    """The substrate's launch on [M, N] buffers, gated: plain, under a
    one-device ``shard_map`` and off the kernel (jnp) all agree bit for
    bit, and the non-participant's rows stay frozen."""
    import numpy as onp
    from jax.sharding import Mesh
    from repro.optim import flat
    tree = {"x": {"w": jnp.zeros((40, 9)), "b": jnp.zeros((7,), jnp.bfloat16)},
            "y": jnp.zeros((30,)), "u": jnp.zeros((30,))}
    spec = flat.make_spec(tree, sections=("x", "y", "u"), block=128)
    M = 3
    ks = iter(jax.random.split(rng, 8))
    rand = lambda dt: tuple(jax.random.normal(next(ks), (M, g.padded)).astype(
        g.dtype if dt is None else dt) for g in spec.groups)
    mask = jnp.asarray([1.0, 0.0, 1.0])
    v, m = rand(None), rand(jnp.float32)
    g = flat.mask_buffers(rand(jnp.float32), mask)
    args = (spec, v, m, g, (0.1, 0.2, 0.3), (0.9, 0.8, 0.7))
    ctx = flat.make_shard_ctx(
        Mesh(onp.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model")))
    plain = flat.storm_partial_step(*args, mask=mask, interpret=True)
    sharded = flat.storm_partial_step(*args, mask=mask, interpret=True,
                                      shard=ctx)
    lowered = flat.storm_partial_step(*args, mask=mask)
    for outs in (sharded, lowered):
        for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(outs)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                          np.asarray(b).view(np.uint8))
    (vn, mn) = plain
    for a, b in zip(vn + mn, v + m):
        np.testing.assert_array_equal(np.asarray(a[1]).view(np.uint8),
                                      np.asarray(b[1]).view(np.uint8))
        assert not np.array_equal(np.asarray(a[0]), np.asarray(b[0]))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, S, H, D, causal, window, softcap)
    (2, 256, 4, 64, True, 0, 0.0),
    (1, 256, 2, 32, True, 64, 0.0),
    (2, 128, 2, 64, False, 0, 0.0),
    (1, 384, 2, 64, True, 128, 50.0),
    (1, 130, 1, 16, True, 32, 0.0),      # padding path
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(case, dtype, rng):
    B, S, H, D, causal, window, cap = case
    ks = jax.random.split(rng, 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)).astype(dtype) for kk in ks)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          interpret=True)

    def to_bh(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, S, D)

    ref = flash_attention_ref(to_bh(q), to_bh(k), to_bh(v), causal=causal,
                              window=window, softcap=cap)
    ref = jnp.transpose(ref.reshape(B, H, S, D), (0, 2, 1, 3))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_matches_model_attention(rng):
    """The kernel path must agree with the model's dense attention layer."""
    from repro.config import ModelConfig
    from repro.models.layers import attention, attn_init
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                      window_size=32)
    params = attn_init(rng, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 128, 64))
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    dense, _ = attention(params, x, cfg, window=32, positions=pos)
    flash, _ = attention(params, x, cfg, window=32, positions=pos,
                         use_flash=True)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(flash),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# lru scan
# ---------------------------------------------------------------------------

LRU_SHAPES = [(2, 256, 64), (1, 100, 33), (3, 128, 512), (1, 8, 1)]


@pytest.mark.parametrize("shape", LRU_SHAPES)
def test_lru_vs_ref(shape, rng):
    B, S, C = shape
    ks = jax.random.split(rng, 3)
    a = jax.random.uniform(ks[0], shape, minval=0.7, maxval=0.999)
    b = 0.1 * jax.random.normal(ks[1], shape)
    h0 = jax.random.normal(ks[2], (B, C))
    np.testing.assert_allclose(lru_scan(a, b, h0, interpret=True),
                               lru_scan_ref(a, b, h0),
                               atol=2e-6, rtol=2e-5)
    np.testing.assert_allclose(lru_scan(a, b, interpret=True),
                               lru_scan_ref(a, b),
                               atol=2e-6, rtol=2e-5)


if st is not None:
    @settings(max_examples=10, deadline=None)
    @given(B=st.integers(1, 3), S=st.integers(1, 200), C=st.integers(1, 80),
           seed=st.integers(0, 2**30))
    def test_lru_property(B, S, C, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        a = jax.random.uniform(ks[0], (B, S, C), minval=0.0, maxval=1.0)
        b = jax.random.normal(ks[1], (B, S, C))
        got = lru_scan(a, b, interpret=True)
        # sequential reference
        h = np.zeros((B, C), np.float32)
        want = np.zeros((B, S, C), np.float32)
        an, bn = np.asarray(a), np.asarray(b)
        for t in range(S):
            h = an[:, t] * h + bn[:, t]
            want[:, t] = h
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)
else:
    @needs_hypothesis
    def test_lru_property():
        pass


def test_lru_matches_griffin_scan(rng):
    """Kernel path must agree with the model's associative-scan path."""
    from repro.models.griffin import linear_scan
    a = jax.random.uniform(rng, (2, 64, 32), minval=0.8, maxval=0.99)
    b = 0.1 * jax.random.normal(jax.random.fold_in(rng, 1), (2, 64, 32))
    np.testing.assert_allclose(
        np.asarray(linear_scan(a, b, use_kernel=True)),
        np.asarray(linear_scan(a, b, use_kernel=False)), atol=1e-5, rtol=1e-5)
