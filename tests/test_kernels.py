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
