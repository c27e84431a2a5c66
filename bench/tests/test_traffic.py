"""The batch generator: what the seed fixes, and what every step changes."""
import jax
import numpy as np

from bench import traffic
from bench.tests import tiny


def batches(seed, steps=2):
    t = tiny.traffic()
    fn = traffic.make_batch_fn(t, 256, traffic.seed_key(seed))
    return [jax.device_get(fn(i)) for i in range(steps)]


def test_same_seed_same_batches_and_all_bits_of_the_seed_count():
    a, b = batches(2 ** 33 + 5), batches(2 ** 33 + 5)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    c = batches(5)
    assert not np.array_equal(a[0]["train"]["tokens"], c[0]["train"]["tokens"])


def test_rows_differ_and_labels_are_next_tokens():
    (b0, b1) = batches(1)
    t = b0["train"]["tokens"]
    assert t.shape == (2, 1, 32) and t.dtype == np.int32
    assert 0 <= t.min() and t.max() < 256
    assert not np.array_equal(t[0], t[1])
    assert not np.array_equal(t, b0["val"]["tokens"])
    assert not np.array_equal(t, b1["train"]["tokens"])
    np.testing.assert_array_equal(b0["train"]["labels"][..., :-1], t[..., 1:])
    assert traffic.tokens_per_step(tiny.traffic()) == 2 * 2 * 32
