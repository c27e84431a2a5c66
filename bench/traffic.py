"""The benchmark's batch generator: heterogeneous per-client token streams.

Each client draws its tokens from its own unigram distribution, itself drawn
from a Dirichlet prior over ``buckets`` contiguous buckets of the vocabulary
(a lower ``dirichlet_alpha`` makes the clients more different).  A step's
batch has a ``train`` stream (read by the lower objective g) and a ``val``
stream (read by the upper objective f), each ``[clients, per_client,
seq_len]`` tokens with next-token labels.  The generator is one jitted
program of the step index; the program under test only ever receives its
output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``: all of its bits count (a plain
    ``PRNGKey`` keeps only the low 32 without 64-bit mode)."""
    seed = int(seed)
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def make_batch_fn(traffic: dict, vocab: int, key):
    """``batch(step) -> {"train": {...}, "val": {...}}`` for one traffic mix
    (its parameters as in ``bench/traffic/*.json``), jitted.  ``key`` fixes
    the clients' unigram distributions and every step's tokens."""
    m, b, s = traffic["clients"], traffic["per_client"], traffic["seq_len"]
    buckets = min(vocab, int(traffic["buckets"]))
    width = max(vocab // buckets, 1)
    k_mix, k_steps = jax.random.split(key)
    g = jax.random.gamma(k_mix, traffic["dirichlet_alpha"], (m, buckets))
    logits = jnp.log(g / jnp.sum(g, axis=1, keepdims=True) + 1e-9)

    def stream(k):
        def one(kc, lg):
            kb, ko = jax.random.split(kc)
            bucket = jax.random.categorical(kb, lg, shape=(b, s))
            off = jax.random.randint(ko, (b, s), 0, width)
            return jnp.minimum(bucket * width + off, vocab - 1).astype(jnp.int32)
        toks = jax.vmap(one)(jax.random.split(k, m), logits)
        return {"tokens": toks,
                "labels": jnp.concatenate([toks[..., 1:], toks[..., :1]], -1)}

    @jax.jit
    def batch(step):
        kt, kv = jax.random.split(jax.random.fold_in(k_steps, step))
        return {"train": stream(kt), "val": stream(kv)}

    return batch


def participants(traffic: dict) -> int:
    """Clients whose work a step requires: every client under the job's
    ``full`` sampler, ``clients_per_round`` of them under ``uniform``.  The
    program may run an oracle on every client's rows and discard the
    others'; that work is not required."""
    p = traffic["experiment"]["participation"]
    if p["sampler"] == "full":
        return traffic["clients"]
    if p["sampler"] == "uniform":
        return p["clients_per_round"] or traffic["clients"]
    raise SystemExit(f"bench: no count of participants for the "
                     f"{p['sampler']!r} sampler")


def tokens_per_step(traffic: dict) -> int:
    """Tokens one step requires: both streams of every participant."""
    return 2 * participants(traffic) * traffic["per_client"] \
        * traffic["seq_len"]
