"""Benchmark harness — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--json] [--events P]

Benchmarks (paper artifact → benchmark):
  * Table 1 (communication / oracle complexities)    → bench_table1_complexity
  * Fig. "Federated Data Cleaning"                   → bench_data_cleaning
  * Fig. "Hyper-Representation"                      → bench_hyperrep
  * Linear-speedup claim (Thm 1/2)                   → bench_linear_speedup
  * Kernel hot-spots (DESIGN §6)                     → bench_kernels
  * §Roofline summary (from the dry-run artifacts)   → bench_roofline_summary

Output: ``name,us_per_call,derived`` CSV rows (derived = the benchmark's
headline metric). ``--json`` additionally writes ``BENCH_kernels.json`` at
the repo root — the machine-readable kernel perf trajectory (fused
triple-sequence STORM vs the 9-pass tree-map chain, with the bytes-moved
model behind each number).  ``--events PATH`` mirrors every result row
(and the measured-run spans) into a ``repro.telemetry`` event stream.

The Experiment-sweep benches (participation, fault tolerance, compressed
comm) all measure through :func:`repro.telemetry.measure_run` — the one
warmed, donation-aware timing path shared with the event stream.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FederatedConfig
from repro.core import (data_cleaning_problem, hyperrep_problem,
                        make_algorithm, quadratic_problem)
from repro.core.problems import fair_federated_problem

ROWS = []
KERNEL_JSON = {}          # machine-readable kernel results (--json)
EVENTS_LOG = None         # repro.telemetry EventLog mirror (--events)


def emit(name: str, us_per_call: float, derived: str):
    ROWS.append(f"{name},{us_per_call:.1f},{derived}")
    print(ROWS[-1], flush=True)
    if EVENTS_LOG is not None:
        EVENTS_LOG.emit("bench", name=name,
                        us_per_step=round(us_per_call, 1), derived=derived)


def _run_rounds(prob, algo, rounds, *, local_steps=4, lr_x=0.03, lr_y=0.1,
                lr_u=0.1, track=None, **kw):
    cfg = FederatedConfig(algorithm=algo, num_clients=prob.num_clients,
                          local_steps=local_steps, lr_x=lr_x, lr_y=lr_y,
                          lr_u=lr_u, neumann_q=10, neumann_tau=0.15, **kw)
    alg = make_algorithm(prob, cfg)
    state = alg.init(jax.random.PRNGKey(1))
    rnd = jax.jit(alg.round)
    key = jax.random.PRNGKey(2)
    state, _ = rnd(state, key)                       # compile
    t0 = time.time()
    traj = []
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        state, _ = rnd(state, sub)
        if track is not None:
            traj.append(track(alg, state))
    us = (time.time() - t0) / rounds * 1e6
    return alg, state, traj, us


# ---------------------------------------------------------------------------
# Table 1: communication complexity / oracle counts to reach epsilon
# ---------------------------------------------------------------------------

# analytic oracle calls per ROUND (per client):  Gc(f), Gc(g), Jv, Hv
_ORACLES_PER_ROUND = {
    "fedbio": lambda I: (2 * I, I, I, I),
    "fedbioacc": lambda I: (4 * I, 2 * I, 2 * I, 2 * I),
    "fednest": lambda I: (2 + I, I, 1, I),
    "commfedbio": lambda I: (2 * I, I, I, 10 * I),
    "stocbio": lambda I: (2, I, 1, 10),
    "mrbo": lambda I: (4, 2, 2, 20),
}


def bench_table1_complexity(fast: bool):
    prob = quadratic_problem(jax.random.PRNGKey(4), num_clients=8, dx=10,
                             dy=10, noise=0.3, hetero=1.0)
    g0 = float(jnp.linalg.norm(prob.exact_hypergrad(jnp.zeros(10))))
    eps = 0.25 * g0
    rounds = 60 if fast else 200

    def track(alg, state):
        return float(jnp.linalg.norm(prob.exact_hypergrad(alg.mean_x(state))))

    for algo in ("fedbio", "fedbioacc", "fednest", "commfedbio",
                 "stocbio", "mrbo"):
        alg, state, traj, us = _run_rounds(prob, algo, rounds, track=track)
        hit = next((i + 1 for i, g in enumerate(traj) if g < eps), None)
        floats = None if hit is None else hit * alg.comm_floats
        oc = _ORACLES_PER_ROUND[algo](4)
        derived = (f"rounds_to_eps={hit};floats_to_eps={floats};"
                   f"final_grad={traj[-1]:.4f};oracles/round Gf={oc[0]} "
                   f"Gg={oc[1]} Jv={oc[2]} Hv={oc[3]}")
        emit(f"table1/{algo}", us, derived)


# ---------------------------------------------------------------------------
# Figure: federated data cleaning
# ---------------------------------------------------------------------------

def bench_data_cleaning(fast: bool):
    prob = data_cleaning_problem(jax.random.PRNGKey(1), num_clients=8,
                                 n_train=256, corrupt_frac=0.4)
    data = prob.data
    rounds = 60 if fast else 200
    mask = np.asarray(data["corrupt_mask"])

    def auc(x_weights):
        """AUC of (-weight) as a corruption detector (higher = cleaner)."""
        w = np.asarray(x_weights)
        pos, neg = -w[mask], -w[~mask]
        return float((pos[:, None] > neg[None, :]).mean())

    for algo in ("fedbio", "fedbioacc"):
        alg, state, _, us = _run_rounds(prob, algo, rounds, lr_x=0.3,
                                        lr_y=0.3, lr_u=0.3)
        x = np.asarray(alg.mean_x(state))
        w = 1.0 / (1.0 + np.exp(-x))
        emit(f"cleaning/{algo}", us,
             f"auc_corrupt_detection={auc(x):.3f};"
             f"mean_w_clean={w[~mask].mean():.3f};"
             f"mean_w_corrupt={w[mask].mean():.3f}")


# ---------------------------------------------------------------------------
# Figure: hyper-representation learning
# ---------------------------------------------------------------------------

def bench_hyperrep(fast: bool):
    prob = hyperrep_problem(jax.random.PRNGKey(2), num_clients=8)
    rounds = 60 if fast else 200

    def val_loss(alg, state):
        x = alg.mean_x(state)
        y = jax.tree.map(lambda v: jnp.mean(v, 0), state.y)
        b = jax.tree.map(lambda v: v[0],
                         prob.sample_batches(jax.random.PRNGKey(9)))
        return float(prob.f(x, y, b))

    for algo in ("fedbio", "fedbioacc", "fedbio_local", "fedbioacc_local",
                 "fednest"):
        alg, state, traj, us = _run_rounds(prob, algo, rounds, lr_x=0.1,
                                           lr_y=0.2, lr_u=0.2, track=val_loss)
        emit(f"hyperrep/{algo}", us,
             f"val0={traj[0]:.3f};valT={traj[-1]:.3f};"
             f"comm_floats_per_round={alg.comm_floats}")


# ---------------------------------------------------------------------------
# Fair Federated Learning (paper §5 conclusion)
# ---------------------------------------------------------------------------

def bench_fair_fl(fast: bool):
    import numpy as np
    prob = fair_federated_problem(jax.random.PRNGKey(0), num_clients=8,
                                  hard_clients=2)
    rounds = 60 if fast else 200

    def run(lr_x):
        alg, state, _, us = _run_rounds(prob, "fedbio", rounds, lr_x=lr_x,
                                        lr_y=0.5, lr_u=0.3)
        lam = alg.mean_x(state)
        y = jax.tree.map(lambda v: jnp.mean(v, 0), state.y)
        return np.asarray(prob.client_val_losses(lam, y)), lam, us

    losses_u, _, us_u = run(0.0)          # uniform baseline
    losses_f, lam, us_f = run(2.0)        # learned fair weights
    w = np.asarray(jax.nn.softmax(lam))
    emit("fairfl/uniform", us_u,
         f"worst_client={losses_u.max():.3f};mean={losses_u.mean():.3f}")
    emit("fairfl/bilevel", us_f,
         f"worst_client={losses_f.max():.3f};mean={losses_f.mean():.3f};"
         f"w_minority={w[:2].mean():.3f};w_majority={w[2:].mean():.3f}")


# ---------------------------------------------------------------------------
# Linear speed-up in M (Theorems 1/2)
# ---------------------------------------------------------------------------

def bench_linear_speedup(fast: bool):
    rounds = 60 if fast else 150
    tails = {}
    for M in (2, 4, 8, 16):
        prob = quadratic_problem(jax.random.PRNGKey(0), num_clients=M,
                                 dx=10, dy=10, noise=1.2, hetero=0.6)

        def track(alg, state, prob=prob):
            return float(jnp.linalg.norm(
                prob.exact_hypergrad(alg.mean_x(state))))

        _, _, traj, us = _run_rounds(prob, "fedbio", rounds, track=track)
        tails[M] = sum(traj[-max(rounds // 5, 1):]) / max(rounds // 5, 1)
        emit(f"speedup/M={M}", us, f"tail_grad_norm={tails[M]:.4f}")
    emit("speedup/ratio_M2_over_M16", 0.0,
         f"{tails[2] / tails[16]:.2f} (linear speedup => >1)")


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _timeit_us(fn, n):
    """Warmed, device-synchronized mean wall time per call in µs — shared by
    the substrate benches so their recorded numbers stay methodologically
    comparable."""
    from repro.telemetry import phase
    r = fn()
    jax.block_until_ready(r)
    with phase("bench/timeit", EVENTS_LOG, calls=n):
        t0 = time.perf_counter()
        for _ in range(n):
            r = fn()
        jax.block_until_ready(r)
        us = (time.perf_counter() - t0) / n * 1e6
    return us


def _timeit_us_donated(jitted, make_args, n, *, warm=True):
    """Donation-aware timing: the engine jits its train step with buffer
    donation (``donate_argnums=(0,)``), so the comm benches measure that
    convention — the in-place sliced reduction then writes only the
    communicated runs.  Fresh argument copies are pre-made outside the timed
    region (each call consumes its donated buffers).  ``warm=False`` skips
    the compile/warm-up execution (callers that already warmed, e.g. the
    interleaved rounds of :func:`_timeit_us_ab`)."""
    from repro.telemetry import phase
    if warm:
        r = jitted(*make_args())
        jax.block_until_ready(r)
    arg_sets = [make_args() for _ in range(n)]
    jax.block_until_ready(arg_sets)
    with phase("bench/timeit_donated", EVENTS_LOG, calls=n):
        t0 = time.perf_counter()
        for a in arg_sets:
            r = jitted(*a)
        jax.block_until_ready(r)
        us = (time.perf_counter() - t0) / n * 1e6
    return us


def _timeit_us_ab(pairs, n, rounds=4):
    """Interleaved A/B timing for comparison entries: measure each
    contender's n-call block once per round, alternating, and report each
    path's MINIMUM block mean.  Back-to-back single blocks let machine-state
    drift (turbo, page cache, background load) land entirely on one side and
    flip the recorded ratio; interleaving + min removes the drift bias
    without favouring either path.  ``pairs``: [(jitted, make_args), ...]
    with donated-argument conventions as in :func:`_timeit_us_donated`."""
    for jitted, make_args in pairs:          # compile + warm outside timing
        jax.block_until_ready(jitted(*make_args()))
    best = [float("inf")] * len(pairs)
    for _ in range(rounds):
        for i, (jitted, make_args) in enumerate(pairs):
            best[i] = min(best[i], _timeit_us_donated(jitted, make_args, n,
                                                      warm=False))
    return best


def bench_kernels(fast: bool):
    from repro.kernels.flash.ops import flash_attention
    from repro.kernels.flash.ref import flash_attention_ref
    from repro.kernels.lru.ops import lru_scan
    from repro.kernels.lru.ref import lru_scan_ref
    from repro.kernels.storm.ops import storm_update
    from repro.kernels.storm.ref import storm_update_ref

    key = jax.random.PRNGKey(0)

    def timeit(fn, n=3):
        fn()
        t0 = time.time()
        for _ in range(n):
            r = fn()
        jax.block_until_ready(r)
        return (time.time() - t0) / n * 1e6

    n = 1 << 16
    p, m, gn, go = (jax.random.normal(jax.random.fold_in(key, i), (n,))
                    for i in range(4))
    t_k = timeit(lambda: storm_update({"x": p}, {"x": m}, {"x": gn},
                                      {"x": go}, 0.1, 0.9))
    t_r = timeit(lambda: jax.jit(storm_update_ref)(p, m, gn, go, 0.1, 0.9))
    emit("kernel/storm", t_k, f"ref_us={t_r:.0f};interpret_mode=True;n={n}")
    KERNEL_JSON["storm_single"] = {"n_elements": n, "kernel_us": round(t_k, 1),
                                   "ref_us": round(t_r, 1),
                                   "backend": jax.default_backend()}

    B, S, H, D = 1, 256, 2, 64
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D))
               for i in range(3))
    t_k = timeit(lambda: flash_attention(q, k, v, causal=True, window=64))

    def ref():
        to = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, S, D)
        return flash_attention_ref(to(q), to(k), to(v), causal=True, window=64)

    t_r = timeit(lambda: jax.jit(ref)())
    emit("kernel/flash", t_k, f"ref_us={t_r:.0f};interpret_mode=True;"
                              f"shape={B}x{S}x{H}x{D};window=64")

    a = jax.random.uniform(key, (2, 256, 128), minval=0.8, maxval=0.99)
    b = 0.1 * jax.random.normal(key, (2, 256, 128))
    t_k = timeit(lambda: lru_scan(a, b))
    t_r = timeit(lambda: jax.jit(lru_scan_ref)(a, b))
    emit("kernel/lru", t_k, f"ref_us={t_r:.0f};interpret_mode=True;"
                            f"shape=2x256x128")

    bench_storm_triple(fast)
    bench_storm_local(fast)
    bench_participation(fast)
    bench_sharded_comm(fast)
    bench_compressed_comm(fast)


def bench_storm_triple(fast: bool):
    """Triple-sequence fused STORM step (flat substrate, one launch + one
    add) vs the 9-pass tree-map chain the unfused train step runs — the
    §Perf memory-term optimization of the FedBiOAcc local step."""
    from repro.optim import flat

    key = jax.random.PRNGKey(7)
    # a model-shaped tree: many body leaves, a few head/aux leaves
    leaf = 1 << 14
    counts = {"x": 48, "y": 8, "u": 8}
    vt = {s: {f"l{i}": jax.random.normal(jax.random.fold_in(key, 100 * j + i),
                                         (leaf,))
              for i in range(n)}
          for j, (s, n) in enumerate(counts.items())}
    rand = lambda off: jax.tree.map(
        lambda v: jax.random.normal(jax.random.fold_in(key, off), v.shape), vt)
    mt, got, gnt = rand(1), rand(2), rand(3)
    lrs, decays = (0.05, 0.1, 0.2), (0.99, 0.98, 0.97)
    n_total = sum(counts.values()) * leaf
    n_leaves = sum(counts.values())

    block = 1 << 16
    spec = flat.make_spec(vt, sections=("x", "y", "u"), block=block)
    # flatten ONCE at "init" — the substrate keeps state flat across steps
    v_b, m_b, go_b, gn_b = (flat.flatten_tree(spec, t)
                            for t in (vt, mt, got, gnt))

    @jax.jit
    def fused_step(v_b, m_b, go_b, gn_b):
        v_b, mp_b = flat.storm_partial_step(spec, v_b, m_b, go_b, lrs, decays)
        return v_b, flat.buffers_add(mp_b, gn_b)

    @jax.jit
    def treemap_step(vt, mt, got, gnt):
        sections = ("x", "y", "u")
        mp = {s: jax.tree.map(lambda m, o: decays[i] * (m - o),
                              mt[s], got[s]) for i, s in enumerate(sections)}
        vn = {s: jax.tree.map(lambda v, m: v - lrs[i] * m, vt[s], mt[s])
              for i, s in enumerate(sections)}
        mn = {s: jax.tree.map(jnp.add, mp[s], gnt[s]) for s in sections}
        return vn, mn

    reps = 10 if fast else 30
    t_fused = _timeit_us(lambda: fused_step(v_b, m_b, go_b, gn_b), reps)
    t_tree = _timeit_us(lambda: treemap_step(vt, mt, got, gnt), reps)

    # bytes-moved model (f32): the fused schedule streams v,m,g_old and
    # writes v',m_part (5N) + the correction add (3N) = 8N floats; the
    # 9-pass chain touches 3 arrays per pass = 27N floats.
    bytes_fused = 8 * n_total * 4
    bytes_tree = 27 * n_total * 4
    emit("kernel/storm3_fused", t_fused,
         f"treemap_us={t_tree:.0f};speedup={t_tree / t_fused:.2f}x;"
         f"n={n_total};leaves={n_leaves};block={block};"
         f"bytes_model_fused={bytes_fused};bytes_model_treemap={bytes_tree}")
    KERNEL_JSON["storm_triple"] = {
        "n_elements": n_total,
        "n_leaves": n_leaves,
        "block": block,
        "dtype": "float32",
        "fused_us": round(t_fused, 1),
        "treemap_us": round(t_tree, 1),
        "speedup": round(t_tree / t_fused, 3),
        "bytes_moved_model": {
            "fused": bytes_fused,
            "treemap_chain": bytes_tree,
            "note": "floats touched per step: fused = 5N (one triple-"
                    "sequence launch) + 3N (correction add); tree-map "
                    "chain = 9 passes x 3 arrays",
        },
        "backend": jax.default_backend(),
        # off-TPU the substrate lowers to the bit-identical jnp path; the
        # Pallas kernel (compiled) is the TPU production path
        "impl": "pallas" if jax.default_backend() == "tpu" else "jnp-flat",
    }


def bench_storm_local(fast: bool):
    """Local-lower-level variants on the sequence-spec engine: the
    dual-sequence fused step (Alg. 4: x/ν averaged, y/ω private) vs its
    tree-map chain, and the section-masked communication (one sliced
    reduction for x, private y untouched) vs the per-leaf tree-map mean.

    Sized to the reduced-arch (CPU) regime — a cache-resident federated
    state over a many-leaf model tree (~100 small tensors, like the reduced
    archs' norms/biases/projections) — where the structural difference (one
    compiled loop over static spec-time section runs vs one loop nest per
    leaf) is what's measured; at HBM-resident sizes both CPU lowerings are
    RAM-bandwidth-bound and indistinguishable, and the fused win is the TPU
    kernel + the sharded collective path (``sharded_comm``)."""
    from repro.optim import flat

    key = jax.random.PRNGKey(11)
    leaf = 1 << 10
    M = 8                               # the benchmark suite's client count
    counts = {"x": 96, "y": 16}         # body-heavy many-leaf tree
    vt = {s: {f"l{i}": jax.random.normal(
        jax.random.fold_in(key, 100 * j + i), (M, leaf))
        for i in range(n)}
        for j, (s, n) in enumerate(counts.items())}
    rand = lambda off: jax.tree.map(
        lambda v: jax.random.normal(jax.random.fold_in(key, off), v.shape), vt)
    mt, got = rand(1), rand(2)
    lrs, decays = (0.05, 0.1), (0.99, 0.98)
    n_total = sum(counts.values()) * leaf
    n_x = counts["x"] * leaf

    block = 1 << 9
    tmpl = jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype), vt)
    spec = flat.make_spec(tmpl, sections=("x", "y"), block=block)
    v_b, m_b, go_b = (flat.flatten_tree(spec, t, batch_dims=1)
                      for t in (vt, mt, got))

    def fused_step(v_b, m_b, go_b):
        v_b, mp_b = flat.storm_partial_step(spec, v_b, m_b, go_b, lrs, decays)
        # the communicated sections only — private y/ω sliced around
        v_b = flat.client_mean_masked(spec, v_b, ("mean", "none"))
        return v_b, mp_b

    def treemap_step(vt, mt, got):
        sections = ("x", "y")
        mp = {s: jax.tree.map(lambda m, o: decays[i] * (m - o),
                              mt[s], got[s]) for i, s in enumerate(sections)}
        vn = {s: jax.tree.map(lambda v, m: v - lrs[i] * m, vt[s], mt[s])
              for i, s in enumerate(sections)}
        from repro.core.tree_util import client_mean
        vn["x"] = client_mean(vn["x"])           # per-leaf comm, x only
        return vn, mp

    def masked_comm(v_b):
        return flat.client_mean_masked(spec, v_b, ("mean", "none"))

    def treemap_comm(vt):
        from repro.core.tree_util import client_mean
        return dict(vt, x=client_mean(vt["x"]))

    # both sides measured under the engine's donation convention (the train
    # step donates its state buffers) — the masked path's in-place chunked
    # sliced reduction then never copies the private y/ω tiles — with
    # interleaved A/B blocks so machine drift cannot flip the ratios
    reps = 10 if fast else 20
    mk_b = lambda: tuple(jax.tree.map(jnp.array, t) for t in (v_b, m_b, go_b))
    mk_t = lambda: tuple(jax.tree.map(jnp.array, t) for t in (vt, mt, got))
    t_fused, t_tree = _timeit_us_ab(
        [(jax.jit(fused_step, donate_argnums=(0, 1, 2)), mk_b),
         (jax.jit(treemap_step, donate_argnums=(0, 1, 2)), mk_t)], reps)
    t_mcomm, t_tcomm = _timeit_us_ab(
        [(jax.jit(masked_comm, donate_argnums=(0,)),
          lambda: (jax.tree.map(jnp.array, v_b),)),
         (jax.jit(treemap_comm, donate_argnums=(0,)),
          lambda: (jax.tree.map(jnp.array, vt),))], reps)

    emit("kernel/storm2_local_fused", t_fused,
         f"treemap_us={t_tree:.0f};speedup={t_tree / t_fused:.2f}x;"
         f"n={n_total};clients={M};private_frac="
         f"{1 - n_x / n_total:.2f}")
    emit("kernel/masked_comm", t_mcomm,
         f"treemap_us={t_tcomm:.0f};speedup={t_tcomm / t_mcomm:.2f}x;"
         f"reduced_elems={M * n_x};private_elems={M * (n_total - n_x)}")
    KERNEL_JSON["storm_dual_local"] = {
        "n_elements": n_total, "clients": M, "block": block,
        "dtype": "float32",
        "fused_us": round(t_fused, 1),
        "treemap_us": round(t_tree, 1),
        "speedup": round(t_tree / t_fused, 3),
        "note": "dual-sequence Alg. 4 step (partial STORM + var step + "
                "masked comm of x only; y/ω private) vs per-leaf tree-map "
                "chain + per-leaf x mean; off-TPU this is the jnp fallback "
                "— the kernel + single-all-reduce win is the TPU path; "
                "both sides donate their buffers (the engine's convention)",
        "backend": jax.default_backend(),
        "impl": "pallas" if jax.default_backend() == "tpu" else "jnp-flat",
    }
    KERNEL_JSON["masked_comm"] = {
        "n_elements": n_total, "clients": M,
        "communicated_elements": n_x,
        "private_elements": n_total - n_x,
        "masked_us": round(t_mcomm, 1),
        "treemap_us": round(t_tcomm, 1),
        "speedup": round(t_tcomm / t_mcomm, 3),
        "note": "section-masked client mean — static spec-time section-run "
                "slices, one in-place chunked sliced reduction for the x "
                "run, private y tiles never touched — vs per-leaf tree-map "
                "client_mean over the x tree; both sides donate their "
                "buffers (the engine's convention)",
        "backend": jax.default_backend(),
    }


def bench_participation(fast: bool):
    """Comm-volume-vs-m participation sweep: uniform(m) sampling over M
    clients on the flat substrate.  The comm model counts the floats that
    cross the network per round — only participants' communicated sections
    move (m · n_comm · 4 bytes), so bytes scale with m/M while the
    non-participant rows pass through bit-identical."""
    from repro.federation.participation import (ParticipationSpec,
                                                expected_comm_fraction,
                                                make_participation)
    from repro.optim import flat

    key = jax.random.PRNGKey(13)
    leaf = 1 << 14
    M = 8
    counts = {"x": 48, "y": 8}          # body communicated, heads private
    vt = {s: {f"l{i}": jax.random.normal(
        jax.random.fold_in(key, 100 * j + i), (M, leaf))
        for i in range(n)}
        for j, (s, n) in enumerate(counts.items())}
    n_comm = counts["x"] * leaf
    block = 1 << 13
    tmpl = jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype),
                        vt)
    spec = flat.make_spec(tmpl, sections=("x", "y"), block=block)
    v_b = flat.flatten_tree(spec, vt, batch_dims=1)

    comm = jax.jit(
        lambda v_b, w: flat.client_mean_masked(spec, v_b, ("mean", "none"),
                                               weights=w),
        donate_argnums=(0,))

    reps = 10 if fast else 30
    sweep = []
    full_bytes = M * n_comm * 4
    for m in (1, 2, 4, 8):
        part = make_participation(ParticipationSpec("uniform", m), M)
        _, w = part.round_weights(jnp.int32(0))
        us = _timeit_us_donated(
            comm, lambda: (jax.tree.map(jnp.array, v_b), w), reps)
        frac = expected_comm_fraction(part)
        bytes_model = int(full_bytes * frac)      # == m/M · full volume
        sweep.append({"m": m, "comm_fraction": round(frac, 4),
                      "bytes_model": bytes_model, "masked_us": round(us, 1)})
        emit(f"participation/m={m}of{M}", us,
             f"bytes_model={bytes_model};fraction_of_full={frac:.3f};"
             f"communicated_elems={m * n_comm}")
    assert sweep[-1]["bytes_model"] == full_bytes
    KERNEL_JSON["participation_sweep"] = {
        "clients": M,
        "communicated_elements_per_client": n_comm,
        "private_elements_per_client": counts["y"] * leaf,
        "dtype": "float32",
        "full_participation_bytes": full_bytes,
        "sweep": sweep,
        "note": "uniform(m)-of-M sampling on the flat substrate: the comm "
                "model counts participants' communicated sections only "
                "(bytes scale with m/M); the masked reduction averages "
                "participants and passes non-participants through "
                "bit-identical (the SPMD sim still touches all rows — the "
                "bytes saving is network traffic, not local HBM)",
        "backend": jax.default_backend(),
    }
    bench_participation_experiments(fast)


def bench_participation_experiments(fast: bool):
    """Straggler/participation sweep as a list of declarative Experiment
    edits (repro.api): m-vs-convergence (uniform m-of-M straggler sweep) and
    an availability_rate sweep (trace-driven availability process) on the
    benchmark problem (reduced mamba2 synthetic-LM federated bilevel run,
    the fused engine end-to-end).  Each scenario IS a data edit of one base
    spec — recorded verbatim next to its result so every row is exactly
    reproducible with ``launch.train --experiment``."""
    from repro.api import (AlgorithmSpec, ExecutionSpec, Experiment,
                           ProblemSpec, ScheduleSpec)
    from repro.federation.participation import (expected_comm_fraction,
                                                make_participation)
    from repro.telemetry import measure_run

    steps = 8 if fast else 24
    base = Experiment(
        algorithm=AlgorithmSpec("fedbioacc"),
        problem=ProblemSpec(arch="mamba2-130m", reduced=True, num_clients=8,
                            per_client=1, seq_len=32),
        execution=ExecutionSpec(fuse_storm=True, fuse_oracles=True,
                                storm_block=256),
        schedule=ScheduleSpec(steps=steps, local_steps=2, lr_x=0.05,
                              lr_y=0.05, lr_u=0.05, neumann_q=2))

    def run_edit(edit: dict):
        # measure_run evaluates at the CLIENT-MEAN iterate (run.eval_fn
        # reads client 0 only, which under m < M sampling may be frozen all
        # run and show no signal)
        exp = base.edit(**edit)
        m = measure_run(exp, log=EVENTS_LOG, label="participation")
        part = make_participation(m["run"].participation,
                                  exp.problem.num_clients)
        rounds = max(exp.schedule.steps // exp.schedule.local_steps, 1)
        return {"edit": edit, "comm_fraction":
                round(expected_comm_fraction(part, rounds), 4),
                "val_loss_step1": m["val_loss_step1"],
                "val_loss_final": m["val_loss_final"],
                "us_per_step": m["us_per_step"]}

    M = base.problem.num_clients
    ms = (2, 8) if fast else (1, 2, 4, 8)
    m_rows = []
    for m in ms:
        row = run_edit({"participation.sampler": "uniform",
                        "participation.clients_per_round": m})
        m_rows.append(row)
        emit(f"participation/convergence_m={m}of{M}", row["us_per_step"],
             f"val_final={row['val_loss_final']};"
             f"comm_fraction={row['comm_fraction']}")

    rates = (0.5, 1.0) if fast else (0.3, 0.5, 0.7, 1.0)
    a_rows = []
    for rate in rates:
        row = run_edit({"participation.sampler": "trace",
                        "participation.availability_rate": rate})
        a_rows.append(row)
        emit(f"participation/availability_rate={rate}", row["us_per_step"],
             f"val_final={row['val_loss_final']};"
             f"comm_fraction={row['comm_fraction']}")

    KERNEL_JSON.setdefault("participation_sweep", {}).update({
        "experiment_base": json.loads(base.to_json()),
        "m_convergence": m_rows,
        "availability_sweep": a_rows,
        "scenario_note": "each row is base experiment + the recorded edits "
                         "(repro.api.Experiment.edit) — straggler sweep "
                         "over uniform m-of-M and over the availability "
                         "process rate; val losses after schedule.steps "
                         "fused steps; comm_fraction = measured mean mask "
                         "over the run's rounds (the comm-volume m/M "
                         "factor)",
    })
    bench_fault_tolerance(fast)


def bench_fault_tolerance(fast: bool):
    """Fault-tolerance bench as declarative Experiment edits (repro.api):
    guard overhead (health screen + robust aggregator attached to a
    zero-rate fault process, vs the unguarded engine) and convergence under
    a NaN/byzantine fault-rate sweep with the guards on — plus one recorded
    unguarded faulty run (the divergence the guards exist for).  Every row
    is the base spec + its edits, reproducible with ``launch.train
    --experiment``."""
    from repro.api import (AlgorithmSpec, ExecutionSpec, Experiment,
                           ProblemSpec, ScheduleSpec)
    from repro.federation.faults import (expected_fault_fraction,
                                         make_faults)
    from repro.telemetry import measure_run

    steps = 8 if fast else 24
    base = Experiment(
        algorithm=AlgorithmSpec("fedbioacc"),
        problem=ProblemSpec(arch="mamba2-130m", reduced=True, num_clients=8,
                            per_client=1, seq_len=32),
        execution=ExecutionSpec(fuse_storm=True, fuse_oracles=True,
                                storm_block=256),
        schedule=ScheduleSpec(steps=steps, local_steps=2, lr_x=0.05,
                              lr_y=0.05, lr_u=0.05, neumann_q=2))

    def run_edit(edit: dict):
        exp = base.edit(**edit)
        m = measure_run(exp, log=EVENTS_LOG, label="fault_tolerance")
        l = m["val_loss_final"]
        rounds = max(exp.schedule.steps // exp.schedule.local_steps, 1)
        frac = expected_fault_fraction(
            make_faults(exp.faults, exp.problem.num_clients), rounds)
        return {"edit": edit, "fault_fraction": frac,
                "finite": bool(np.isfinite(l)),
                "val_loss_final": l if np.isfinite(l) else None,
                "us_per_step": m["us_per_step"]}

    # guard overhead: zero-rate faults keep the trajectory bit-identical,
    # so the step-time delta IS the price of the guarded reduction
    aggs = ("clip",) if fast else ("mean", "clip", "trim")
    clean = run_edit({})
    emit("fault_tolerance/unguarded", clean["us_per_step"],
         f"val_final={clean['val_loss_final']}")
    overhead_rows = [clean]
    for agg in aggs:
        row = run_edit({"faults.dropout_rate": 0.0,     # attach zero faults
                        "robustness.aggregator": agg})
        overhead_rows.append(row)
        pct = 100.0 * (row["us_per_step"] / clean["us_per_step"] - 1.0)
        emit(f"fault_tolerance/guard_overhead_{agg}", row["us_per_step"],
             f"overhead_pct={pct:.1f};val_final={row['val_loss_final']}")

    # convergence under injected faults, guards on (screened clip)
    rates = (0.25,) if fast else (0.125, 0.25, 0.5)
    sweep_rows = []
    for rate in rates:
        row = run_edit({"faults.nan_rate": rate,
                        "faults.byzantine_rate": rate / 2,
                        "robustness.aggregator": "clip"})
        sweep_rows.append(row)
        emit(f"fault_tolerance/guarded_nan_rate={rate}", row["us_per_step"],
             f"finite={row['finite']};val_final={row['val_loss_final']};"
             f"nan_frac={row['fault_fraction']['nan']}")

    # the failure mode on record: the same faults without guards diverge
    bad = run_edit({"faults.nan_rate": 0.25})
    sweep_rows.append(bad)
    emit("fault_tolerance/unguarded_nan_rate=0.25", bad["us_per_step"],
         f"finite={bad['finite']};val_final={bad['val_loss_final']}")

    KERNEL_JSON["fault_tolerance"] = {
        "experiment_base": json.loads(base.to_json()),
        "guard_overhead": overhead_rows,
        "fault_rate_sweep": sweep_rows,
        "scenario_note": "each row is base experiment + the recorded edits "
                         "(repro.api.Experiment.edit) — guard_overhead "
                         "attaches a ZERO-rate fault process (trajectory "
                         "bit-identical, the step-time delta is the guarded "
                         "reduction's price); fault_rate_sweep injects "
                         "NaN + byzantine rows with the screened clip "
                         "aggregator on (finite=True is the claim) and "
                         "records the same faults unguarded "
                         "(finite=False, the divergence the guards catch); "
                         "fault_fraction = measured injection rates over "
                         "the run's rounds",
        "backend": jax.default_backend(),
    }
    bench_stragglers(fast)


def bench_stragglers(fast: bool):
    """Straggler bench as declarative Experiment edits (repro.api): the
    elastic round (deadline + quorum + over-provisioned uniform sampling)
    against the synchronous wait-for-slowest barrier on identical
    heavy-tailed compute-time draws.  Wall-clock is simulated through
    :func:`repro.federation.stragglers.simulate_rounds` — the same pure
    ``round_decision`` the engine traces — and the acceptance row (drop
    policy: summed elastic wall-clock < summed wait-for-slowest, final
    loss within 5% of the synchronous baseline) is checked in-band."""
    from repro.api import (AlgorithmSpec, ExecutionSpec, Experiment,
                           ProblemSpec, ScheduleSpec)
    from repro.api.build import _resolve_participation
    from repro.federation.participation import make_participation
    from repro.federation.stragglers import (expected_arrival_fraction,
                                             make_stragglers, over_provision,
                                             simulate_rounds)
    from repro.telemetry import measure_run

    steps = 8 if fast else 24
    base = Experiment(
        algorithm=AlgorithmSpec("fedbioacc"),
        problem=ProblemSpec(arch="mamba2-130m", reduced=True, num_clients=8,
                            per_client=1, seq_len=32),
        execution=ExecutionSpec(fuse_storm=True, fuse_oracles=True,
                                storm_block=256),
        schedule=ScheduleSpec(steps=steps, local_steps=2, lr_x=0.05,
                              lr_y=0.05, lr_u=0.05, neumann_q=2))
    base = base.edit(**{"participation.sampler": "uniform",
                        "participation.clients_per_round": 4})
    sim_rounds = 32 if fast else 64       # clock sim is host-side and cheap

    sync = measure_run(base, log=EVENTS_LOG, label="stragglers")
    loss_sync = sync["val_loss_final"]
    emit("stragglers/synchronous", sync["us_per_step"],
         f"val_final={loss_sync}")

    policies = ("drop",) if fast else ("drop", "carry", "cancel")
    rows = []
    for policy in policies:
        edit = {"stragglers.tail": 1.0, "stragglers.deadline": 1.5,
                "stragglers.over_provision": 2, "stragglers.quorum": 0.5,
                "stragglers.late_policy": policy}
        exp = base.edit(**edit)
        m = measure_run(exp, log=EVENTS_LOG, label="stragglers")
        loss = m["val_loss_final"]
        M = exp.problem.num_clients
        strag = make_stragglers(exp.stragglers, M)
        part = make_participation(
            over_provision(exp.stragglers, _resolve_participation(exp), M), M)
        sim = simulate_rounds(strag, part, sim_rounds)
        wall = round(sum(r["wall_clock"] for r in sim), 6)
        slow = round(sum(r["wait_for_slowest"] for r in sim), 6)
        within = (np.isfinite(loss) and np.isfinite(loss_sync)
                  and abs(loss - loss_sync) <= 0.05 * abs(loss_sync))
        rows.append({"edit": edit, "val_loss_final": loss,
                     "val_loss_sync": loss_sync,
                     "loss_within_5pct": bool(within),
                     "us_per_step": m["us_per_step"],
                     "sim_rounds": sim_rounds,
                     "sim_wall_clock": wall,
                     "sim_wait_for_slowest": slow,
                     "sim_speedup": round(slow / max(wall, 1e-9), 4),
                     "arrival_fraction": expected_arrival_fraction(
                         strag, part, sim_rounds)})
        emit(f"stragglers/elastic_{policy}", m["us_per_step"],
             f"sim_speedup={rows[-1]['sim_speedup']};"
             f"wall={wall};slowest={slow};"
             f"within_5pct={within};val_final={loss}")

    KERNEL_JSON["straggler_sweep"] = {
        "experiment_base": json.loads(base.to_json()),
        "policy_sweep": rows,
        "scenario_note": "each row is base experiment + the recorded edits "
                         "(repro.api.Experiment.edit) — elastic rounds "
                         "(deadline 1.5, quorum 0.5, over_provision 2, "
                         "lognormal tail 1.0) vs the synchronous barrier; "
                         "sim_wall_clock sums min(effective deadline, "
                         "slowest sampled arrival) over simulate_rounds, "
                         "sim_wait_for_slowest sums the barrier's max "
                         "arrival on the SAME draws; the acceptance claim "
                         "is sim_wall_clock < sim_wait_for_slowest with "
                         "loss_within_5pct=True on the drop row",
        "backend": jax.default_backend(),
    }


_COMPRESSED_WIRE_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from repro.launch.hlo_stats import collective_bytes
from repro.optim import flat

key = jax.random.PRNGKey(5)
leaf = 1 << 12
counts = {"x": 24, "y": 8}          # body communicated, heads private
MODEL = 2
mesh = Mesh(np.asarray(jax.devices()[: 4 * MODEL]).reshape(4, MODEL),
            ("data", "model"))
ctx = flat.make_shard_ctx(mesh)
M = 8
vt = {s: {f"l{i}": jax.random.normal(
    jax.random.fold_in(key, 100 * j + i), (M, leaf)) for i in range(n)}
    for j, (s, n) in enumerate(counts.items())}
tmpl = jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype), vt)
BLOCK = 1 << 10
spec = flat.make_spec(tmpl, sections=("x", "y"), block=BLOCK, shards=MODEL)
v_b = flat.flatten_tree(spec, vt, batch_dims=1)
# per-device psum payload: the model-shard chunk of the communicated x run
elems = counts["x"] * leaf // MODEL
out = {"comm_elems_per_chunk": elems, "block": BLOCK, "wire": {}}
for name, ccfg in (("exact", None),
                   ("int8", flat.CompressCfg(quant="int8")),
                   ("int8_topk10",
                    flat.CompressCfg(quant="int8", topk_frac=0.1))):
    if ccfg is None:
        fn = jax.jit(lambda b: flat.client_mean_masked(
            spec, b, ("mean", "none"), shard=ctx))
        hlo = fn.lower(v_b).compile().as_text()
    else:
        ef = (tuple(jnp.zeros_like(b) for b in v_b)
              if ccfg.has_ef else None)
        fn = jax.jit(lambda b, e, c=ccfg: flat.client_mean_masked(
            spec, b, ("mean", "none"), shard=ctx, compress=c, ef=e))
        hlo = fn.lower(v_b, ef).compile().as_text()
    out["wire"][name] = collective_bytes(hlo)["bytes_by_dtype"]
print("COMPRESSED_WIRE_JSON " + json.dumps(out))
'''


def _compressed_wire_hlo(fast: bool):
    """Compile the masked reduction exact vs int8(+topk) on an 8-host-device
    mesh in a subprocess and return the collective ``bytes_by_dtype``
    breakdowns — the HLO half of the wire-bytes agreement record."""
    import subprocess
    import sys
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # forced host devices only: the chip, if any, stays with the parent
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.join(os.path.dirname(__file__), "..")
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    try:
        res = subprocess.run([sys.executable, "-c", _COMPRESSED_WIRE_SCRIPT],
                             env=env, capture_output=True, text=True,
                             timeout=1200)
        line = next((l for l in res.stdout.splitlines()
                     if l.startswith("COMPRESSED_WIRE_JSON ")), None)
        if res.returncode != 0 or line is None:
            return {"failure": f"rc={res.returncode}: {res.stderr[-300:]}"}
    except subprocess.TimeoutExpired:
        return {"failure": "timeout after 1200s"}
    return json.loads(line[len("COMPRESSED_WIRE_JSON "):])


def bench_compressed_comm(fast: bool):
    """Compressed-communication bench as declarative Experiment edits
    (repro.api): bytes and wall-clock vs comm policy (exact / bf16 / int8 /
    top-k x {1%, 10%} / int8+top-k) and the compressed-vs-exact convergence
    curves, every row replayable as base spec + its recorded edits.  The
    bytes trade-off is recorded twice — the analytic uplink/wire models
    (``repro.federation.compression``) and the compiled-HLO collective
    dtype breakdown from an 8-host-device subprocess — and the acceptance
    row (int8 + top-k 10%: >= 4x fewer uplink bytes, final loss within 5%
    of exact) is checked in-band.  One top-k row runs with error feedback
    OFF — the documented divergence row the EF buffers exist for."""
    from repro.api import (AlgorithmSpec, ExecutionSpec, Experiment,
                           ProblemSpec, ScheduleSpec)
    from repro.federation.compression import (CompressionSpec,
                                              uplink_bytes_per_elem,
                                              wire_bytes_per_elem)
    from repro.telemetry import measure_run

    steps = 8 if fast else 24
    block = 256
    base = Experiment(
        algorithm=AlgorithmSpec("fedbioacc"),
        problem=ProblemSpec(arch="mamba2-130m", reduced=True, num_clients=8,
                            per_client=1, seq_len=32),
        execution=ExecutionSpec(fuse_storm=True, fuse_oracles=True,
                                storm_block=block),
        schedule=ScheduleSpec(steps=steps, local_steps=2, lr_x=0.05,
                              lr_y=0.05, lr_u=0.05, neumann_q=2))

    def run_edit(edit: dict):
        exp = base.edit(**edit)
        m = measure_run(exp, curve=True, log=EVENTS_LOG,
                        label="compressed_comm")
        cp = exp.compression or CompressionSpec()
        return {"edit": edit,
                "uplink_bytes_per_elem":
                    round(uplink_bytes_per_elem(cp, block), 4),
                "wire_bytes_per_elem":
                    round(wire_bytes_per_elem(cp, block), 4),
                "val_loss_curve": m["val_loss_curve"],
                "val_loss_step1": m["val_loss_step1"],
                "val_loss_final": m["val_loss_final"],
                "us_per_step": m["us_per_step"]}

    policies = [
        ("exact", {}),
        ("bf16", {"compression.quant": "bf16"}),
        ("int8", {"compression.quant": "int8"}),
        ("topk1", {"compression.topk_frac": 0.01}),
        ("topk10", {"compression.topk_frac": 0.10}),
        ("int8_topk10", {"compression.quant": "int8",
                         "compression.topk_frac": 0.10}),
        ("topk10_no_ef", {"compression.topk_frac": 0.10,
                          "compression.error_feedback": False}),
    ]
    if fast:
        policies = [p for p in policies if p[0] != "topk1"]
    rows = []
    exact_loss = None
    for name, edit in policies:
        row = run_edit(edit)
        row["policy"] = name
        if name == "exact":
            exact_loss = row["val_loss_final"]
        row["uplink_ratio_vs_exact"] = round(
            4.0 / row["uplink_bytes_per_elem"], 2)
        row["loss_delta_vs_exact"] = (
            None if exact_loss is None
            else round(row["val_loss_final"] - exact_loss, 5))
        rows.append(row)
        emit(f"compressed_comm/{name}", row["us_per_step"],
             f"uplink_B_per_elem={row['uplink_bytes_per_elem']};"
             f"uplink_ratio={row['uplink_ratio_vs_exact']}x;"
             f"val_final={row['val_loss_final']}")

    # in-band acceptance: int8 + top-k(10%) moves >= 4x fewer uplink bytes
    # with final loss within 5% of the exact-comm run
    acc = next(r for r in rows if r["policy"] == "int8_topk10")
    rel = abs(acc["loss_delta_vs_exact"]) / abs(exact_loss)
    acceptance = {"uplink_ratio_vs_exact": acc["uplink_ratio_vs_exact"],
                  "uplink_ratio_ok": acc["uplink_ratio_vs_exact"] >= 4.0,
                  "loss_rel_delta": round(rel, 5),
                  "loss_within_5pct": bool(rel <= 0.05)}
    emit("compressed_comm/acceptance_int8_topk10",
         acc["us_per_step"],
         f"uplink_ratio={acc['uplink_ratio_vs_exact']}x(>=4:"
         f"{acceptance['uplink_ratio_ok']});"
         f"loss_rel_delta={acceptance['loss_rel_delta']}"
         f"(<=0.05:{acceptance['loss_within_5pct']})")

    wire = _compressed_wire_hlo(fast)
    if "failure" not in wire:
        elems = wire["comm_elems_per_chunk"]
        s8 = wire["wire"]["int8"].get("s8", 0)
        f32_exact = wire["wire"]["exact"].get("f32", 0)
        wire["hlo_agrees_with_model"] = bool(s8 == elems)  # 1 B/elem dense
        wire["wire_ratio_exact_over_int8"] = round(
            f32_exact / max(sum(wire["wire"]["int8"].values()), 1), 2)
        emit("compressed_comm/wire_hlo", 0.0,
             f"s8_bytes={s8};expected={elems};"
             f"agrees={wire['hlo_agrees_with_model']};"
             f"ratio_vs_exact={wire['wire_ratio_exact_over_int8']}x")
    else:
        emit("compressed_comm/wire_hlo", 0.0, f"FAILED {wire['failure']}")

    KERNEL_JSON["compressed_comm"] = {
        "experiment_base": json.loads(base.to_json()),
        "policy_sweep": rows,
        "acceptance_int8_topk10": acceptance,
        "wire_hlo": wire,
        "scenario_note": "each row is base experiment + the recorded edits "
                         "(repro.api.Experiment.edit) — comm-policy sweep "
                         "over exact / bf16 / int8 / top-k x {1%,10%} / "
                         "int8+top-k(10%); uplink/wire bytes are the "
                         "analytic models of repro.federation.compression "
                         "at the run's storm_block; topk10_no_ef is the "
                         "error-feedback-OFF divergence row on record (the "
                         "dropped mass is never re-sent, so its trajectory "
                         "drifts from every EF run); wire_hlo compiles the "
                         "sharded masked reduction exact vs int8 on an "
                         "8-host-device mesh and records the collective "
                         "bytes-by-dtype — s8 bytes must equal the dense "
                         "per-chunk element count (1 B/elem), the analytic "
                         "wire model",
        "backend": jax.default_backend(),
    }


_SHARDED_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from benchmarks.run import _timeit_us_donated as timeit_donated
from repro.config import FederatedConfig
from repro.launch.hlo_stats import collective_bytes
from repro.optim import flat, sequences as seqs

FAST = bool(int(os.environ.get("BENCH_FAST", "0")))
reps = 5 if FAST else 15
key = jax.random.PRNGKey(17)
leaf = 1 << 12
counts = {"x": 24, "y": 8}          # body communicated, heads private
n_comm_per_client = counts["x"] * leaf
MODEL = 2
out = {"weak_scaling": [], "model_shards": MODEL,
       "communicated_elements_per_client": n_comm_per_client,
       "private_elements_per_client": counts["y"] * leaf,
       "dtype": "float32"}


# --- weak scaling over the data axis: M grows with d, M/d fixed at 2 ---
for d in (1, 2, 4):
    M = 2 * d
    mesh = Mesh(np.asarray(jax.devices()[: d * MODEL]).reshape(d, MODEL),
                ("data", "model"))
    ctx = flat.make_shard_ctx(mesh)
    vt = {s: {f"l{i}": jax.random.normal(
        jax.random.fold_in(key, 100 * j + i), (M, leaf))
        for i in range(n)}
        for j, (s, n) in enumerate(counts.items())}
    tmpl = jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype),
                        vt)
    spec = flat.make_spec(tmpl, sections=("x", "y"), block=1 << 10,
                          shards=MODEL)
    v_b = flat.flatten_tree(spec, vt, batch_dims=1)
    comm = jax.jit(lambda b: flat.client_mean_masked(
        spec, b, ("mean", "none"), shard=ctx), donate_argnums=(0,))
    hlo = comm.lower(v_b).compile().as_text()
    coll = collective_bytes(hlo)
    us = timeit_donated(comm, lambda: (jax.tree.map(jnp.array, v_b),), reps)
    out["weak_scaling"].append({
        "data_axis": d, "clients": M,
        "comm_us": round(us, 1),
        # the collective payload each device contributes: its model-shard
        # slice of the communicated x run (partial sums), f32
        "per_shard_psum_bytes": n_comm_per_client // MODEL * 4,
        "psum_count": coll["counts"]["all-reduce"],
        "collective_bytes": coll["bytes"]["all-reduce"],
    })

# --- overlap on/off: fedbioacc-local engine, matmul oracle, 4x2 mesh ---
d, M, dx = 4, 8, 192
mesh = Mesh(np.asarray(jax.devices()[: d * MODEL]).reshape(d, MODEL),
            ("data", "model"))
ctx = flat.make_shard_ctx(mesh)
A = jax.random.normal(key, (dx, dx)) / np.sqrt(dx)
templates = {"x": {"w": jax.ShapeDtypeStruct((dx, dx), jnp.float32)},
             "y": {"h": jax.ShapeDtypeStruct((dx,), jnp.float32)}}


def oracle1(v, batch):
    w, h = v["x"]["w"], v["y"]["h"]
    # a few matmuls of compute for the issued all-reduce to hide behind
    g = A @ jnp.tanh(A @ w + batch[:, None] * 0.01) @ A.T
    gh = jnp.tanh(w) @ h + batch
    return {"x": {"w": g}, "y": {"h": gh}}


voracle = jax.vmap(oracle1)
cfg = FederatedConfig(num_clients=M, local_steps=2, lr_x=0.05, lr_y=0.05)
batch = jax.random.normal(key, (M, dx))
steps_n = 4 if FAST else 8
for overlap in (False, True):
    eng = seqs.make_engine(cfg, seqs.SPECS["fedbioacc_local"], templates,
                           voracle, block=1 << 10, shard=ctx,
                           overlap=overlap)
    w0 = jax.random.normal(key, (M, dx, dx))
    h0 = jax.random.normal(key, (M, dx))
    state0 = eng.init_state({"x": {"w": w0}, "y": {"h": h0}})
    jstep = jax.jit(eng.step, donate_argnums=(0,))
    st = jstep(state0, batch)               # compile + warm
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    for _ in range(steps_n):
        st = jstep(st, batch)
    jax.block_until_ready(st)
    us = (time.perf_counter() - t0) / steps_n * 1e6
    out["overlap_off_us" if not overlap else "overlap_on_us"] = round(us, 1)

out["note"] = (
    "sharded flat substrate on forced-host-device meshes (d x 2): "
    "client_mean_masked under shard_map — per-shard partial sums, one "
    "lax.psum over 'data' per communicated run, private tiles never enter "
    "the collective; weak scaling holds M/d fixed; overlap_on/off times one "
    "fused engine step (matmul oracle) with the variable all-reduce issued "
    "concurrently with (resp. after) the new-iterate oracle; host-device "
    "collectives share 2 CPU cores, so wall clocks measure schedule "
    "validity, not network speed")
out["backend"] = jax.default_backend()
print("SHARDED_COMM_JSON " + json.dumps(out))
'''


def bench_sharded_comm(fast: bool):
    """Sharded-substrate communication: real psum collectives under
    shard_map on an 8-host-device mesh, measured in a subprocess (the device
    count flag must precede jax init).  Records per-shard collective bytes,
    psum counts, weak scaling over the data axis, and the comm/compute
    overlap schedule's step time (on vs off)."""
    import subprocess
    import sys
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # forced host devices only: the chip, if any, stays with the parent
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_FAST"] = "1" if fast else "0"
    root = os.path.join(os.path.dirname(__file__), "..")
    # repo root too: the script imports the timing helper from benchmarks.run
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep + root
                         + os.pathsep + env.get("PYTHONPATH", ""))
    try:
        res = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                             capture_output=True, text=True, timeout=1200)
        line = next((l for l in res.stdout.splitlines()
                     if l.startswith("SHARDED_COMM_JSON ")), None)
        failure = (f"rc={res.returncode}: {res.stderr[-300:]}"
                   if res.returncode != 0 or line is None else None)
    except subprocess.TimeoutExpired:
        line, failure = None, "timeout after 1200s"
    if failure is not None:
        emit("kernel/sharded_comm", 0.0, f"FAILED {failure}")
        # --json rewrites BENCH_kernels.json wholesale — carry the previously
        # recorded sweep forward instead of silently dropping the artifact
        prev = os.path.join(root, "BENCH_kernels.json")
        if os.path.exists(prev):
            with open(prev) as fh:
                old = json.load(fh).get("sharded_comm")
            if old is not None:
                old["carried_forward"] = f"this run FAILED ({failure})"
                KERNEL_JSON["sharded_comm"] = old
        return
    rec = json.loads(line[len("SHARDED_COMM_JSON "):])
    for row in rec["weak_scaling"]:
        emit(f"kernel/sharded_comm/d={row['data_axis']}", row["comm_us"],
             f"clients={row['clients']};psum_count={row['psum_count']};"
             f"per_shard_psum_bytes={row['per_shard_psum_bytes']};"
             f"collective_bytes={row['collective_bytes']}")
    emit("kernel/sharded_overlap", rec["overlap_on_us"],
         f"overlap_off_us={rec['overlap_off_us']};"
         f"ratio={rec['overlap_off_us'] / rec['overlap_on_us']:.2f}x")
    KERNEL_JSON["sharded_comm"] = rec


# ---------------------------------------------------------------------------
# Roofline summary (reads dry-run artifacts if present)
# ---------------------------------------------------------------------------

def bench_roofline_summary(fast: bool):
    path = os.path.join(os.path.dirname(__file__), "..", "dryrun_single.jsonl")
    if not os.path.exists(path):
        emit("roofline/summary", 0.0, "dryrun_single.jsonl missing — run "
             "repro.launch.dryrun --all first")
        return
    from benchmarks.roofline import analyze
    recs = [json.loads(l) for l in open(path)]
    rows = [r for r in analyze(recs) if r.get("status") == "OK"]
    doms = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    worst = max(rows, key=lambda r: r["roofline_s"])
    emit("roofline/summary", 0.0,
         f"combos_ok={len(rows)};dominant_counts={doms};"
         f"worst={worst['arch']}x{worst['shape']}@{worst['roofline_s']:.1f}s")


# ---------------------------------------------------------------------------

BENCHES = [bench_table1_complexity, bench_data_cleaning, bench_hyperrep,
           bench_fair_fl, bench_linear_speedup, bench_kernels,
           bench_roofline_summary]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced round counts (CI mode)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_kernels.json (machine-readable kernel "
                         "perf trajectory) at the repo root")
    ap.add_argument("--events", default=None, metavar="PATH",
                    help="mirror every result row (plus the measured-run "
                         "build/step spans) into a repro.telemetry JSONL "
                         "event stream at PATH")
    args = ap.parse_args()
    global EVENTS_LOG
    if args.events:
        from repro.telemetry import EventLog
        EVENTS_LOG = EventLog(args.events, kind="bench", fast=args.fast)
    print("name,us_per_call,derived")
    try:
        for b in BENCHES:
            if args.only and args.only not in b.__name__:
                continue
            b(args.fast)
        if EVENTS_LOG is not None:
            EVENTS_LOG.emit("run_end", step=0, status="ok")
    finally:
        # no run_end on a crash — the summarizer reports the stream as such
        if EVENTS_LOG is not None:
            EVENTS_LOG.close()
    if args.json:
        if not KERNEL_JSON:    # e.g. --only excluded bench_kernels
            print("BENCH_kernels.json NOT written: bench_kernels did not "
                  "run, refusing to clobber the recorded trajectory",
                  flush=True)
            return
        path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_kernels.json")
        with open(path, "w") as fh:
            json.dump(KERNEL_JSON, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {os.path.normpath(path)}", flush=True)


if __name__ == '__main__':
    main()
