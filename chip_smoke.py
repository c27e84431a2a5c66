#!/usr/bin/env python3
"""Run the federated bilevel train step on a TPU at mamba2-130m's published
widths (24 layers, d_model 768, ssm_state 128, 24 SSD heads x 64, chunk 256,
vocab 50,280), through the entry points a user calls.

    python chip_smoke.py            # one chip: kernel phase, then train phase
    python chip_smoke.py --chips 4  # four chips: sharded step vs one chip

One chip:

* kernel phase — on buffers of the real flat size (M=2 clients, the layout
  ``flat.make_spec`` gives the model's ``eval_shape``) the compiled
  ``storm3_step_flat`` and the ``quantpack_flat``/``quantunpack_flat`` pair
  are compared with their jnp references (``kernels/storm/ref.py``);
* train phase — ``experiments/fedbioacc.json`` at full width (M=2,
  per_client 1, seq_len 1024 = four SSD chunks, remat on) goes through
  ``repro.api.build`` -> ``Run.init`` -> a jitted, donating ``Run.step`` for
  3 steps -> ``Run.eval_fn``.  Every loss must be finite and the compiled
  step must hold the Pallas kernels (``tpu_custom_call``).

Four chips (``--chips 4``): ``experiments/fedbioacc_sharded_overlap.json``
at the same widths with M=2 and f32 parameters on a (data 2, model 2) mesh,
then the same spec on a (1, 1) mesh — all of it on one chip — in the same
process.  The state's shards must sit on 4 distinct devices and the
compiled step must hold its all-reduces.  Both runs start from the same
state; what the sharded run's steps changed in (x, y, u) and in the eval
loss must match the one-chip run's change within ``STATE_RTOL`` and
``LOSS_RTOL`` of that change, and the sharded run's initial state, planted
as its end, must fail that check.

Times, compile seconds and peak device memory are printed for information;
none of them is a claim.  Any failed phase exits non-zero, and so does a run
that outlives ``--deadline`` (after dumping every thread's stack).  Without a TPU
the script exits non-zero before any work.  The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import concurrent.futures
import faulthandler
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

SEED = 0
M = 2
SEQ_LEN = 1024
STEPS = 3
#: sharded vs one-chip: how far the sharded run's change of the state and
#: of the eval loss may sit from the one-chip run's, relative to that change
#: (``drift``/``loss_drift``).  The reductions sum in another order, so an
#: update to a bf16 parameter may round the other way; a run that leaves
#: its state unchanged reads 1.
#: With f32 parameters a (2, 2) run on 4 host devices read 6.2e-6 (state)
#: and 3.2e-4 (loss); one that skips the exchange between chips read 0.96
#: and 1.03.
STATE_RTOL = 1e-2
LOSS_RTOL = 0.1
#: seconds after which every thread's stack goes to stderr and the script
#: exits non-zero, so a run that hangs names where
DEADLINE = 1150.0


def say(name: str, **fields) -> None:
    print(f"{name}: " + json.dumps(fields), flush=True)


def tpu_devices():
    """JAX's devices, or exit non-zero: there is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's first device is "
                         f"{devs[0].platform!r}, not a TPU; nothing was run")
    return devs


def published_spec():
    """The fedbioacc flat layout at published widths (bf16 parameters)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import build_model
    from repro.optim import flat
    model = build_model(get_config("mamba2-130m"), dtype=jnp.bfloat16)
    tmpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return flat.make_spec({"x": tmpl["body"], "y": tmpl["head"],
                           "u": tmpl["head"]}, sections=("x", "y", "u"))


def _diff(a, b):
    import jax.numpy as jnp
    return jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))


def _amax(x):
    import jax.numpy as jnp
    return jnp.max(jnp.abs(x.astype(jnp.float32)))


def _compiled(fn, *args):
    """AOT-compile ``jax.jit(fn)`` and require a Pallas kernel in it."""
    import jax
    c = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in c.as_text():
        raise SystemExit(f"chip_smoke: no tpu_custom_call in {fn}")
    return c


def kernel_phase() -> None:
    """The compiled kernels against their jnp references, bit for bit — the jitted
    ``*_jnp`` lowerings of ``kernels/storm/ref.py`` the substrate takes off
    the chip, each in a program of its own (fused into a comparison, XLA
    may round a reference differently)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.storm.kernel import (storm3_step_flat,
                                            storm3_step_flat_jnp)
    from repro.kernels.storm.quantpack import (quantpack_flat,
                                               quantpack_flat_jnp,
                                               quantunpack_flat,
                                               quantunpack_flat_jnp)

    grp = published_spec().groups[0]
    n, block = M * grp.padded, grp.block
    k = jax.random.split(jax.random.PRNGKey(SEED), 5)
    p = jax.random.normal(k[0], (n,), jnp.bfloat16)
    m = jax.random.normal(k[1], (n,), jnp.float32)
    g = jax.random.normal(k[2], (n,), jnp.float32)
    lrs = jax.random.uniform(k[3], (n // block,), maxval=0.1)
    decays = jax.random.uniform(k[4], (n // block,), minval=0.5)
    say("kernel_inputs", elements=n, block=block, tiles=n // block,
        param_dtype="bfloat16")
    diff, amax = jax.jit(_diff), jax.jit(_amax)

    storm = _compiled(functools.partial(storm3_step_flat, block=block,
                                        interpret=False),
                      p, m, g, lrs, decays)
    pk, mk = storm(p, m, g, lrs, decays)
    pr, mr = storm3_step_flat_jnp(p, m, g, lrs, decays, block=block)
    dp, dm = float(diff(pk, pr)), float(diff(mk, mr))
    say("storm3_step_flat_vs_ref", max_abs_diff_param=dp,
        max_abs_diff_momentum=dm)
    # elementwise f32 arithmetic in the same order: bit-identical
    if not (dp == 0.0 and dm == 0.0):
        raise SystemExit("chip_smoke: storm3_step_flat disagrees with "
                         "storm3_step_ref")
    del p, g, pk, mk, pr, mr

    pack = _compiled(functools.partial(quantpack_flat, block=block,
                                       interpret=False), m)
    qk, sk = pack(m)
    qr, sr = quantpack_flat_jnp(m, block=block)
    unpack = _compiled(functools.partial(quantunpack_flat, block=block,
                                         interpret=False), qk, sk)
    xk = unpack(qk, sk)
    xr = quantunpack_flat_jnp(qk, sk, block=block)
    dq, ds, du = float(diff(qk, qr)), float(diff(sk, sr)), float(diff(xk, xr))
    nq = int(jnp.sum(qk != qr))
    # a round trip is off by at most half a quantization step
    rt, half_step = float(diff(xk, m)), 0.5 * float(amax(sk))
    say("quantpack_pair_vs_ref",
        bit_identical=(nq == 0 and ds == 0.0 and du == 0.0),
        max_abs_diff_q=dq, q_mismatches=nq, max_abs_diff_scale=ds,
        max_abs_diff_unpack=du, roundtrip_max_abs_err=rt,
        half_step=half_step)
    if not (nq == 0 and ds == 0.0 and du == 0.0
            and rt <= half_step * (1 + 1e-5)):
        raise SystemExit("chip_smoke: the int8 pack pair disagrees with "
                         "quantpack_ref/quantunpack_ref")


def full_width(exp, *, reduced: bool = False, **edits):
    """``exp`` at mamba2-130m's published widths with the smoke's job.
    ``reduced`` keeps the CPU-sized model (2 SSD chunks) so the same job
    can be rehearsed on host devices."""
    size = ({"problem.reduced": True, "problem.seq_len": 64} if reduced else
            {"problem.reduced": False, "problem.seq_len": SEQ_LEN,
             "execution.remat": True})
    return exp.edit(**{"problem.num_clients": M, "problem.per_client": 1,
                       "schedule.steps": STEPS, **size, **edits})


def host_views(run, state):
    """The (x, y, u) trees of ``state`` on the host, as f32 numpy."""
    import jax
    import numpy as np

    @jax.jit
    def xyu(s):
        v = run.views(s)
        return {"x": v.x, "y": v.y, "u": v.u}
    return jax.tree.map(lambda a: np.asarray(a, np.float32),
                        jax.device_get(xyu(state)))


def compile_step(run):
    """``run.step`` jitted with its state donated, as ``repro.launch.train``
    does, and compiled for the state and batch ``train`` feeds it; returns
    (the executable, compile seconds).  The state stays abstract, so it can
    run on a thread beside another run's steps."""
    import jax
    key = jax.random.PRNGKey(run.spec.schedule.seed)
    state = jax.eval_shape(run.init, key)
    placed = run.shardings(state)
    if placed is not None:
        state = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), state, placed)
    batch = run.place_batch(run.batch_fn(key))
    t = time.perf_counter()
    step = jax.jit(run.step, donate_argnums=(0,)).lower(state,
                                                        batch).compile()
    return step, time.perf_counter() - t


def train(run, name: str, *, kernels: bool = True, compiled=None,
          keep_start: bool = False):
    """Take ``STEPS`` jitted, donating steps of ``run`` the way
    ``repro.launch.train`` does; returns (eval losses, compiled HLO text,
    final state, the state's (x, y, u) on the host at init if
    ``keep_start``).  ``kernels``: require the Pallas kernels in the
    compiled step; ``compiled``: a future of :func:`compile_step`."""
    import jax
    import numpy as np

    mc, exp = run.model_cfg, run.spec
    say(f"{name}_model", arch=mc.name, layers=mc.num_layers,
        d_model=mc.d_model, ssm_state=mc.ssm_state, ssm_heads=mc.ssm_heads,
        ssm_head_dim=mc.ssm_head_dim, ssm_chunk=mc.ssm_chunk,
        vocab=mc.vocab_size, clients=exp.problem.num_clients,
        seq_len=exp.problem.seq_len, mesh=exp.execution.mesh,
        param_dtype=exp.problem.param_dtype)
    key = jax.random.PRNGKey(exp.schedule.seed)
    t = time.perf_counter()
    state = run.init(key)
    jax.block_until_ready(state)
    say(f"{name}_init", seconds=time.perf_counter() - t)
    start = host_views(run, state) if keep_start else None
    t = time.perf_counter()
    losses = [run.eval_fn(state)]
    say(f"{name}_eval", seconds=time.perf_counter() - t,
        eval_loss=losses[0])

    t = time.perf_counter()
    step, dt = compiled.result() if compiled else compile_step(run)
    say(f"{name}_compile", seconds=dt, waited=time.perf_counter() - t)
    hlo = step.as_text()
    if kernels and "tpu_custom_call" not in hlo:
        raise SystemExit(f"chip_smoke: {name}: the compiled step holds no "
                         f"tpu_custom_call — the Pallas kernels did not run")
    for i in range(STEPS):
        key, sub = jax.random.split(key)
        batch = run.place_batch(run.batch_fn(sub))
        t = time.perf_counter()
        state, _ = step(state, batch)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t
        losses.append(run.eval_fn(state))
        say(f"{name}_step", step=i + 1, seconds=dt, eval_loss=losses[-1])
    if not all(np.isfinite(losses)):
        raise SystemExit(f"chip_smoke: {name}: non-finite loss in {losses}")
    return losses, hlo, state, start


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip(devs) -> None:
    from repro.api import Experiment, build
    kernel_phase()
    say("kernel_phase_peak", peak_bytes_in_use=peak_bytes(devs[0]))
    exp = full_width(Experiment.load(
        os.path.join(HERE, "experiments", "fedbioacc.json")))
    losses = train(build(exp), "train")[0]
    say("train_phase", eval_losses=losses,
        peak_bytes_in_use=peak_bytes(devs[0]))


def drift(start, end, ref_start, ref_end):
    """How far a run's change of (x, y, u) is from the reference run's:
    per section ||end - ref_end|| / ||ref_end - ref_start|| over all of
    its leaves, and the worst single leaf.  Both runs start from the same
    state, so a run that left its state unchanged reads exactly 1."""
    import jax
    import numpy as np

    def sq(a):
        return float(np.sum(np.square(a, dtype=np.float64)))

    out = {}
    for sec in ("x", "y", "u"):
        num = jax.tree.leaves(jax.tree.map(lambda a, b: sq(a - b),
                                           end[sec], ref_end[sec]))
        den = jax.tree.leaves(jax.tree.map(lambda a, b: sq(a - b),
                                           ref_end[sec], ref_start[sec]))
        leaf = max((np.sqrt(n / d) if d else (0.0 if not n else np.inf))
                   for n, d in zip(num, den))
        out[sec] = {"rel": float(np.sqrt(sum(num) / sum(den))),
                    "worst_leaf": float(leaf),
                    "ref_change_norm": float(np.sqrt(sum(den)))}
    return out


def loss_drift(losses, ref):
    """max_t |(L_t - L_0) - (R_t - R_0)| / max_t |R_t - R_0|: the eval
    loss's change against the reference run's; a run whose loss did not
    move reads exactly 1."""
    moved = max(abs(r - ref[0]) for r in ref[1:])
    off = max(abs((a - losses[0]) - (r - ref[0]))
              for a, r in zip(losses[1:], ref[1:]))
    return off / moved


def agree(d, ld) -> bool:
    return (all(v["rel"] <= STATE_RTOL for v in d.values())
            and ld <= LOSS_RTOL)


def four_chips(devs, *, reduced: bool = False) -> None:
    """The sharded spec on a (2, 2) mesh against the same spec on a
    (1, 1) mesh.  ``reduced``: the CPU-sized rehearsal on 4 host devices
    (no Pallas kernels there, so none is required)."""
    import jax
    import numpy as np
    from repro.api import Experiment, build
    from repro.launch.hlo_stats import collective_bytes
    if len(devs) < 4:
        raise SystemExit(f"chip_smoke: --chips 4 needs 4 devices, found "
                         f"{len(devs)}")
    base = Experiment.load(os.path.join(
        HERE, "experiments", "fedbioacc_sharded_overlap.json"))
    # f32 parameters: three steps move a bf16 parameter by about one ulp,
    # so in bf16 the summation order alone decides most of the change
    size = {"reduced": reduced, "problem.param_dtype": "float32"}

    # the one-chip run's step compiles on a thread while the sharded run
    # goes through its own compile and steps
    run = build(full_width(base, **size, **{"execution.mesh": (2, 2)}))
    ref_run = build(full_width(base, **size, **{"execution.mesh": (1, 1)}))
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        ref_step = pool.submit(compile_step, ref_run)
        losses, hlo, state, start = train(run, "sharded",
                                          kernels=not reduced,
                                          keep_start=True)
        big = max(jax.tree.leaves(state), key=lambda a: a.size)
        shard_devs = {s.device.id for s in big.addressable_shards}
        coll = collective_bytes(hlo)
        say("sharded_layout", buffer_shape=list(big.shape),
            shard_shape=list(big.addressable_shards[0].data.shape),
            shard_devices=sorted(shard_devs), collectives=coll["counts"],
            peak_bytes_in_use=[peak_bytes(d) for d in devs[:4]])
        if len(shard_devs) != 4:
            raise SystemExit(f"chip_smoke: state shards sit on "
                             f"{shard_devs}, not 4 distinct devices")
        if not coll["counts"].get("all-reduce"):
            raise SystemExit("chip_smoke: the sharded step holds no "
                             "all-reduce")
        end = host_views(run, state)
        del state, big
        ref_losses, _, ref_state, ref_start = train(
            ref_run, "one_chip", kernels=not reduced, compiled=ref_step,
            keep_start=True)
    ref_end = host_views(ref_run, ref_state)
    del ref_state
    if not all(jax.tree.leaves(jax.tree.map(np.array_equal, start,
                                           ref_start))):
        raise SystemExit("chip_smoke: the two runs start from different "
                         "states")
    d, ld = drift(start, end, ref_start, ref_end), loss_drift(losses,
                                                              ref_losses)
    # the check must tell a run that trained from one that did not: the
    # sharded run's initial state, planted as its end, has to fail it
    planted = (drift(start, start, ref_start, ref_end),
               loss_drift(losses[:1] * len(losses), ref_losses))
    say("sharded_vs_one_chip", eval_losses=losses, one_chip=ref_losses,
        state_drift=d, loss_drift=ld, state_rtol=STATE_RTOL,
        loss_rtol=LOSS_RTOL)
    say("planted_unchanged_state", state_drift=planted[0],
        loss_drift=planted[1])
    if agree(*planted):
        raise SystemExit("chip_smoke: the agreement check passes a state "
                         "that was never trained")
    if not agree(d, ld):
        raise SystemExit("chip_smoke: the sharded run's change disagrees "
                         "with the one-chip run's")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel + train phases on one chip; 4: the "
                         "sharded step against the one-chip run")
    ap.add_argument("--deadline", type=float, default=DEADLINE,
                    help="dump every thread's stack and exit non-zero "
                         "after this many seconds (default %(default)s)")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(args.deadline, exit=True)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = tpu_devices()
    say("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs))
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip(devs)
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
