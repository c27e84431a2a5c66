"""Federated bilevel training driver — a thin adapter over ``repro.api``.

Every run IS a declarative :class:`repro.api.Experiment`: flags never feed a
bespoke kwargs pile, they produce spec edits applied to a base Experiment
(the built-in defaults, ``--experiment exp.json``, or the spec embedded in a
checkpoint).  The spec is embedded in every checkpoint, so resume needs no
re-specified flags.

    # flags build a spec
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --reduced \
        --algo fedbioacc --steps 100 --clients 4 --per-client 2 --seq 128

    # a committed spec runs as-is; flags override individual fields
    PYTHONPATH=src python -m repro.launch.train --experiment exp.json
    PYTHONPATH=src python -m repro.launch.train --experiment exp.json --steps 500

    # resume reconstructs the exact run from the embedded spec — zero flags;
    # a spec-affecting flag that CONTRADICTS the embedded spec fails loudly
    PYTHONPATH=src python -m repro.launch.train --resume ckpt_dir

Checkpoints land in --ckpt-dir every --ckpt-every rounds (raw train state +
``experiment.json``).

Fault tolerance (see ``repro.federation.faults``): with
``experiment.robustness`` set, the loop snapshots last-known-good states
and rolls back on a non-finite or spiking eval loss (re-drawing batches and
fault masks for the retried rounds) before failing loudly; without it a
non-finite eval loss still fails loudly — diagnostic checkpoint + non-zero
exit naming the offending round.  ``--max-restarts N`` supervises the run
in a subprocess and auto-resumes from the latest checkpoint after a crash
(``--crash-at-step`` injects one for testing).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import subprocess
import sys
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Experiment, RollbackError, RollbackGuard, SpecError, \
    build
from repro.checkpoint import (checkpoint_metadata, load_checkpoint,
                              load_experiment, save_checkpoint)
from repro.configs import ARCHS
from repro.launch.compile_cache import enable_compile_cache

# CLI dest → dotted Experiment path, for every flag that simply sets one
# spec field.  Flags with coupled semantics (--seed, --participation
# promotions, --mesh parsing, --comm-every) are handled in
# :func:`apply_overrides` below.
_FLAG_PATHS = {
    "algo": "algorithm.name",
    "arch": "problem.arch",
    "reduced": "problem.reduced",
    "clients": "problem.num_clients",
    "per_client": "problem.per_client",
    "seq": "problem.seq_len",
    "steps": "schedule.steps",
    "local_steps": "schedule.local_steps",
    "lr_x": "schedule.lr_x",
    "lr_y": "schedule.lr_y",
    "lr_u": "schedule.lr_u",
    "hierarchy_period": "schedule.hierarchy_period",
    "neumann_q": "schedule.neumann_q",
    "fuse_storm": "execution.fuse_storm",
    "fuse_oracles": "execution.fuse_oracles",
    "overlap": "execution.overlap",
    "scatter_comm": "execution.scatter_comm",
    "participation": "participation.sampler",
    "clients_per_round": "participation.clients_per_round",
    "availability_seed": "participation.seed",
    "availability_rate": "participation.availability_rate",
    "availability_trace": "participation.trace_path",
    "stale_discount": "participation.stale_discount",
    "telemetry_sink": "telemetry.sink",
}


def _parser() -> argparse.ArgumentParser:
    S = argparse.SUPPRESS   # spec-affecting flags: only record what was SET
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--experiment", default=None, metavar="EXP.json",
                    help="base Experiment spec (repro.api JSON); other "
                         "flags override individual fields")
    ap.add_argument("--resume", default=None, metavar="CKPT_DIR",
                    help="reconstruct the run from the checkpoint's embedded "
                         "experiment.json and continue it; spec-affecting "
                         "flags must match the embedded spec")
    ap.add_argument("--arch", choices=sorted(ARCHS), default=S)
    ap.add_argument("--reduced", action="store_true", default=S,
                    help="train the reduced same-family variant (CPU-sized)")
    ap.add_argument("--algo", default=S,
                    help="registered algorithm name (repro.api.algorithms())")
    ap.add_argument("--steps", type=int, default=S)
    ap.add_argument("--clients", type=int, default=S)
    ap.add_argument("--local-steps", type=int, default=S)
    ap.add_argument("--per-client", type=int, default=S)
    ap.add_argument("--seq", type=int, default=S)
    ap.add_argument("--lr-x", type=float, default=S)
    ap.add_argument("--lr-y", type=float, default=S)
    ap.add_argument("--lr-u", type=float, default=S)
    ap.add_argument("--seed", type=int, default=S,
                    help="sets both problem.data_seed and schedule.seed")
    ap.add_argument("--hierarchy-period", type=int, default=S,
                    help="k>0: pod-local averaging, cross-pod only every "
                         "k-th round (all algorithms honor this)")
    ap.add_argument("--neumann-q", type=int, default=S,
                    help="Neumann series terms for the local-lower "
                         "hyper-gradient (fedbio_local/fedbioacc_local)")
    ap.add_argument("--comm-every", default=S, metavar="SEC=K[,SEC=K]",
                    help="per-section async communication cadence, e.g. "
                         "'u=2' reduces the u sequence every 2nd comm round")
    ap.add_argument("--participation",
                    choices=["full", "uniform", "weighted", "trace"],
                    default=S,
                    help="client sampler: m-of-M uniform/data-size-weighted "
                         "sampling or a trace-driven availability process "
                         "(non-participants frozen, participants-only means)")
    ap.add_argument("--clients-per-round", type=int, default=S,
                    help="m for the uniform/weighted samplers (0 = all "
                         "clients; implies --participation uniform when set)")
    ap.add_argument("--availability-seed", type=int, default=S,
                    help="seed of the deterministic per-round availability "
                         "process (resume-safe: masks depend only on "
                         "seed + round)")
    ap.add_argument("--availability-rate", type=float, default=S,
                    help="trace sampler: per-round client up-probability")
    ap.add_argument("--availability-trace", default=S, metavar="PATH.json",
                    help="recorded availability log ([rounds, clients] 0/1 "
                         "JSON matrix) replayed deterministically (cyclic) "
                         "through the trace sampler; implies "
                         "--participation trace")
    ap.add_argument("--client-weights", default=S,
                    help="comma-separated per-client data sizes (required by "
                         "--participation weighted; also weights the means)")
    ap.add_argument("--stale-discount", type=float, default=S,
                    help="alpha^staleness discount for returning clients' "
                         "contributions (1.0 = off; a no-op under full "
                         "participation)")
    ap.add_argument("--fuse-storm", action="store_true", default=S,
                    help="flat-buffer substrate: the algorithm's sequence "
                         "spec compiled to fused triple-sequence updates "
                         "+ section-masked communication (all algorithms)")
    ap.add_argument("--fuse-oracles", action="store_true", default=S,
                    help="share one linearization (and one batch) across "
                         "the oracle directions (no-op for fedavg)")
    ap.add_argument("--mesh", default=S, metavar="DATA,MODEL",
                    help="shard the flat substrate over a (data, model) "
                         "device mesh (e.g. 4,2 under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8, or "
                         "'production' for the 16x16 pod mesh): clients "
                         "over 'data', packed params over 'model', real "
                         "psum collectives under shard_map; needs "
                         "--fuse-storm")
    ap.add_argument("--overlap", action="store_true", default=S,
                    help="comm/compute overlap: issue the variable-section "
                         "all-reduce concurrently with the new-iterate "
                         "oracle (STORM algorithms; needs --mesh)")
    ap.add_argument("--scatter-comm", action="store_true", default=S,
                    help="with --mesh: lower the participant mean to the "
                         "psum_scatter + all_gather all-reduce decomposition "
                         "instead of one psum (the form XLA can software-"
                         "pipeline with compute)")
    ap.add_argument("--telemetry-sink", default=S, metavar="EVENTS.jsonl",
                    help="write the structured event stream here (a spec "
                         "edit: enables experiment.telemetry when absent; "
                         "see repro.telemetry)")
    # driver-only knobs (never part of the spec / trajectory)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="supervise the run in a subprocess and auto-resume "
                         "from the latest --ckpt-dir checkpoint after a "
                         "crash, up to N times (requires --ckpt-dir)")
    ap.add_argument("--restart-backoff", type=float, default=1.0,
                    help="base seconds between restart attempts (doubles "
                         "each retry)")
    ap.add_argument("--crash-at-step", type=int, default=0,
                    help="testing: hard-exit the process after completing "
                         "this step (fresh runs only — inert on --resume, "
                         "so a supervised restart runs to completion)")
    return ap


def apply_overrides(base: Experiment, ov: dict) -> Experiment:
    """Apply explicitly-passed CLI flags as spec edits (the only way flags
    reach the run), mirroring the CLI's coupled-flag semantics."""
    edits = {}
    for dest, path in _FLAG_PATHS.items():
        if dest in ov:
            edits[path] = ov[dest]
    if "seed" in ov:
        edits["problem.data_seed"] = ov["seed"]
        edits["schedule.seed"] = ov["seed"]
    if "client_weights" in ov:
        edits["participation.client_weights"] = tuple(
            float(v) for v in ov["client_weights"].split(","))
    if "mesh" in ov:
        m = ov["mesh"]
        if m == "production":
            edits["execution.mesh"] = "production"
        else:
            try:
                edits["execution.mesh"] = tuple(int(v) for v in m.split(","))
            except ValueError:
                raise SystemExit(f"--mesh expects DATA,MODEL (e.g. 4,2) or "
                                 f"'production'; got {m!r}")
    if "comm_every" in ov:
        try:
            edits["schedule.comm_every"] = {
                k: int(v) for k, v in
                (pair.split("=") for pair in ov["comm_every"].split(","))}
        except ValueError:
            raise SystemExit(f"--comm-every expects SEC=K[,SEC=K] (e.g. "
                             f"u=2); got {ov['comm_every']!r}")
    # sampler promotions (trace_path / clients_per_round on the default
    # sampler) are part of the SPEC's normal form — Experiment.normalize(),
    # applied by validate()/build() for every consumer, not a CLI quirk;
    # contradictory combinations fail there with field-naming errors
    exp = base.edit(**edits).normalize()
    if exp.participation.sampler == "full" \
            and exp.participation.stale_discount != 1.0:
        # full participation keeps every staleness counter at 0, so the
        # discount could never bite — flag the no-op instead of aborting
        print("stale_discount ignored: full participation has no "
              "stale clients (pick a sampler)")
    return exp


def _resolve_experiment(args, overrides: dict) -> tuple[Experiment, int]:
    """(experiment, start_step) from --resume / --experiment / defaults,
    with flag overrides applied."""
    if args.resume:
        base = load_experiment(args.resume)
        if base is None:
            raise SystemExit(
                f"--resume {args.resume}: no experiment.json in the "
                f"checkpoint (pre-spec checkpoint?) — re-specify the run "
                f"with --experiment/flags instead")
        exp = apply_overrides(base, overrides)
        # compare normal forms: CLI-embedded specs are already canonical,
        # hand-embedded ones may predate promotion
        if exp != base.normalize():
            raise SystemExit(
                f"--resume {args.resume}: flags contradict the embedded "
                f"experiment spec (passed: "
                f"{sorted('--' + k.replace('_', '-') for k in overrides)}). "
                f"Resume continues the EXACT run; drop the conflicting "
                f"flags or start a fresh run with --experiment")
        return exp, int(checkpoint_metadata(args.resume)["step"])
    if args.experiment:
        return apply_overrides(Experiment.load(args.experiment),
                               overrides), 0
    if "arch" not in overrides:
        raise SystemExit("--arch is required (or pass --experiment/--resume)")
    # the CLI's historical baseline: nothing reduced unless asked
    base = Experiment()
    base = base.edit(**{"problem.reduced": False})
    return apply_overrides(base, overrides), 0


def _strip_flag(argv: list, flag: str) -> list:
    """``argv`` without ``flag`` and its value (``--f v`` or ``--f=v``)."""
    out, i = [], 0
    while i < len(argv):
        if argv[i] == flag:
            i += 2
        elif argv[i].startswith(flag + "="):
            i += 1
        else:
            out.append(argv[i])
            i += 1
    return out


_RESTART_WAIT_CAP = 60.0   # seconds — a supervised restart never sleeps longer


def _restart_wait(backoff: float, attempt: int, token: str = "") -> float:
    """Bounded exponential backoff with deterministic jitter: the
    exponential wait is capped at :data:`_RESTART_WAIT_CAP` (an unbounded
    ``backoff * 2**attempt`` sleeps for hours by attempt ~12), then spread
    by a ±25% factor derived from ``crc32(token:attempt)`` — deterministic
    (reproducible runs, no RNG), but decorrelated across checkpoint dirs so
    a fleet of supervisors doesn't restart in lockstep."""
    base = min(backoff * (2 ** attempt), _RESTART_WAIT_CAP)
    frac = zlib.crc32(f"{token}:{attempt}".encode()) % 1000 / 999.0
    return min(base * (0.75 + 0.5 * frac), _RESTART_WAIT_CAP)


def _supervise(ns, raw_argv: list) -> list:
    """--max-restarts: run the train loop in a child process, resuming from
    the latest --ckpt-dir checkpoint after each crash (non-zero exit) until
    it succeeds or the restart budget runs out."""
    if not ns.ckpt_dir:
        raise SystemExit("--max-restarts requires --ckpt-dir (restarts "
                         "resume from the latest checkpoint)")
    base = raw_argv
    for flag in ("--max-restarts", "--restart-backoff"):
        base = _strip_flag(base, flag)
    for attempt in range(ns.max_restarts + 1):
        child = list(base)
        if attempt:
            # the crash knob only fires on fresh runs, but strip it anyway —
            # a retry that crashed before its first checkpoint IS fresh
            child = _strip_flag(child, "--crash-at-step")
            if os.path.exists(os.path.join(ns.ckpt_dir, "manifest.json")):
                child = _strip_flag(child, "--resume")
                child += ["--resume", ns.ckpt_dir]
        rc = subprocess.call([sys.executable, "-m", "repro.launch.train",
                              *child])
        if rc == 0:
            return []
        if attempt < ns.max_restarts:
            wait = _restart_wait(ns.restart_backoff, attempt,
                                 ns.ckpt_dir or "")
            print(f"run crashed (exit {rc}); restart "
                  f"{attempt + 1}/{ns.max_restarts} in {wait:.1f}s",
                  flush=True)
            time.sleep(wait)
    raise SystemExit(f"run still crashing after {ns.max_restarts} "
                     f"restarts (last exit {rc}) — inspect "
                     f"{ns.ckpt_dir}/diagnostic or the traceback above")


def _diagnostic_checkpoint(ns, state, step: int, exp) -> None:
    """Dump the offending state next to the regular checkpoints so a failed
    run can be inspected (never overwrites the last good checkpoint)."""
    if not ns.ckpt_dir:
        return
    d = os.path.join(ns.ckpt_dir, "diagnostic")
    save_checkpoint(d, state, {"step": int(step), "diagnostic": True},
                    experiment=exp)
    print(f"diagnostic checkpoint -> {d}", flush=True)


def main(argv=None):
    ap = _parser()
    ns = ap.parse_args(argv)
    if ns.max_restarts > 0:
        # the parent only starts children: it never touches a backend, so
        # each child is the one process holding the chip
        return _supervise(ns, list(argv) if argv is not None
                          else sys.argv[1:])
    enable_compile_cache()
    # SUPPRESS-defaulted flags only exist on the namespace when passed
    driver = {"experiment", "resume", "ckpt_dir", "ckpt_every", "log_every",
              "max_restarts", "restart_backoff", "crash_at_step"}
    overrides = {k: v for k, v in vars(ns).items() if k not in driver}
    exp, start = _resolve_experiment(ns, overrides)

    try:
        run = build(exp)
    except SpecError as e:
        raise SystemExit(str(e))
    exp = run.spec

    # one code path for everything the driver reports: every line goes
    # through the event stream (when experiment.telemetry is set) and
    # stdout stays a thin render of the same records
    log = None
    if exp.telemetry is not None:
        from repro.telemetry import EventLog
        # a resumed run appends its segment to the checkpoint dir's stream
        ckdir = ns.ckpt_dir or ns.resume
        sink = exp.telemetry.sink or (
            os.path.join(ckdir, "events.jsonl") if ckdir
            else "events.jsonl")
        log = EventLog(sink, experiment=json.loads(exp.to_json()),
                       start_step=start)
    tracing = log is not None and exp.telemetry.trace

    def emit(event, render=None, **fields):
        if log is not None:
            log.emit(event, **fields)
        if render is not None:
            print(render, flush=True)

    if run.mesh is not None:
        axes = dict(run.mesh.shape)
        banner = (f"mesh: data={axes['data']} model={axes['model']} "
                  f"({len(run.mesh.devices.flat)} devices)"
                  + (" overlap=on" if exp.execution.overlap else "")
                  + (" comm=psum_scatter" if exp.execution.scatter_comm
                     else ""))
        emit("note", render=banner, text=banner)
    pspec = run.participation
    if pspec is not None:
        M = exp.problem.num_clients
        if pspec.trace_path is not None:
            detail = f"log={pspec.trace_path}"
        elif pspec.sampler == "trace":
            detail = f"rate={pspec.availability_rate}"
        else:
            detail = f"m={pspec.clients_per_round or M}/{M}"
        banner = (f"participation: {pspec.sampler} {detail} "
                  f"seed={pspec.seed}")
        emit("note", render=banner, text=banner)
    sg = exp.stragglers
    if sg is not None:
        banner = (f"stragglers: policy={sg.late_policy} "
                  f"deadline={sg.deadline} quorum={sg.quorum} "
                  f"over_provision={sg.over_provision} tail={sg.tail}")
        emit("note", render=banner, text=banner)

    guard = (RollbackGuard(exp.robustness) if exp.robustness is not None
             else None)
    key = jax.random.PRNGKey(exp.schedule.seed)
    if start:
        state = load_checkpoint(ns.resume, jax.eval_shape(run.init, key))
        if run.shardings(state) is not None:
            state = jax.device_put(state, run.shardings(state))
        md = checkpoint_metadata(ns.resume)
        if md.get("key") is not None:
            # the raw key was recorded: exact even after rollbacks folded
            # retries into it (split-replay could never reconstruct that)
            key = jnp.asarray(np.asarray(md["key"], np.uint32))
        else:
            # pre-fault-tolerance checkpoint: replay the batch-key sequence
            # up to the resume point (exact for rollback-free runs)
            for _ in range(start):
                key, _ = jax.random.split(key)
        if guard is not None:
            guard.retries = int(md.get("retries", 0))
        banner = f"resumed from {ns.resume} @ step {start}"
        emit("note", render=banner, text=banner)
    else:
        state = run.init(key)

    jstep = jax.jit(run.step, donate_argnums=(0,))
    n_params = sum(int(np.prod(s.shape)) for s in
                   jax.tree.leaves(jax.eval_shape(run.model.init,
                                                  jax.random.PRNGKey(0))))
    banner = (f"arch={run.model_cfg.name} family={run.model_cfg.family} "
              f"algo={exp.algorithm.name} params={n_params:,}")
    emit("note", render=banner, text=banner)

    # analytic per-round comm-bytes plan (fused engine only): every comm
    # round emits one reconcilable `comm` event — `python -m
    # repro.telemetry.validate` rebuilds the byte model from the stream's
    # embedded experiment and checks bytes_wire against it
    plan = None
    if log is not None:
        flat_spec = getattr(run.step, "spec", None)
        aspec = getattr(run.step, "aspec", None)
        if flat_spec is not None and aspec is not None:
            from repro.telemetry import comm_plan
            plan = comm_plan(flat_spec, aspec, exp.compression)

    def _host_metrics(metrics) -> dict:
        """The step's in-band metrics side output as JSON scalars/lists
        (the `screened` verdict vector is consumed separately)."""
        out = {}
        for k, v in metrics.items():
            if k in ("step", "screened"):
                continue
            a = np.asarray(v)
            out[k] = (round(float(a), 8) if a.ndim == 0
                      else [round(float(x), 8) for x in a.reshape(-1)])
        return out

    # host phases: with telemetry.trace on, each enters a profiler
    # annotation and is summed in memory, one span event per phase at log
    # steps; off, they cost nothing
    if tracing:
        from repro.telemetry import SpanTotals
        spans = SpanTotals()
        phase = spans.phase
    else:
        spans = None
        phase = lambda name: contextlib.nullcontext()
    local_steps = exp.schedule.local_steps
    retry = lambda: guard.retries if guard is not None else 0
    t0 = time.time()  # analysis: ignore[L301] driver timing
    history = []
    t = start
    while t < exp.schedule.steps:
        key, sub = jax.random.split(key)
        with phase("train/batch"):
            batch = run.place_batch(run.batch_fn(sub))
        with phase("train/dispatch"):
            state, metrics = jstep(state, batch)
        t += 1
        is_comm = t % local_steps == 0
        is_log = t % ns.log_every == 0 or t == start + 1
        if log is not None and (is_comm or is_log) and len(metrics) > 1:
            # in-band metrics: host-converted at comm/log steps only, so
            # the dispatch stream stays as deep as the telemetry-free loop
            emit("metrics", step=t, retry=retry(), **_host_metrics(metrics))
            screened = metrics.get("screened")
            if (is_comm and screened is not None
                    and exp.robustness is not None
                    and exp.robustness.screen):
                idx = np.flatnonzero(np.asarray(screened) > 0)
                if idx.size:
                    emit("clients_screened", step=t, round=t // local_steps,
                         retry=retry(), clients=[int(i) for i in idx])
            if (is_comm and exp.stragglers is not None
                    and "deadline" in metrics):
                # one first-class event per elastic round, read off the
                # engine's in-band straggler metrics (a warmup round —
                # deadline 0, synchronous — emits nothing)
                dl = round(float(np.asarray(metrics["deadline"])), 6)
                ext = int(np.asarray(metrics["extensions"]))
                if dl > 0:
                    emit("deadline", step=t, round=t // local_steps,
                         retry=retry(), deadline=dl,
                         deadline_next=round(float(np.asarray(
                             metrics["deadline_next"])), 6),
                         arrivals=int(np.asarray(metrics["arrivals"])),
                         quorum=int(np.asarray(metrics["quorum"])),
                         extensions=ext,
                         arrival_hist=[int(round(float(x))) for x in
                                       np.asarray(metrics["arrival_hist"])])
                    if ext > 0:
                        emit("quorum_miss", step=t, round=t // local_steps,
                             retry=retry(), extensions=ext, deadline=dl)
        if plan is not None and is_comm:
            from repro.telemetry import round_bytes
            rb_ev = round_bytes(plan, t // local_steps)
            if rb_ev is not None:
                emit("comm", step=t, retry=retry(), **rb_ev)
        if is_log:
            with phase("eval"):
                l = run.eval_fn(state)
            if guard is not None:
                # host-copied snapshot: the live state's buffers are donated
                # to the next jstep call, a stored alias would be invalid
                snap = jax.tree.map(np.array, state)
                try:
                    rb = guard.observe(t, snap, key, l)
                except RollbackError as e:
                    emit("retry_budget_exhausted", step=t, retry=retry(),
                         bad_loss=float(l))
                    if log is not None:
                        log.emit("run_end", step=t,
                                 status="retry_budget_exhausted")
                        log.close()
                    _diagnostic_checkpoint(ns, state, t, exp)
                    raise SystemExit(f"round {t}: {e}")
                if rb is not None:
                    bad = l
                    t, snap, key = rb
                    state = jax.tree.map(jnp.asarray, snap)
                    if run.shardings(state) is not None:
                        state = jax.device_put(state, run.shardings(state))
                    emit("rollback",
                         render=json.dumps(
                             {"rollback_to": t, "retry": guard.retries,
                              "bad_loss": bad}),
                         step=t, retry=guard.retries, bad_loss=float(bad))
                    continue
            elif not np.isfinite(l):
                if log is not None:
                    log.emit("run_end", step=t, status="diverged")
                    log.close()
                _diagnostic_checkpoint(ns, state, t, exp)
                raise SystemExit(
                    f"non-finite eval loss ({l}) at round {t}: training "
                    f"diverged — inspect the diagnostic checkpoint, enable "
                    f"robustness guards (experiment.robustness), or lower "
                    f"the learning rates")
            history.append({"step": t, "val_loss": l,
                            "wall_s": round(time.time() - t0, 1)})  # analysis: ignore[L301] driver timing
            emit("metrics", render=json.dumps(history[-1]), **history[-1])
        if ns.ckpt_dir and t % ns.ckpt_every == 0:
            # the RAW state (flat buffers included) + the embedded spec:
            # --resume rebuilds the structure from the spec alone.  The raw
            # key + retry count make resume exact across rollbacks.
            with phase("checkpoint"):
                save_checkpoint(
                    ns.ckpt_dir, state,
                    {"step": t, "arch": run.model_cfg.name,
                     "key": np.asarray(key).tolist(),
                     "retries": retry()},
                    experiment=exp)
            emit("checkpoint", render=f"checkpoint @ step {t} -> "
                                      f"{ns.ckpt_dir}",
                 step=t, path=ns.ckpt_dir)
        if spans is not None and is_log:
            spans.emit(log, step=t)
        if ns.crash_at_step and start == 0 and t == ns.crash_at_step:
            print(f"crash-at-step: hard exit after step {t}", flush=True)
            os._exit(17)
    if spans is not None:
        spans.emit(log, step=t)
    if log is not None:
        log.emit("run_end", step=t, status="ok")
        log.close()
    assert not any(jnp.isnan(jnp.asarray(h["val_loss"])) for h in history)
    return history


if __name__ == "__main__":
    main()
