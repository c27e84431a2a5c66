"""Span-based phase tracing and the shared measured-run path.

Host-side phases (batch synthesis, the dispatched device step, eval,
checkpoint writes, benchmark measurement windows) are wrapped in
:func:`phase`: a context manager that enters a
``jax.profiler.TraceAnnotation`` (so the phase shows up in a profiler
trace) and records the wall-clock span as a ``span`` event in the run's
:class:`~repro.telemetry.events.EventLog`; a loop's per-step phases go
through :class:`SpanTotals`, which sums them in memory and writes one
event per phase when asked.  Device-side phases (oracle,
fused kernel, collective) are marked with :func:`annotate` —
``jax.named_scope`` — which attaches the phase name to the traced
equations' metadata for profiler/HLO attribution without touching the
computation.

:func:`measure_run` is the one warmed, donation-aware measured training
run that ``benchmarks/run.py``'s sweeps share (previously three copies of
the same timing boilerplate): compile + step 1 off the clock, the
remaining steps timed, client-mean val losses evaluated off the clock —
so BENCH_kernels.json rows and run events come from the same measurement
path.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax


def annotate(name: str):
    """Device-phase marker for traced code: attaches ``name`` to the traced
    equations (profiler/HLO metadata only — numerics and trajectories are
    untouched)."""
    return jax.named_scope(name)


@contextmanager
def phase(name: str, log=None, **fields):
    """Wall-clock + profiler span around a host-side phase; records a
    ``span`` event on ``log`` (ignored when ``log`` is None)."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()  # analysis: ignore[L301] driver timing
        try:
            yield
        finally:
            if log is not None:
                log.emit("span", name=name,
                         dur_s=round(time.perf_counter() - t0, 6), **fields)  # analysis: ignore[L301] driver timing


class SpanTotals:
    """Host phases of a loop, kept in memory: :meth:`phase` is
    :func:`phase` without the event, adding the span's wall-clock time and
    one to its phase's count; :meth:`emit` writes one ``span`` event per
    phase seen since the last call (``dur_s`` summed, ``count``) and starts
    again.  A training loop pays no event write per step."""

    def __init__(self):
        self.totals: dict = {}

    @contextmanager
    def phase(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()  # analysis: ignore[L301] host timing
            try:
                yield
            finally:
                dur = time.perf_counter() - t0  # analysis: ignore[L301] host timing
                tot, n = self.totals.get(name, (0.0, 0))
                self.totals[name] = (tot + dur, n + 1)

    def emit(self, log, **fields) -> None:
        for name, (dur, n) in self.totals.items():
            log.emit("span", name=name, dur_s=round(dur, 6), count=n,
                     **fields)
        self.totals.clear()


def _client_mean_loss(run, eval_batch):
    """Participation-insensitive convergence metric: val loss at the
    CLIENT-MEAN iterate (``run.eval_fn`` reads client 0 only, which under
    m < M sampling may be frozen all run and show no signal)."""
    import jax.numpy as jnp

    def mean_loss(state):
        v = run.views(state)
        p = (v.params if hasattr(v, "params")
             else {"body": v.x, "head": v.y})
        p = jax.tree.map(lambda t: jnp.mean(t, axis=0), p)
        return float(run.model.loss(p, eval_batch["val"])[0])

    return mean_loss


def measure_run(exp, *, curve: bool = False, log=None, label: str = None):
    """Build ``exp``, run its full schedule, and measure it (see the module
    docstring).  ``curve=True`` synchronizes every step
    (``block_until_ready``) and evaluates the client-mean val loss after
    each one with the eval excluded from the timed wall — the convergence-
    curve mode; ``curve=False`` times the steps as one dispatch stream and
    evaluates only after step 1 and at the end.

    Returns ``{"us_per_step", "val_loss_step1", "val_loss_final",
    "val_loss_curve", "run", "state"}`` (``val_loss_curve`` is None
    without ``curve``).
    """
    from repro.api import build
    label = label or exp.algorithm.name
    with phase(f"bench/{label}/build", log):
        run = build(exp)
    eval_batch = jax.tree.map(lambda v: v[0],
                              run.batch_fn(jax.random.PRNGKey(123)))
    mean_loss = _client_mean_loss(run, eval_batch)

    key = jax.random.PRNGKey(exp.schedule.seed)
    state = run.init(key)
    jstep = jax.jit(run.step, donate_argnums=(0,))
    key, sub = jax.random.split(key)
    with phase(f"bench/{label}/compile+step1", log):
        state, _ = jstep(state, run.batch_fn(sub))       # compile + step 1
    loss1 = round(mean_loss(state), 5)

    n = max(exp.schedule.steps - 1, 1)
    losses = [loss1]
    if curve:
        t0 = time.perf_counter()  # analysis: ignore[L301] driver timing
        wall = 0.0
        for _ in range(exp.schedule.steps - 1):
            key, sub = jax.random.split(key)
            state, _ = jstep(state, run.batch_fn(sub))
            jax.block_until_ready(state)
            wall += time.perf_counter() - t0  # analysis: ignore[L301] driver timing
            losses.append(round(mean_loss(state), 5))  # eval off the clock
            t0 = time.perf_counter()  # analysis: ignore[L301] driver timing
    else:
        t0 = time.perf_counter()  # analysis: ignore[L301] driver timing
        for _ in range(exp.schedule.steps - 1):
            key, sub = jax.random.split(key)
            state, _ = jstep(state, run.batch_fn(sub))
        wall = time.perf_counter() - t0  # analysis: ignore[L301] driver timing
    us = wall / n * 1e6
    if log is not None:
        log.emit("span", name=f"bench/{label}/steps",
                 dur_s=round(wall, 6), steps=exp.schedule.steps - 1)
    final = losses[-1] if curve else round(mean_loss(state), 5)
    return {"us_per_step": round(us, 1), "val_loss_step1": loss1,
            "val_loss_final": final,
            "val_loss_curve": losses if curve else None,
            "run": run, "state": state}
