#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chips and print its line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: turn on the compile cache, load the cell's files, build its
Experiment through ``repro.api.build``, make the state from the seed with
``Run.init`` (one jitted call), then drive the window's own entry —
``jstep(state, run.place_batch(batch(i)))`` with ``jstep = jax.jit(run.step,
donate_argnums=(0,))`` and ``batch`` the benchmark's generator — through the
check's first steps (their readings are kept), and measure from there for
``--seconds`` (``--trace 0``) or profile a few steps (``--trace 1``).  Once
the window has closed, the peak memory has been read and the state freed,
the plain reference runs the same first steps and decides ``correct``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1`` the
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``check``: every compared number with its limit, also printed as the last
lines of stderr.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero before any work and prints no result.

Per-layer metrics are read by ``bench/layers/<metric>.py``:
``read(trace, ctx)`` of a :class:`bench.trace.Trace` and a dict with
``steps``, ``chips``, ``flops_per_step``, ``storm_bytes_per_step``, the
chip's ``peak``, the compiled step's HLO text (``hlo``) and its matrix ops
(``matmul_ops``); ``None`` leaves the metric out of the line.

The output check and the counts follow the traffic's job: the plain
reference is ``bench/reference/<algorithm>.py``, the check compares the
sections that algorithm declares, and tokens and FLOPs count only the
clients whose work a step requires (:func:`bench.traffic.participants`).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: steps the traced run profiles
TRACE_STEPS = 6


def log(what: str, **fields) -> None:
    """A progress line on stderr (the check's lines come last)."""
    print(f"bench {what} " + json.dumps(fields), file=sys.stderr, flush=True)


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded by file (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(config: dict):
    """(plain reference module, FLOP model module) of the config's family."""
    return tuple(importlib.import_module(f"bench.{kind}.{config['family']}")
                 for kind in ("reference", "flops"))


def peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def param_dtype(config: dict):
    import jax.numpy as jnp
    return jnp.dtype(config["param_dtype"])


def algorithm(traffic: dict) -> str:
    """The name of the federated algorithm the traffic's job runs."""
    return traffic["experiment"]["algorithm"]["name"]


def schedule(traffic: dict) -> dict:
    """The hyper-parameters the plain references need, as the traffic
    file's job states them: the schedule's, the algorithm's own and the
    participation."""
    job = traffic["experiment"]
    sch = job["schedule"]
    return {**{k: sch[k] for k in ("lr_x", "lr_y", "lr_u", "local_steps",
                                   "lower_l2", "neumann_q", "neumann_tau")},
            **job["algorithm"]["params"],
            "participation": job["participation"]}


def flops_per_step(cell) -> float:
    """Required FLOPs of one step: ``forward_units`` forward passes
    (``bench/flops/<family>.py``) over every participant's tokens, plus,
    where the algorithm's oracle does more, its own count
    (``bench/flops/<algorithm>.py``)."""
    from bench import traffic
    tr, sizes = cell.traffic, cell.config["sizes"]
    per_token = tr["forward_units"] * family(cell.config)[1].flops_per_token(
        sizes, tr["seq_len"])
    if os.path.isfile(os.path.join(HERE, "flops", algorithm(tr) + ".py")):
        per_token += _module("flops", algorithm(tr)).flops_per_token(
            sizes, schedule(tr))
    return per_token * traffic.participants(tr) * tr["per_client"] \
        * tr["seq_len"]


def keys(seed: int):
    """(weights key, traffic key) of a run's seed."""
    import jax
    from bench.traffic import seed_key
    k = seed_key(seed)
    return jax.random.fold_in(k, 0), jax.random.fold_in(k, 1)


def build(cell):
    """The cell's built :class:`repro.api.Run`."""
    from repro import api
    from bench import cell as cells
    with cells.registered(cell.config) as arch:
        return api.build(cells.experiment(cell.config, cell.traffic, arch))


def check_steps(run, jstep, state, feed, n: int):
    """Drive ``state`` through the check's first ``n`` steps with the
    window's own ``jstep`` and ``feed``; returns (state, the program's
    readings).  Blocks after each step."""
    import jax
    from bench.check import program_readers
    first, grad1, late = program_readers(run)
    v0 = jax.device_get(first(state))
    log("start_read", at_s=time.perf_counter() - T0)
    prog = {}
    for i in range(n):
        state, _ = jstep(state, feed(i))
        if i == 0:
            prog["grad1"] = jax.device_get(grad1(state))
            log("first_step", at_s=time.perf_counter() - T0)
    prog.update(jax.device_get(late(state, jax.device_put(v0))))
    floats = lambda d: {k: float(v) for k, v in d.items()}
    return state, {k: floats(v) for k, v in prog.items()}


def reference(cell, batch, seed: int, ar=None):
    """The readings of the traffic's algorithm's plain reference
    (``bench/reference/<algorithm>.py``) over the check's first steps,
    from the same seed and batches."""
    from bench.reference import common
    alg = importlib.import_module(
        f"bench.reference.{algorithm(cell.traffic)}")
    n = cell.traffic["check_steps"]
    return alg.run(family(cell.config)[0], cell.config["sizes"],
                   param_dtype(cell.config), keys(seed)[0],
                   [batch(i) for i in range(n)], schedule(cell.traffic),
                   cell.traffic["clients"], ar or common.Arith())


def nonfinite_leaves(tree) -> int:
    """How many leaves of ``tree`` hold a value that is not finite."""
    import jax
    import jax.numpy as jnp
    return int(jax.jit(lambda t: sum(
        jnp.any(~jnp.isfinite(x)).astype(jnp.int32)
        for x in jax.tree.leaves(t)))(tree))


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, pk,
             build_fn=build):
    """Everything after the look for a chip; returns the result dict.
    ``pk``: the chip's peaks; ``build_fn(cell)`` builds the run."""
    import jax
    from bench import check, storm_bytes, traffic
    from bench.window import Window

    fam, _ = family(cell.config)
    tr, sizes = cell.traffic, cell.config["sizes"]
    log("imports", at_s=time.perf_counter() - T0)
    run = build_fn(cell)
    log("build", at_s=time.perf_counter() - T0)
    kw, kd = keys(seed)
    state = jax.block_until_ready(jax.jit(run.init)(kw))
    log("init", at_s=time.perf_counter() - T0)
    batch = traffic.make_batch_fn(tr, sizes["vocab_size"], kd)

    def feed(i):
        return run.place_batch(batch(i))

    jstep = jax.jit(run.step, donate_argnums=(0,))
    n_check = tr["check_steps"]
    state, prog = check_steps(run, jstep, state, feed, n_check)
    setup_s = time.perf_counter() - T0
    log("setup", setup_s=setup_s)

    win = Window(jstep, feed)
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
        try:
            state, n = win.run(state, n_check, lambda k, t: k >= TRACE_STEPS)
        finally:
            jax.profiler.stop_trace()
        # which ops multiply matrices, from the compiled step's HLO
        hlo = jstep.lower(state, feed(0)).compile().as_text()
    else:
        state, n = win.run(state, n_check, lambda k, t: t >= seconds)
    # the TPU runtime reserves a loaded program's temporaries apart from
    # the buffers in use: the device's peak holds both
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    log("memory", **stats[0])
    bad = nonfinite_leaves(state)
    del state
    log("window", steps=n, seconds=win.seconds, peak_bytes=peak)

    t_ref = time.perf_counter()
    limits = cell.limits["numbers"]
    ref = reference(cell, batch, seed)
    nums = {k: v for k, v in check.numbers(prog, ref).items() if k in limits}
    log("reference", seconds=time.perf_counter() - t_ref,
        left_out=check.left_out(ref),
        worst_leaves={k: leaf for k, (_, leaf) in nums.items()})
    correct = bad == 0 and all(v <= limits[k] for k, (v, _) in nums.items())

    dev = devices[0]
    flops_step = flops_per_step(cell)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": n, "failed": n if bad else 0}
    if not trace:
        metrics = {
            "setup_s": (setup_s, "s"),
            "tokens_per_s": (n * traffic.tokens_per_step(tr) / win.seconds,
                             "tokens/s"),
            "mfu": (100.0 * n * flops_step / (
                win.seconds * len(devices) * pk["bf16_flops_per_s"]), "%"),
            "step_s.p90": (statistics.quantiles(
                win.intervals(), n=10, method="inclusive")[8], "s"),
            "peak_hbm_gb": (peak / 1e9, "GB"),
        }
        out["metrics"] = {m["name"]: {"value": metrics[m["name"]][0],
                                      "unit": m["unit"]}
                          for m in cell.metrics["end_to_end"]}
    else:
        import shutil
        from bench import trace as trace_mod
        try:
            t = trace_mod.Trace(trace_mod.load(tdir), n)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        params = jax.eval_shape(lambda k: fam.init(
            k, sizes, param_dtype(cell.config)), kw)
        ctx = {"steps": n, "chips": len(devices), "peak": pk,
               "flops_per_step": flops_step,
               "matmul_ops": trace_mod.matmul_ops(hlo), "hlo": hlo,
               "storm_bytes_per_step": storm_bytes.bytes_per_step(
                   params, tr["clients"],
                   [s for s, _, _ in check.sections(run)])}
        out["metrics"] = {}
        for m in cell.metrics["per_layer"]:
            v = _module("layers", m["name"]).read(t, ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = t.busy_ns() / 1e9
        device["window_s"] = t.window_ns / 1e9
        out["breakdown"] = {
            "device_ops": [[k, v / 1e9] for k, v in t.top_ops()],
            "idle_gaps": [[k, v / 1e9] for k, v in t.idle_gaps()]}
    out["device"] = device
    out["check"] = {"nonfinite_leaves": {"value": bad, "limit": 0}}
    for k, (v, _) in nums.items():
        out["check"][k] = {"value": v, "limit": limits[k]}
    return out


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices, or exit non-zero: no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX's first device is {devs[0].platform!r}"
                         f", not a TPU; nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # every program goes to the cache, so a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.cell import load
    cell = load(args.manifest, args.workload)
    devices = tpu_devices(cell.chips)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   peaks(devices[0].device_kind))
    for k, v in out["check"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
