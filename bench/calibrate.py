#!/usr/bin/env python3
"""Readings that set a cell's limits (bench/limits/<cell>.json): the check's
numbers for sound runs of the program, for the control and for each planted
fault, over several seeds, in one process and at the cell's own size, for
whichever algorithm the cell's traffic names (its plain reference is
``bench/reference/<algorithm>.py``, as in the benchmark's own runs).

    python bench/calibrate.py --workload <cell> --seeds 1-12 \
        [--control-seeds 1-3] [--operand-control-seeds 1-6] \
        [--faults half_batch,no_exchange] [--fault-seeds 1-3]

The control (``control``) is the plain reference computed in float8 (every
product's operands and result and every layer's output rounded by
``bench.reference.common.fp8``), put in the program's place; the operand
control (``control_operands``) rounds only the products' operands and sums
each product in float32.  Neither needs the program run on its seed.
Prints one JSON line per reading; the benchmark's own runs never run this.
Exits non-zero without a TPU unless ``--cpu`` is given.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"),
                os.path.dirname(HERE)]


def seeds(text: str):
    out = []
    for part in filter(None, text.split(",")):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def batches(cell, seed):
    """The seed's batch generator, as the benchmark's run makes it."""
    from bench import run as R
    from bench.traffic import make_batch_fn
    return make_batch_fn(cell.traffic, cell.config["sizes"]["vocab_size"],
                         R.keys(seed)[1])


def readings(cell, run, seed):
    """The program's readings for ``seed``; the state is freed after."""
    import jax
    from bench import run as R
    kw, _ = R.keys(seed)
    batch = batches(cell, seed)
    state = jax.jit(run.init)(kw)
    jstep = jax.jit(run.step, donate_argnums=(0,))
    state, prog = R.check_steps(run, jstep, state,
                                lambda i: run.place_batch(batch(i)),
                                cell.traffic["check_steps"])
    finite = R.nonfinite_leaves(state) == 0
    del state
    return prog, finite, batch


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--operand-control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--manifest",
                    default=os.path.join(os.path.dirname(HERE),
                                         "BENCHMARK.json"))
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import check, faults
    from bench import run as R
    from bench.cell import load
    from bench.reference.common import Arith
    cell = load(args.manifest, args.workload)
    if not args.cpu:
        R.tpu_devices(cell.chips)

    def say(kind, seed, prog, ref, **kw):
        nums = check.numbers(prog, ref)
        detail = {r: check.leaf_gaps(prog[r], ref[r])
                  for r in ("grad1", "change", "mom")}
        print(json.dumps({"kind": kind, "seed": seed, **kw, **{
            k: v for k, (v, _) in nums.items()},
            "leaves": {k: leaf for k, (_, leaf) in nums.items()},
            "detail": detail}), flush=True)

    run = R.build(cell)
    refs = {}
    prog_seeds = seeds(args.seeds)
    ctl = set(seeds(args.control_seeds))
    opc = set(seeds(args.operand_control_seeds))
    for s in sorted(set(prog_seeds) | ctl | opc):
        if s in prog_seeds:
            prog, finite, batch = readings(cell, run, s)
        else:
            batch = batches(cell, s)
        refs[s] = R.reference(cell, batch, s)
        if s in prog_seeds:
            say("program", s, prog, refs[s], finite=finite)
        if s in ctl:
            c = R.reference(cell, batch, s, Arith(control=True))
            say("control", s, c, refs[s])
        if s in opc:
            c = R.reference(cell, batch, s,
                            Arith(control=True, operands_only=True))
            say("control_operands", s, c, refs[s])
    for name in filter(None, args.faults.split(",")):
        with faults.planted(name) as build_fn:
            frun = build_fn(cell)
            for s in seeds(args.fault_seeds):
                prog, finite, batch = readings(cell, frun, s)
                if s not in refs:
                    refs[s] = R.reference(cell, batch, s)
                say(name, s, prog, refs[s], finite=finite)


if __name__ == "__main__":
    main()
