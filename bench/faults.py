"""Faults planted in the program under test, to show that the output check
catches them.  Each is a context manager yielding the ``build_fn`` that
:func:`bench.run.run_cell` takes; patches of the program's modules last as
long as the context, so they are in place when the step is traced.

* ``unchanged`` — the step returns its state as it got it;
* ``half_batch`` — the second half of every row's labels is masked out, so
  the loss is the mean over the first half;
* ``no_exchange`` — the client reduction returns each client's own value
  (only the averaged sections ever enter it);
* ``answer_altered`` — the oracle's lower gradient comes out doubled, from
  whichever fused oracle the algorithm runs (global or local lower level).
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "no_exchange", "answer_altered")


@contextlib.contextmanager
def planted(name: str):
    import jax
    from bench.run import build
    from repro.core import hypergrad
    from repro.optim import sequences

    if name == "unchanged":
        def step(state, batch):
            return state, {"step": state.step}
        yield lambda cell: build(cell)._replace(step=step)
    elif name == "half_batch":
        def halve(stream):
            lab = stream["labels"]
            cut = lab.shape[-1] // 2
            return dict(stream, labels=lab.at[..., cut:].set(-1))

        def fn(cell):
            run = build(cell)
            return run._replace(place_batch=lambda b: run.place_batch(
                {k: halve(v) for k, v in b.items()}))
        yield fn
    elif name == "no_exchange":
        orig = sequences.comm_buffers

        def local(spec, cfg, step, bufs, policies, **kw):
            return bufs
        sequences.comm_buffers = local
        try:
            yield build
        finally:
            sequences.comm_buffers = orig
    elif name == "answer_altered":
        origs = {k: getattr(hypergrad, k)
                 for k in ("fused_oracles", "fused_local_oracles")}

        def doubled(orig):
            def fn(*args):
                omega, *rest = orig(*args)
                return (jax.tree.map(lambda a: 2 * a, omega), *rest)
            return fn
        for k, orig in origs.items():
            setattr(hypergrad, k, doubled(orig))
        try:
            yield build
        finally:
            for k, orig in origs.items():
                setattr(hypergrad, k, orig)
    else:
        raise ValueError(f"unknown fault {name!r}; choose from {FAULTS}")
