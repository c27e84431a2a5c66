"""Device time per step of the ops whose outermost scope is
``fed.unflatten`` or ``fed.flatten``: the views of the flat client buffers
before each oracle call and the gradients written back into them
(bench/scopes.py)."""
from bench import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx.get("hlo"),
                     scopes.METRICS["flat.boundary_ms"])
