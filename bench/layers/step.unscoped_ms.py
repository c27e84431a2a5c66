"""Device time per step of the ops under no ``fed.`` scope: what the
program's layer names miss, XLA's own relayouts among it
(bench/scopes.py)."""
from bench import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx.get("hlo"),
                     scopes.METRICS["step.unscoped_ms"])
