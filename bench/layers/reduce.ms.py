"""Device time per step of the ops under ``fed.reduce``, the client
reduction, averaged over the traced steps that communicate and those that
do not (bench/scopes.py)."""
from bench import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx.get("hlo"), scopes.METRICS["reduce.ms"])
