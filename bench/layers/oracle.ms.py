"""Device time per step of the ops under the program's ``fed.oracle``
scope: the hypergradient oracle at both STORM points (bench/scopes.py)."""
from bench import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx.get("hlo"), scopes.METRICS["oracle.ms"])
