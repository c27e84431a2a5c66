"""Device time per step of the ops under ``fed.oracle_g``: every
differentiation of the lower objective g, the Neumann series' products
with its Hessian among them (bench/scopes.py)."""
from bench import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx.get("hlo"), scopes.METRICS["oracle.g_ms"])
