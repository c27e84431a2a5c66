"""Device time per step of the ops under ``fed.update``: the fused STORM
launches and the correction add (bench/scopes.py)."""
from bench import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx.get("hlo"), scopes.METRICS["update.ms"])
