"""Host time per step of the benchmark's ``dispatch`` span: making the
step's batch, placing it and calling the jitted step."""


def read(trace, ctx):
    spans = [h for h in trace.host if h.name == "dispatch"]
    if not spans:
        return None
    return sum(h.dur for h in spans) / len(spans) / 1e6
