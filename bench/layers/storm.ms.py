"""Device time per step of the fused STORM Pallas kernel (its custom calls,
``%storm3_step_flat*``, one per parameter dtype)."""


def is_storm(op) -> bool:
    return op.category == "custom-call" and op.name.startswith("%storm3_step")


def read(trace, ctx):
    ns = trace.op_ns(is_storm)
    if ns <= 0:
        return None
    return ns / trace.steps / 1e6
