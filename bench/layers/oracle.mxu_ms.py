"""Device time per step of the matrix products: the ops that the compiled
step's HLO shows to be, or to fuse, a ``convolution`` or ``dot``
(``ctx["matmul_ops"]``, from :func:`bench.trace.matmul_ops`).  Only the
oracle issues matrix products in the step, so this is the oracle's matrix
time."""


def read(trace, ctx):
    names = ctx.get("matmul_ops")
    if not names:
        return None
    ns = trace.op_ns(lambda op: op.name in names)
    if ns <= 0:
        return None
    return ns / trace.steps / 1e6
