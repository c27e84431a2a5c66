"""The STORM kernel's share of its bandwidth roofline: the least time its
bytes (``bench/storm_bytes.py``) take at the chip's HBM bandwidth, over the
kernel's device time (its custom calls, ``%storm3_step_flat*``).  Bound by
bandwidth: the kernel does a handful of flops per 16-20 bytes."""


def is_storm(op) -> bool:
    return op.category == "custom-call" and op.name.startswith("%storm3_step")


def read(trace, ctx):
    ns = trace.op_ns(is_storm)
    if ns <= 0:
        return None
    least = ctx["storm_bytes_per_step"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (ns / trace.steps / 1e9)
