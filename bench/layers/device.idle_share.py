"""Share of the traced window in which no operation ran on the device."""


def read(trace, ctx):
    if not trace.devices or trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_ns() / trace.window_ns)
