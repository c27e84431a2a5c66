"""The whole step's share of the chips' peak bf16 FLOP/s over the traced
window: the required FLOPs of the traced steps (``bench/flops/``) over the
window times the chips times the peak."""


def read(trace, ctx):
    if trace.window_ns <= 0:
        return None
    spent = trace.window_ns / 1e9 * ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * ctx["flops_per_step"] * trace.steps / spent
