"""Mamba-2: per layer the in/out projections, the depthwise convolution and
the four products of the chunked SSD scan; then the output head."""


def flops_per_token(s: dict, seq_len: int) -> float:
    d, h, p, n = s["d_model"], s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"]
    di = h * p
    q = min(s["ssm_chunk"], seq_len)
    proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d
    conv = 2 * s["conv_width"] * (di + 2 * n)
    # within a chunk each position reads (q + 1) / 2 positions on average:
    # C.B^T over the state, then the masked scores times x over the heads
    intra = 2 * (q + 1) / 2 * n + 2 * (q + 1) / 2 * di
    # a chunk's state from its inputs, the outputs from the entering state,
    # and the recurrence over chunks
    inter = 2 * n * di + 2 * n * di + 2 * n * di / q
    return s["num_layers"] * (proj + conv + intra + inter) \
        + 2 * d * s["vocab_size"]
