"""Grouped-query attention plus a top-k routed expert MLP per layer, then
the output head.  Only the ``experts_per_token`` chosen experts count."""


def flops_per_token(s: dict, seq_len: int) -> float:
    d, hq, hkv = s["d_model"], s["num_heads"], s["num_kv_heads"]
    hd = d // hq
    proj = 2 * d * (2 * hq * hd + 2 * hkv * hd)
    # causal: a query reads (seq_len + 1) / 2 keys on average, for the
    # scores and again for the values
    attn = 2 * 2 * hq * hd * (seq_len + 1) / 2
    router = 2 * d * s["num_experts"]
    expert = s["experts_per_token"] * 3 * 2 * d * s["d_ff"]
    return s["num_layers"] * (proj + attn + router + expert) \
        + 2 * d * s["vocab_size"]
