"""The FLOP models against counts made by hand at a tiny size."""
from bench.run import _module

SSM = {"num_layers": 2, "d_model": 8, "vocab_size": 10, "ssm_state": 4,
       "ssm_heads": 2, "ssm_head_dim": 4, "ssm_chunk": 4, "conv_width": 3}
MOE = {"num_layers": 3, "d_model": 8, "num_heads": 4, "num_kv_heads": 2,
       "d_ff": 6, "vocab_size": 10, "num_experts": 5,
       "experts_per_token": 2}


def test_ssm_hand_count():
    # d_inner 8; in_proj 8 x (16 + 8 + 2) = 208 and out_proj 8 x 8 = 64
    # weights: 2 x 272 = 544; conv 2 x 3 x 16 = 96; chunk 4 of seq 16:
    # intra 2 x 2.5 x 4 + 2 x 2.5 x 8 = 60; inter 2 x 32 x 2 + 2 x 32 / 4
    # = 144; per layer 844, two layers 1688, head 2 x 80 = 160
    assert _module("flops", "ssm").flops_per_token(SSM, 16) == 1848


def test_ssm_chunk_capped_by_sequence():
    # seq 2 < chunk 4: intra 2 x 1.5 x 4 + 2 x 1.5 x 8 = 36; inter
    # 2 x 32 x 2 + 2 x 32 / 2 = 160: per layer 544 + 96 + 36 + 160 = 836
    assert _module("flops", "ssm").flops_per_token(SSM, 2) == 2 * 836 + 160


def test_moe_hand_count():
    # head_dim 2: projections 2 x 8 x (2 x 8 + 2 x 4) = 384; attention at
    # seq 7: 2 x 2 x 8 x 4 = 128; router 2 x 8 x 5 = 80; 2 chosen experts
    # of 3 matrices 8 x 6: 2 x 3 x 2 x 48 = 576; per layer 1168, three
    # layers 3504, head 160
    assert _module("flops", "moe").flops_per_token(MOE, 7) == 3664


def test_local_oracle_hand_count():
    # Q = 2 products with the head's Hessian at each of two STORM points,
    # each two head products of 2 x 8 x 10 = 160: 2 x 2 x 2 x 160 = 1280
    count = _module("flops", "fedbioacc_local").flops_per_token(
        SSM, {"neumann_q": 2, "neumann_tau": 0.5})
    assert count == 1280
    assert _module("flops", "fedbioacc_local").flops_per_token(
        SSM, {"neumann_q": 0}) == 0
