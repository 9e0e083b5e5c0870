"""The yardstick's operation and byte counts against hand counts at a
small size."""
from bench import flops as FL
from bench.reference import internlm2, qwen2_moe

DENSE = internlm2.Shape(layers=2, d_model=8, heads=4, kv_heads=2,
                        head_dim=2, vocab=16, eps=1e-6, rope_theta=1e4,
                        d_ff=32)
MOE = qwen2_moe.Shape(layers=1, d_model=8, heads=2, kv_heads=2, head_dim=4,
                      vocab=16, eps=1e-6, rope_theta=1e4, experts=6, top_k=2,
                      expert_d_ff=4, shared_d_ff=8, redundant=2, ep_ranks=2)


def test_matmul_params_by_hand():
    # wq 8x8 + wo 8x8 + wk 8x4 + wv 8x4 = 192; SwiGLU 3 x 8 x 32 = 768
    assert internlm2.matmul_params(DENSE, 0) == 192 + 768
    # attention 4 x 8x8 = 256; router 8x6 = 48; two routed experts
    # 2 x 3 x 8x4 = 192; shared 3 x 8x8 = 192
    assert qwen2_moe.matmul_params(MOE, 0) == 256 + 48 + 192 + 192


def test_token_and_prefill_flops_by_hand():
    # per layer: 2 x 960 + 4 x ctx x heads x head_dim (QK^T and PV)
    assert FL.token_flops(internlm2, DENSE, 5, False) == \
        2 * (2 * 960 + 4 * 5 * 8)
    assert FL.token_flops(internlm2, DENSE, 5, True) == \
        2 * (2 * 960 + 4 * 5 * 8) + 2 * 8 * 16
    # positions 3..6 attend over 4..7 keys
    assert FL.prefill_flops(internlm2, DENSE, 3, 7) == sum(
        FL.token_flops(internlm2, DENSE, c, False) for c in range(4, 8))


def test_paged_attention_reads_each_sequences_kv_once():
    # a chunk of three rows of one sequence (valid 5, 6, 7) and one decode
    # row of another (valid 10), bf16
    flops, nbytes = internlm2.attention_call(DENSE, [[5, 6, 7], [10]])
    assert flops == 4 * (5 + 6 + 7 + 10) * 4 * 2
    kv = 2 * 2 * 2 * 2                  # K and V, kv_heads x head_dim, 2 B
    qo = 2 * 4 * 2 * 2                  # q and out, heads x head_dim, 2 B
    assert nbytes == kv * 7 + kv * 10 + 4 * qo
    # the same rows as separate sequences read their KV once each
    _, apart = internlm2.attention_call(DENSE, [[5], [6], [7], [10]])
    assert apart == kv * (5 + 6 + 7 + 10) + 4 * qo
    assert internlm2.attention_call(DENSE, [[]]) == (0, 0)
