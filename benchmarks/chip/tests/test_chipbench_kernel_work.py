"""Operations and bytes of a hand-sized call, counted by the dense
architecture module."""

import chipbench_setup  # noqa: F401  (the benchmark and src on the path)
import registry

DENSE = registry.arch({"name": "t", "model_type": "mistral"})
M = {"layers": 3, "d_model": 8, "heads": 4, "kv_heads": 2, "head_dim": 2,
     "d_ff": 16, "vocab": 10}


def test_paged_attention_counts_live_tokens_of_real_lanes():
    # two lanes, 3 and 5 live tokens, 4 query heads over 2 KV heads of 2,
    # one call in each of 3 layers
    flops, byts = DENSE.KERNELS["paged_attention"](M, [3, 5])
    assert flops == 3 * 4 * 8 * 4 * 2      # 3 x 256
    kv = 8 * 2 * 2 * 2 * 2                 # tokens x kvh x d x (k, v) x 2 B
    q_out = 2 * 4 * 2 * 2 * 2              # lanes x h x d x (q, out) x 2 B
    assert byts == 3 * (kv + q_out) == 3 * (128 + 64)


def test_decode_and_prefill_model_flops():
    per_token = 2 * 8 * (2 * 4 * 2 + 2 * 2 * 2) + 2 * 3 * 8 * 16
    assert per_token == 1152
    # a lane at valid length 0: 3 layers of matmuls and one row of logits
    assert DENSE.decode_flops(M, [0]) == 3 * 1152 + 2 * 8 * 10
    # one lane at valid length 5: 3 layers of (matmuls + attention) + head
    assert DENSE.decode_flops(M, [5]) == 3 * (1152 + 4 * 5 * 4 * 2) + 160
    # positions 2..4 computed: keys 3 + 4 + 5 = 12
    assert DENSE.prefill_flops(M, 2, 5) == 3 * (
        3 * 1152 + 4 * 12 * 4 * 2) + 160
    assert DENSE.prefill_flops(M, 5, 5) == 0
