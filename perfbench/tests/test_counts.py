"""Each count function against a hand count at one shape."""
from perfbench.counts import lm_flops, paged_prefill

CFG = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
       "vocab_size": 10, "hidden_act": "silu"}


def test_matmul_params():
    # per layer: q 8*4*2=64, k and v 8*2*2=32 each, o 4*2*8=64; mlp 3*8*16
    assert lm_flops.layer_matmul_params(CFG) == 2 * (64 + 32 + 32 + 64 + 384)
    assert lm_flops.head_params(CFG) == 80
    gelu = dict(CFG, hidden_act="gelu")
    assert lm_flops.layer_matmul_params(gelu) == 2 * (192 + 256)


def test_train_flops_per_token():
    n = 2 * 576 + 80
    # 6N + 12 * L * H * Dh * S
    assert lm_flops.train_flops_per_token(CFG, 5) == 6 * n + 12 * 2 * 4 * 2 * 5


def test_prefill_flops():
    lay = 2 * 576
    # 3 prompt tokens attend 1+2+3 keys; the head once
    assert lm_flops.prefill_flops(CFG, 3) == 2 * lay * 3 + 4 * 2 * 4 * 2 * 6 + 160
    # one token: its own key, and the head once
    assert lm_flops.prefill_flops(CFG, 1) == 2 * lay + 4 * 2 * 4 * 2 + 160


def test_paged_prefill_counts():
    # 3 queries after 4 prefix tokens: 3*4 + (1+2+3) = 18 pairs
    assert paged_prefill.flops(CFG, 4, 3) == 4 * 4 * 2 * 18
    assert paged_prefill.bytes_moved(CFG, 4, 3) == (2 * 2 * 2 * 7 + 2 * 4 * 2 * 3) * 2
