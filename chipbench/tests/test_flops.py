"""The operation and byte counts, against counts made by hand."""
import sizes  # noqa: F401  (puts the repository on the path)

from chipbench import flops

GPT2 = {"n_layers": 20, "d_model": 768, "n_heads": 12, "n_kv_heads": 12,
        "head_dim": 0, "d_ff": 3072, "vocab_size": 50304, "act": "gelu"}
QWEN3 = {"n_layers": 28, "d_model": 2048, "n_heads": 16, "n_kv_heads": 8,
         "head_dim": 128, "d_ff": 6144, "vocab_size": 151936,
         "act": "silu"}


def test_gpt2_train_flops_per_token():
    # per layer: q, k, v, o 4 * 768^2 weights, MLP 2 * 768 * 3072 weights,
    # 2 flops per weight; attention 4 * 768 per key, 512.5 keys on average
    # at seq 1024 (causal); head 2 * 768 * 50304; x3 for the backward
    per_layer = 2 * 4 * 768 * 768 + 2 * 2 * 768 * 3072 + 4 * 768 * 512.5
    fwd = 20 * per_layer + 2 * 768 * 50304
    assert flops.train_flops_per_token(GPT2, 1024) == 3 * fwd
    assert round(3 * fwd / 1e6) == 1176


def test_qwen3_served_flops():
    # per layer: q 2048x2048, k and v 2048x1024 each, o 2048x2048, MLP three
    # 2048x6144 matrices; a 4-token prompt attends 1+2+3+4 keys, then 2
    # decoded tokens attend 5 and 6; logits for the first token and the 2
    dense = 2 * (2048 * 2048 * 2 + 2048 * 1024 * 2) + 2 * 3 * 2048 * 6144
    att = 4 * 16 * 128
    want = (28 * (dense * 6 + att * (1 + 2 + 3 + 4 + 5 + 6))
            + 3 * 2 * 2048 * 151936)
    # as the engine's calls: a prefill of 4 (an idle slot beside it), then
    # two decodes
    calls = (flops.serve_call_flops(QWEN3, [0, 9], [4, 0])
             + flops.serve_call_flops(QWEN3, [4], [1])
             + flops.serve_call_flops(QWEN3, [5], [1]))
    assert calls == want


def test_paged_attention_bytes_per_wave():
    # a decode wave: slot 0 holds 100 tokens, slot 1 30, slot 2 idle; K and
    # V of the live tokens (101 and 31 with the new one) in bf16 over 8 kv
    # heads of 128, plus q and out (16 heads of 128, bf16) per new token
    flo, byt = flops.paged_attn_call(QWEN3, [100, 30, 7], [1, 1, 0])
    assert byt == (101 + 31) * 8 * 128 * 2 * 2 + 2 * 2 * 16 * 128 * 2
    assert flo == 4 * 16 * 128 * (101 + 31)
    t, bound = flops.roofline_s(flo, byt, {"bf16_flops_per_s": 197e12,
                                           "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and t == byt / 819e9
