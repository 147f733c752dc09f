"""The FLOP and byte functions against hand-worked values at GPT-2 small
and medium shapes."""

from benchmarks.harness import flops
from benchmarks.harness.peaks import peaks_for

SMALL = dict(n_layer=12, n_head=12, n_embd=768, n_positions=1024, vocab_size=50257)
MEDIUM = dict(n_layer=24, n_head=16, n_embd=1024, n_positions=1024, vocab_size=50257)


def test_param_counts_are_the_published_ones():
    assert flops.gpt2_param_count(SMALL) == 124_439_808
    assert flops.gpt2_param_count(MEDIUM) == 354_823_168


def test_train_flops_per_sample_medium():
    # forward, one 1024-token row: blocks 24*D^2*L*T, causal attention
    # 4*D*L*(T*(T+1)/2), tied head 2*D*V*T; backward twice that.
    dense = 24 * 1024 * 1024 * 24 * 1024
    attn = 4 * 1024 * 24 * (1024 * 1025 // 2)
    head = 2 * 1024 * 50257 * 1024
    assert dense == 618_475_290_624 and attn == 51_589_939_200
    want = 3 * (dense + attn + head)
    assert flops.gpt2_train_flops_per_sample(MEDIUM, 1024) == want
    assert 2.2e9 < want / 1024 < 2.4e9  # "about 2.3 GFLOP a token"


def test_forward_flops_of_one_decode_token_small():
    # one token attending to 300 keys, head at that one position
    got = flops.gpt2_forward_flops(SMALL, 1, 300, 1)
    assert got == 24 * 768 * 768 * 12 + 4 * 768 * 12 * 300 + 2 * 768 * 50257


def test_causal_ctx_sum():
    assert flops.causal_ctx_sum(4) == 1 + 2 + 3 + 4
    assert flops.causal_ctx_sum(6, start=4) == 5 + 6


def test_attention_work_medium_batch8():
    w = flops.attention_train_work(MEDIUM, 8, 1024)
    pairs = 8 * (1024 * 1025 // 2)
    assert w["flops"] == 6 * 2 * 1024 * pairs * 24
    assert w["bytes"] == 12 * 8 * 1024 * 1024 * 2 * 24
    least = flops.roofline_seconds(w["flops"], w["bytes"], peaks_for("TPU v5 lite"))
    # 1.24 TFLOP -> 6.3 ms at 197 TFLOP/s; 4.8 GB -> 5.9 ms at 819 GB/s
    assert least["bound"] == "compute"
    assert abs(least["compute_s"] - 6.29e-3) < 5e-5
    assert abs(least["memory_s"] - 5.90e-3) < 5e-5


def test_decode_bytes_small():
    # weights without the position table, float32; K and V bfloat16
    weights = (124_439_808 - 1024 * 768) * 4
    kv = 2 * 12 * 768 * 2 * 33_000
    assert flops.gpt2_decode_bytes(SMALL, 33_000) == weights + kv
    assert 2 * 12 * 768 * 2 == 36_864  # bytes of K and V a token


def test_unknown_device_kind_is_an_error():
    import pytest

    with pytest.raises(ValueError):
        peaks_for("TPU v9 imaginary")
