"""FLOP and byte counts of one call of each kernel, against values worked
by hand, and the model FLOPs per token."""
import pytest

from bench.counts import kernels, model


def test_causal_pairs():
    assert kernels.causal_pairs(4, 0) == 1 + 2 + 3 + 4
    assert kernels.causal_pairs(5, 2) == 1 + 2 + 2 + 2 + 2
    assert kernels.causal_pairs(3, 8) == kernels.causal_pairs(3, 0) == 6


def test_decode_attention_one_call():
    # 2 rows, 4 query heads over 2 kv heads, head_dim 8, 10 valid positions
    flops, nbytes = kernels.decode_attention(2, 4, 2, 8, 10)
    assert flops == 2 * (2 * 4 * 8 * 10) * 2      # QK^T and PV, 2 per MAC
    kv = 2 * (2 * 2 * 10 * 8) * 2                 # K and V, bf16
    qo = 2 * (2 * 4 * 8) * 2                      # q in, out
    assert nbytes == kv + qo == 1536


def test_decode_attention_counts_valid_length_not_cache():
    short = kernels.decode_attention(1, 9, 3, 64, 129)
    longer = kernels.decode_attention(1, 9, 3, 64, 257)
    assert longer[0] > short[0] and longer[1] > short[1]


def test_swa_prefill_one_call():
    # 1 prompt of 4 tokens, 2 heads over 1 kv head, head_dim 4, full causal
    flops, nbytes = kernels.swa_prefill(1, 2, 1, 4, 4, 0)
    assert flops == 4 * 2 * 4 * 10 == 320
    assert nbytes == 2 * (2 * 4 * 4) * 2 + 2 * (1 * 4 * 4) * 2 == 192
    # a window of 2 keeps 7 of the 10 causal pairs
    assert kernels.swa_prefill(1, 2, 1, 4, 4, 2)[0] == 4 * 2 * 4 * 7


def test_model_flops_per_token(tiny):
    cfg = dict(tiny, sliding_window=None)
    layer = 64 * 64 * 2 + 64 * 32 * 2 + 3 * 64 * 128
    assert model.layer_matmul_params(cfg) == layer
    assert model.decode_flops(cfg, 9) == pytest.approx(
        2 * 2 * layer + 4 * 2 * 4 * 16 * 10 + 2 * 64 * 256)
    assert model.prefill_flops(cfg, 4) == pytest.approx(
        2 * 2 * layer * 4 + 4 * 2 * 4 * 16 * 10 + 2 * 64 * 256)
