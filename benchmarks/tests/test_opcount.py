import pytest

from benchmarks.arch import dense_gqa
from benchmarks.arch.dense_gqa import Dims
from benchmarks.lib import opcount

SMALL = Dims(d=8, layers=2, heads=4, kv_heads=2, head_dim=4, d_ff=16,
             vocab=32, rope_theta=1e4, window=0, norm_eps=1e-5)


def test_matmul_count_by_hand():
    # q 8x16, k 8x8, v 8x8, o 16x8 = 384; gate, up, down 3 x 8x16 = 384
    assert dense_gqa.layer_matmul_params(SMALL) == 768
    # 3 tokens, full causal: keys seen 1 + 2 + 3 = 6
    # body 2 * 2 layers * 768 * 3 = 9216; attention 2 layers * 4*4*4*6 = 768
    # head 2 * 8 * 32 * 3 = 1536
    assert dense_gqa.forward_flops(SMALL, 0, 3, 3) == 9216 + 768 + 1536
    assert dense_gqa.train_flops_per_token(SMALL, 3) == 3 * (9216 + 768 + 1536) / 3


def test_window_is_honoured():
    import dataclasses

    w = dataclasses.replace(SMALL, window=2)
    # positions 0..3 see 1, 2, 2, 2 keys
    assert opcount.visible_keys_sum(0, 4, 2) == 7
    assert [opcount.visible_keys(p, 2) for p in range(4)] == [1, 2, 2, 2]
    assert dense_gqa.forward_flops(w, 0, 4, 0) < dense_gqa.forward_flops(SMALL, 0, 4, 0)
    # Mistral-7B at 8,192 with a 4,096 window: 3,072.25 keys a query
    assert opcount.visible_keys_sum(0, 8192, 4096) / 8192 == 3072.25
    # and a span is the difference of two prefixes
    assert (opcount.visible_keys_sum(3, 9, 4)
            == sum(opcount.visible_keys(p, 4) for p in range(3, 9)))


def test_mistral_7b_layer_is_218m():
    d = Dims(d=4096, layers=8, heads=32, kv_heads=8, head_dim=128,
             d_ff=14336, vocab=32000, rope_theta=1e4, window=4096,
             norm_eps=1e-5)
    assert dense_gqa.layer_matmul_params(d) == 218_103_808
    assert round(dense_gqa.train_flops_per_token(d, 8192) / 1e9, 2) == 12.46


def test_request_and_decode_bytes():
    # prompt 3, 2 new tokens: positions 0..3 run (the last token is not
    # fed back), the head twice
    assert (dense_gqa.request_flops(SMALL, 3, 2)
            == dense_gqa.forward_flops(SMALL, 0, 4, 2))
    # one decode tick after a prompt of 3 reads 4 positions (its own too)
    assert dense_gqa.decode_kv_bytes(SMALL, 3, 2) == dense_gqa.kv_bytes(SMALL, 4)
    assert dense_gqa.kv_bytes(SMALL, 1) == 2 * 2 * 2 * 4 * 2
    assert dense_gqa.weight_bytes(SMALL, 1) == 2 * 768 + 8 * 32


def test_unknown_device_kind_raises():
    assert opcount.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        opcount.peaks("cpu")
    with pytest.raises(KeyError):
        opcount.peaks("TPU v9")
