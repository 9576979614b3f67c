"""Each cost function against a count made by hand."""

import pytest

from benchmarks import costs


def test_conv_flops():
    # 2 x 2 outputs, 3 x 3 window, 4 -> 5 channels: 4*9*4*5 multiply-adds
    assert costs.conv_flops(2, 2, 3, 3, 4, 5) == 2 * 720


def test_resnet50_is_the_published_four_gigamacs():
    flops = costs.resnet_flops_per_image([3, 4, 6, 3], 64, 1000, 224)
    # He et al. 2015 give 3.8e9 multiply-adds for the 50-layer net with the
    # stride on the first 1x1; with it on the 3x3 (every ONNX export) 4.1e9
    assert flops / 2 == pytest.approx(4.09e9, rel=0.01)


def test_one_block_net_by_hand():
    # stem 7x7x3x8 at 16x16, pool to 8x8, one block 8 -> 8 -> 32 with a
    # projection 8 -> 32, all at 8x8, head 32 -> 10
    stem = 2 * 16 * 16 * 49 * 3 * 8
    block = 2 * 64 * (8 * 8 + 9 * 8 * 8 + 8 * 32 + 8 * 32)
    assert costs.resnet_flops_per_image([1], 8, 10, 32) \
        == stem + block + 2 * 32 * 10


def test_kv_bytes():
    # gpt2-xl: 48 layers x (K and V) x 1600 x 2 bytes = 307,200 a position
    assert costs.kv_bytes_per_token(48, 1600, 2) == 307200
    assert costs.paged_attention_bytes(1000, 48, 1600, 2) == 307200000


def test_histogram_bytes():
    # 1M rows x (28 one-byte bins + gradient and hessian in float32)
    assert costs.histogram_bytes(1_000_000, 28, 2) == 36_000_000


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.roofline_seconds(1000, 10, peak) == (10.0, "compute")
    assert costs.roofline_seconds(10, 1000, peak) == (100.0, "memory")
