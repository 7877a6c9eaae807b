"""flops.py and peaks.json against hand-worked values."""

import json
import os

import pytest

from conftest import BENCH

import flops


def test_transformer_base_per_token():
    # per layer 4 d^2 + 2 d d_ff = 4*512^2 + 2*512*2048 = 3,145,728;
    # six layers 18,874,368; output projection 512*32000 = 16,384,000
    n = flops.transformer_matmul_params(512, 6, 2048, 32000)
    assert n == 18_874_368 + 16_384_000 == 35_258_368
    # 6N + 12 L d s at seq 512, the full square
    full = flops.transformer_train_flops_per_token(n, 512, 6, 512, False)
    assert full == 6 * 35_258_368 + 12 * 6 * 512 * 512 == 230_424_576
    # causal: half the attention term
    causal = flops.transformer_train_flops_per_token(n, 512, 6, 512, True)
    assert causal == 6 * 35_258_368 + 6 * 6 * 512 * 512 == 220_987_392
    # at seq 8192 attention is 151 M of 362.5 M FLOPs a token
    long = flops.transformer_train_flops_per_token(n, 512, 6, 8192, True)
    assert long == 211_550_208 + 150_994_944


def test_resnet50_is_8_2_gflop_an_image():
    fwd = flops.resnet_forward_flops_per_image(50, 224, 1000)
    # 4.09 G multiply-adds, the figure every ResNet-50 table quotes
    assert fwd == pytest.approx(8.2e9, rel=0.01)
    assert flops.resnet_train_flops_per_image(50, 224, 1000) == 3 * fwd
    # ResNet-18: 1.81 G multiply-adds
    assert flops.resnet_forward_flops_per_image(18, 224, 1000) == \
        pytest.approx(3.63e9, rel=0.01)
    # the stem alone, by hand: 112^2 outputs x 64 filters x 3*7*7 taps
    assert 2 * 112 * 112 * 64 * 147 == 236_027_904


def test_flash_attention_work():
    b, h, t, d = 4, 8, 8192, 64
    # causal forward: 2 B H T^2 d
    fwd = flops.flash_attention_flops(b, h, t, t, d, causal=True)
    assert fwd == 2 * b * h * t * t * d == 274_877_906_944
    # full square: QK^T and PV, 2 B H T^2 d each
    assert flops.flash_attention_flops(b, h, t, t, d, causal=False) == \
        4 * b * h * t * t * d
    # backward is twice the forward; recomputed scores are not counted
    assert flops.flash_attention_flops(b, h, t, t, d, causal=True,
                                       backward=True) == 2 * fwd
    # a six-layer step: 6 x 3 x forward = 4.95e12
    step, nbytes = flops.transformer_flash_step(b, h, t, d, 6)
    assert step == 18 * fwd == pytest.approx(4.95e12, rel=0.001)
    # bytes: forward q,k,v,o and backward q,k,v,o,do,dq,dk,dv in bf16
    els = b * h * t * d
    assert nbytes == 6 * (4 + 8) * els * 2
    # per token and layer this is the 6 d s of the model's formula
    assert step / (b * t) / 6 == 6 * (h * d) * t


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(197e12, 819e9 / 2, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.roofline_seconds(197e12 / 4, 819e9, peak)
    assert (t, bound) == (1.0, "memory")


def test_peaks_table():
    path = os.path.join(BENCH, "peaks.json")
    table = json.load(open(path))
    assert "TPU v5e" in table["source"]
    v5e = flops.load_peaks(path, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    # a device the table does not know is an error, not a default
    with pytest.raises(KeyError, match="no published peaks"):
        flops.load_peaks(path, "cpu")
