"""The operation counts against hand counts (PERF.md, section 3)."""

import json
from pathlib import Path

import pytest

from perfbench import ops_count

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_resnet50_forward_is_4_09_gmacs_and_a_step_six_times_that():
    cfg = json.loads((CONFIGS / "resnet50.json").read_text())
    shapes = ops_count.resnet_conv_shapes(cfg)
    assert len(shapes) == 1 + 16 * 3 + 4 + 1      # stem, blocks, proj, fc
    assert shapes[0] == (112, 112, 49, 3, 64)
    assert shapes[1:5] == [(56, 56, 1, 64, 64), (56, 56, 9, 64, 64),
                           (56, 56, 1, 64, 256), (56, 56, 1, 64, 256)]
    assert shapes[-1] == (1, 1, 1, 2048, 1000)
    macs = sum(a * b * k * ci * co for a, b, k, ci, co in shapes)
    # by hand: stem 118.0 M; stages 667.9 + 1027.6 + 1464.3 + 809.2 M
    # (v1.5, projections included); classifier 2.0 M
    assert macs == pytest.approx(4.0892e9, rel=1e-4)
    assert ops_count.resnet_train_flops_per_image(cfg) == 6 * macs


def test_lm_step_per_token():
    sizes = {"d_model": 2048, "d_ff": 8192, "vocab_size": 50304,
             "n_layers": 8}
    # by hand: a layer's matmuls 4*2048^2 + 3*2048*8192 = 67.11 M weights,
    # eight layers 536.9 M, the tied output projection 103.0 M: 639.9 M,
    # times 6 = 3.8394 GFLOP; causal attention 8 layers * 6 * 2048 * 2048
    # = 0.2013 GFLOP
    assert ops_count.lm_train_flops_per_token(sizes, 2048) == \
        pytest.approx(3.8394e9 + 0.2013e9, rel=1e-3)


def test_attention_kernel_needs():
    need = ops_count.flash_attention_needed(8, 16, 2048, 128, 8)
    # by hand: one causal S x S x D matmul of one head 2*2048^2*128/2 =
    # 0.5369 GFLOP; seven of them, 16 heads, 8 rows, 8 layers: 3.848 TFLOP
    assert need["ops"] == pytest.approx(3.848e12, rel=1e-3)
    # a [8,2048,16,128] bf16 tensor is 67.1 MB; twelve passes, 8 layers
    assert need["bytes"] == pytest.approx(12 * 8 * 67.109e6, rel=1e-3)
