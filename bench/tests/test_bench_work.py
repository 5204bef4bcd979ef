"""Required-work counts at smoke sizes, against counts worked by hand."""

import pytest

from bench.work import ivim as ivim_work
from bench.work import lm as lm_work

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}

IVIM = {"sub_networks": 4, "width": 11, "n_masks": 4, "mask_scale": 2.0}
LM = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
      "head_dim": 4, "num_hidden_layers": 2, "vocab_size": 10,
      "intermediate_size": 16, "mask_samples": 4, "mask_scale": 2.0}


def test_ivim_scan_work():
    # keep = round(11 / (2 * (1 - 0.5**4))) = round(5.87) = 6
    assert ivim_work.kept(IVIM) == (6, 6)
    # per voxel: 4 sub-networks x 4 masks x 2 x (11*6 + 6*6 + 6) = 3456
    assert ivim_work.flops(IVIM, 10) == 34560
    # 4 bytes x (11 in + 2 x 4 out) per voxel
    assert ivim_work.io_bytes(IVIM, 10) == 4 * 10 * 19
    # 16 rows x (11*6 + 6 + 6*6 + 6 + 6 + 1) floats
    assert ivim_work.weight_bytes(IVIM) == 4 * 16 * 121
    t = ivim_work.seconds(IVIM, 10, PEAKS)
    assert t == pytest.approx((760 + 7744) / 819e9)


def test_lm_weights_and_kv():
    # per layer: qkv 8*(2+2)*4 = 128, bias 16, o 8*8 = 64, ffn 3*8*16 = 384,
    # norms 16 -> 608; two layers 1216, embedding 80, final norm 8
    assert lm_work.weight_bytes(LM) == 2 * (1216 + 80 + 8)
    # 2 layers x (k, v) x 1 head x 4 x 2 bytes
    assert lm_work.kv_bytes_per_position(LM) == 32


def test_lm_decode_step():
    # keep = round(16 / 1.875) = 9; per row-token: 2 layers x (2*8*16 +
    # 2*8*8 + 6*8*9) = 2 x 816 = 1632, head 2*8*10 = 160
    assert lm_work.kept_width(LM) == 9
    flops, nbytes = lm_work.decode_step(LM, rows=4, attended=40)
    # attention: 2 layers x 4 x 2 heads x 4 x 40 = 2560
    assert flops == 4 * (1632 + 160) + 2560
    assert nbytes == 2 * 1304 + 32 * (40 + 4)


def test_lm_prefill_counts_the_true_length():
    flops, nbytes = lm_work.prefill(LM, 5)
    # 4 masks x (5 x 1632 + 2 x 4 x 2 x 4 x 15 + 160)
    assert flops == 4 * (5 * 1632 + 960 + 160)
    assert nbytes == 2 * 1304 + 32 * 4 * 5
    assert lm_work.seconds((197e12, 0.0), PEAKS) == pytest.approx(1.0)
