"""Counts of operations and bytes, checked against hand counts."""
import pytest

from bench import counts

EUROSAT = (64, 64, 3)


def test_cnn_forward_flops_by_hand():
    # conv1: 32*32 outputs x 16 ch x 27 MACs; conv2: 16*16 x 32 x 144;
    # dense 8192 x 128; classifier 128 x 10; 2 FLOPs per MAC
    hand = 2 * (32 * 32 * 16 * 27 + 16 * 16 * 32 * 144 + 8192 * 128
                + 128 * 10)
    assert hand == 5_343_744
    assert counts.cnn_forward_flops(EUROSAT, 16, 10) == hand


def test_cnn_train_flops_leave_out_the_input_gradient():
    fwd, conv1 = 5_343_744, 2 * 32 * 32 * 16 * 27
    assert counts.cnn_train_flops(EUROSAT, 16, 10) == 3 * fwd - conv1


def test_cnn_params_by_hand():
    hand = (27 * 16 + 16) + (144 * 32 + 32) + (8192 * 128 + 128) \
        + (128 * 10 + 10)
    assert counts.cnn_params(EUROSAT, 16, 10) == (hand, 8)
    assert hand == 1_055_082


def test_quant_agg_need_by_hand():
    ops, nbytes = counts.quant_agg_need(50, 1_055_082, 8, 8)
    assert ops == 2 * 50 * 1_055_082
    assert nbytes == 50 * 1_055_082 + 50 * 8 * 4 + 1_055_082 * 4


def test_least_time_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time_s(1000.0, 50.0, peak) == 10.0
    assert counts.least_time_s(100.0, 50.0, peak) == 5.0


def test_mamba2_1p3b_train_flops_by_hand():
    # per layer: in_proj 2048 x (2*4096 + 2*128 + 64), out_proj 4096 x 2048,
    # depthwise conv (4096 + 256) x 4; SSD per token: C B^T 2*256*128,
    # (L o C B^T) X 2*256*64 per head, states and read-out 2*128*64 each
    proj = 2048 * (8192 + 256 + 64) + 4096 * 2048
    conv = (4096 + 256) * 4
    ssd = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 2 * 2 * 128 * 64)
    hand = 48 * (6 * (proj + conv) + 3 * ssd) + 6 * 50280 * 2048
    assert hand == 8_672_772_096
    assert counts.mamba2_train_flops_per_token(
        d_model=2048, n_layers=48, vocab=50280, d_state=128, head_dim=64,
        expand=2, n_groups=1, conv_width=4, chunk=256) == hand


def test_v5e_peaks_and_unknown_device_kind(tmp_path):
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["int8_ops_per_s"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in peak["source"]
    with pytest.raises(KeyError, match="TPU v9"):
        counts.peaks("TPU v9")
