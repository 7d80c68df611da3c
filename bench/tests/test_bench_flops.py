"""``bench/flops.py`` against a count by hand, and the peaks table."""

import json

import pytest

from bench import flops, harness

QWEN = json.loads((harness.BENCH / "configs" /
                   "qwen1_5_0_5b_train.json").read_text())


def test_qwen1_5_0_5b_by_hand():
    # per layer: q, k, v, o of 1024 x 1024 and three MLP matrices of
    # 1024 x 2816; output head 152,064 x 1024 (the padded vocabulary)
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert per_layer == 12_845_056
    n = 24 * per_layer + 152_064 * 1024
    assert flops.matmul_params(QWEN) == n == 463_994_880
    # attention at seq 2048: 24 layers x 2 products x 2 flops x 1024.5
    # keys on average x 16 heads x 64
    attn = 24 * 2 * 2 * 1024.5 * 1024
    assert flops.attention_flops_fwd(QWEN, 2048) == attn == 100_712_448
    assert flops.train_flops_per_token(QWEN, 2048) == 3 * (2 * n + attn) \
        == 3_086_106_624


def test_attention_grows_with_context():
    short = flops.train_flops_per_token(QWEN, 512)
    long = flops.train_flops_per_token(QWEN, 2048)
    assert long - short == pytest.approx(3 * 24 * 4 * 1024 * (2049 - 513) / 2)


def test_peaks_of_a_v5e():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5"])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks(kind)
