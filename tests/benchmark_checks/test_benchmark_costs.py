"""The operation and byte counts against hand-worked numbers, and the
table of peaks."""
import json
import os

import pytest

from benchmark import costs, device

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    path = os.path.join(REPO, "benchmark", "configs", name + ".json")
    with open(path, encoding="utf8") as file:
        return json.load(file)


def test_vit_large_forward_is_123_gflop_an_image():
    config = _config("vit-large-patch16-224")
    assert costs.vit_positions(config) == 197
    # by hand: a block is 197 x 1024 x (3 x 1024 + 1024 + 2 x 4096) x 2
    # = 4.957 GFLOP of projections and MLP, plus 2 x 2 x 197^2 x 1024
    # = 0.159 GFLOP of attention; 24 blocks; the patch embedding is
    # 2 x 196 x 768 x 1024 = 0.308 GFLOP and the head 2 MFLOP
    block = 2 * 197 * 1024 * (4 * 1024 + 2 * 4096) + 4 * 197 * 197 * 1024
    total = 24 * block + 2 * 196 * 768 * 1024 + 2 * 1024 * 1000
    assert costs.vit_forward_flops(config) == total
    assert costs.vit_forward_flops(config) == pytest.approx(123.1e9, rel=1e-3)


def test_gpt2_medium_cache_is_98304_bytes_a_token():
    config = _config("gpt2-medium")
    assert costs.gpt2_cache_bytes_per_token(config) == 98304
    assert costs.gpt2_cache_bytes_per_token(config) * 1024 \
        == pytest.approx(100e6, rel=0.01)          # 100 MB a full row


def test_gpt2_medium_step_reads_its_weights_once_and_each_live_cache():
    config = _config("gpt2-medium")
    # by hand: 24 x (3 + 1 + 8) x 1024^2 block weights and the 50257 x 1024
    # tied head: 353.5 M weights, 0.707 GB in bfloat16
    weights = 24 * 12 * 1024 * 1024 + 50257 * 1024
    assert costs.gpt2_matmul_params(config) == weights
    assert weights * 2 == pytest.approx(0.707e9, rel=1e-3)
    one = costs.gpt2_decode_step_bytes(config, rows=1, live_positions=0)
    assert one == weights * 2
    full = costs.gpt2_decode_step_bytes(config, rows=64, live_positions=384)
    assert full == weights * 2 + 64 * 384 * 98304
    assert costs.gpt2_decode_step_flops(config, 1, 0) == 2 * weights


def test_the_v5e_peaks_are_the_published_ones():
    peaks = device.peaks_for("TPU v5 lite")
    assert peaks == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                     "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                     "ici_bits_per_s": 1600e9}


def test_an_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks_for("cpu")


@pytest.mark.parametrize("stamp,chips,ok", [
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1, True),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 4}, 4, True),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 4, False),
    ({"platform": "cpu", "kind": "cpu", "count": 8}, 1, False),
])
def test_a_run_needs_a_tpu_and_the_cells_chips(stamp, chips, ok):
    if ok:
        device.require(stamp, chips)
    else:
        with pytest.raises(device.NoAccelerator):
            device.require(stamp, chips)
