"""Model FLOPs against hand counts, and the peaks table."""
import json

import pytest

from perfbench import flops, peaks
from perfbench.tests.tiny import BENCH


def test_mamba2_2p7b_five_layers_hand_count():
    model = json.loads((BENCH / "configs/mamba2-2.7b-zoo.json").read_text()
                       )["model"]
    # in_proj 2560 -> 2*5120 + 2*1*128 + 80 = 10576; out_proj 5120 -> 2560
    proj = 2 * 2560 * 10576 + 2 * 5120 * 2560          # 80,363,520
    # 80 heads, chunk 256, d_state 128, head_dim 64
    ssd = 80 * (2 * 256 * 128 + 2 * 256 * 64 + 4 * 128 * 64)  # 10,485,760
    # the tied head over the 50,288 padded rows
    head = 2 * 2560 * 50288                              # 257,474,560
    want = 3 * (5 * (proj + ssd) + head)
    assert want == 2_135_162_880
    assert flops.mamba2_per_token(model, 2048) == want


def test_mamba2_short_sequence_shrinks_the_chunk():
    model = json.loads((BENCH / "configs/mamba2-2.7b-zoo.json").read_text()
                       )["model"]
    full = flops.mamba2_per_token(model, 2048)
    short = flops.mamba2_per_token(model, 128)
    # only the SSD terms with the chunk Q change: Q 256 -> 128
    assert full - short == 3 * 5 * 80 * (2 * 128 * 128 + 2 * 128 * 64)


def test_mnist_mlp_per_sample():
    assert flops.mlp_per_sample([784, 64, 10]) == 6 * (784 * 64 + 64 * 10)
    assert flops.mlp_per_sample([784, 64, 10]) == 304_896


def test_peaks_known_and_unknown_kind():
    assert peaks.peak("TPU v5 lite").bf16_flops == 197e12
    assert peaks.peak("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v99")
