"""The shapes-based FLOP count against hand counts."""

import json
import os

import pytest

from perfbench import flops, manifest


def _config(name):
    with open(os.path.join(manifest.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_one_conv_by_hand():
    # 3x3, 4 -> 16 channels over 84x84, SAME: each of the 84*84*16
    # outputs takes 3*3*4 multiply-adds.
    assert flops.conv_flops(84, 84, 3, 4, 16) == 2 * (84 * 84 * 16) * 36
    assert flops.conv_flops(84, 84, 3, 4, 16) == 8_128_512


def test_lstm_step_by_hand():
    # Four gates, each [257] x [257, 256] and [256] x [256, 256].
    want = 2 * 4 * (257 * 256 + 256 * 256)
    assert flops.lstm_step_flops(257, 256) == want == 1_050_624


@pytest.mark.parametrize("size,want", [(84, 42), (42, 21), (21, 11)])
def test_pooled_sizes(size, want):
    assert flops._pooled(size) == want


def test_flagship_forward_parts():
    parts = flops.forward_flops_per_frame(_config("impala_deep_lstm"))
    # Stage fronts at 84, 42, 21; four residual convs at 42, 21, 11.
    trunk = (
        flops.conv_flops(42, 42, 3, 16, 32) + flops.conv_flops(21, 21, 3, 32, 32)
        + 4 * flops.conv_flops(42, 42, 3, 16, 16)
        + 4 * flops.conv_flops(21, 21, 3, 32, 32)
        + 4 * flops.conv_flops(11, 11, 3, 32, 32)
    )
    assert parts["first_conv"] == 8_128_512
    assert parts["trunk_convs"] == trunk
    assert parts["fc"] == 2 * 3872 * 256
    assert parts["core"] == 1_050_624
    assert parts["heads"] == 2 * 256 * 7


@pytest.mark.parametrize("name,tflop", [
    ("impala_deep_lstm", 2 * 0.8304021504),
    ("impala_deep_x4_lstm", 12.472825430016),
])
def test_train_flops_per_step(name, tflop):
    config = _config(name)
    parts = flops.forward_flops_per_frame(config)
    per_frame = 3 * sum(parts.values()) - parts["first_conv"]
    assert flops.train_flops_per_step(config) == (
        per_frame * 81 * config["batch_size"]
    )
    assert flops.train_flops_per_step(config) / 1e12 == pytest.approx(tflop)


def test_feed_forward_has_no_core():
    config = dict(_config("impala_deep_lstm"), use_lstm=False)
    assert flops.forward_flops_per_frame(config)["core"] == 0
