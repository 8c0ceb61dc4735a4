"""The operations the IMPALA ResNet+LSTM update needs, from its shapes.

One multiply-add is two operations. The count is the algorithm's: what
the forward pass and its gradient require, with nothing for
rematerialised stages and nothing for whatever the compiler emitted.
The backward pass is twice the forward (gradient with respect to the
input and to the weights), except that the first convolution has no
input gradient to form: its input is the uint8 frame.
"""

from typing import Dict, Sequence


def conv_flops(height: int, width: int, kernel: int, c_in: int,
               c_out: int) -> int:
    """One stride-1 SAME convolution over one image."""
    return 2 * height * width * kernel * kernel * c_in * c_out


def lstm_step_flops(input_size: int, hidden_size: int) -> int:
    """One step of one LSTM layer for one row: four gates, each an
    input and a hidden projection."""
    return 2 * 4 * hidden_size * (input_size + hidden_size)


def _pooled(size: int) -> int:
    return (size + 1) // 2  # 3x3 stride-2 max pool, padding 1


def forward_flops_per_frame(config: Dict) -> Dict[str, int]:
    """Forward operations for one frame, by part of the network."""
    height, width, c_in = config["frame_shape"]
    channels: Sequence[int] = config["trunk_channels"]
    parts = {"first_conv": 0, "trunk_convs": 0}
    for stage, c_out in enumerate(channels):
        front = conv_flops(height, width, 3, c_in, c_out)
        parts["first_conv" if stage == 0 else "trunk_convs"] += front
        height, width = _pooled(height), _pooled(width)
        parts["trunk_convs"] += 4 * conv_flops(height, width, 3, c_out, c_out)
        c_in = c_out
    hidden = config["hidden_size"]
    parts["fc"] = 2 * height * width * c_in * hidden
    parts["core"] = (
        lstm_step_flops(hidden + 1, hidden) if config["use_lstm"] else 0
    )
    parts["heads"] = 2 * hidden * (config["num_actions"] + 1)
    return parts


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update: the batch is
    [unroll_length + 1, batch_size] frames."""
    parts = forward_flops_per_frame(config)
    per_frame = 3 * sum(parts.values()) - parts["first_conv"]
    return per_frame * (config["unroll_length"] + 1) * config["batch_size"]
