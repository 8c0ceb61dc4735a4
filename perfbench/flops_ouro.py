"""The operations and the least bytes one update of the Ouro looped-block
policy needs, from the configuration's shapes.

One multiply-add is two operations. Both counts are the algorithm's and
both are lower bounds, as `flops_olmoe.py`'s are: nothing for norms,
RoPE, softmax, the exit gates or the losses, nothing for whatever the
compiler emitted (a rematerialised block's second forward pass among
it). A share of a peak computed from them that reads over 100% therefore
means a wrong count.

Operations of the forward pass for one token (one step of one row):

    projection  2 x (frame elements) x d: the flat frame times a matrix
    extras      2 x (1 + actions) x d
    per APPLICATION of a layer, of which there are total_ut_steps x
    num_hidden_layers (the same weights do the work of every pass):
      qkvo      four matrices of d x (heads x head size)
      attention scores and the weighted sum of values over the keys
                inside the band (`flops_olmoe.band_keys`): 2 x 2 x keys
                x heads x head size
      mlp       gate, up and down: 3 x 2 x d x intermediate_size
    heads       2 x d x (actions + 1)

The backward pass is twice the forward (gradient with respect to the
input and to the weights), except for the projection, whose input is the
uint8 frame: it has a weight gradient and no input gradient.

Bytes: a layer's float32 weights are read once in every pass forward and
once in every pass backward (2 x total_ut_steps reads: 1.6 GB of them
outlast any on-chip memory between two passes), every other weight once
each way; then the optimizer reads and writes every weight and RMSprop's
second moment (four passes over all of them). Activations, caches,
gradients and the batch are left out: they are not a floor.
"""

from typing import Dict

from perfbench.flops_mellum2 import _frame
from perfbench.flops_olmoe import band_keys


def applications(config: Dict) -> int:
    """Layer applications a forward pass makes: passes x layers."""
    return config["total_ut_steps"] * config["num_hidden_layers"]


def forward_flops_per_step(config: Dict) -> Dict[str, int]:
    """Forward operations of one [T+1, B] batch, by part."""
    d, actions = config["hidden_size"], config["num_actions"]
    q_width = config["num_attention_heads"] * config["head_dim"]
    steps, rows = config["unroll_length"] + 1, config["batch_size"]
    tokens, applied = steps * rows, applications(config)
    return {
        "projection": tokens * 2 * _frame(config) * d,
        "extras": tokens * 2 * (1 + actions) * d,
        "qkvo": applied * tokens * 2 * d * 4 * q_width,
        "attention": (
            applied * rows * band_keys(steps, config["memory_len"])
            * 4 * q_width
        ),
        "mlp": applied * tokens * 3 * 2 * d * config["intermediate_size"],
        "heads": tokens * 2 * d * (actions + 1),
    }


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update."""
    parts = forward_flops_per_step(config)
    return 3 * sum(parts.values()) - parts["projection"]


def layer_param_count(config: Dict) -> int:
    d = config["hidden_size"]
    q_width = config["num_attention_heads"] * config["head_dim"]
    return (
        4 * d * q_width  # q, k, v, o, no bias
        + 3 * d * config["intermediate_size"]  # gate, up, down
        + 4 * d  # the four norms
    )


def param_count(config: Dict) -> int:
    d, actions = config["hidden_size"], config["num_actions"]
    return (
        _frame(config) * d + d  # projection
        + (1 + actions) * d + d  # extras
        + config["num_hidden_layers"] * layer_param_count(config)
        + d  # the norm after every pass
        + d + 1  # exit gate
        + d * (actions + 1) + actions + 1  # heads
    )


def least_bytes_per_step(config: Dict) -> int:
    looped = config["num_hidden_layers"] * layer_param_count(config)
    once = param_count(config) - looped
    return 4 * (
        2 * config["total_ut_steps"] * looped + 2 * once
        + 4 * param_count(config)
    )
