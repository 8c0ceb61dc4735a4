"""The operations and the least bytes one update of the Mellum2-block
policy needs on this chip, from the configuration's shapes.

One multiply-add is two operations. Both counts are the algorithm's and
both are lower bounds, as `flops_olmoe.py`'s are: nothing for the sort
and the gathers of the dispatch, nothing for norms, RoPE, softmax or the
losses, nothing for whatever the compiler emitted (a rematerialised
block's second forward pass among it). A share of a peak computed from
them that reads over 100% therefore means a wrong count.

Operations of the forward pass for one token (one step of one row):

    projection  2 x (frame elements) x d: the flat frame times a matrix
    extras      2 x (1 + actions) x d
    per layer:
      qkvo      q and o are d x (heads x head size), k and v are
                d x (key/value heads x head size): four widths
      attention scores and the weighted sum of values over the keys
                inside the layer's band, for every QUERY head: 2 x 2 x
                keys x heads x head size. A sliding layer's band is its
                window, a full layer's its whole cache
                (`flops_olmoe.band_keys` with the kind's cache length)
      router    2 x d x the PUBLISHED number of experts: it routes over
                all of them
      experts   the experts HELD here: a token's experts_per_token
                assignments fall on them in the held / published share,
                on average (2 of 8 for 16 of 64), each 3 matrices of
                d x width x 2
    heads       2 x d x (actions + 1)

The backward pass is twice the forward (gradient with respect to the
input and to the weights), except for the projection, whose input is the
uint8 frame: it has a weight gradient and no input gradient.

Bytes: six passes over 4 bytes of every parameter HELD (forward,
backward, the optimizer's read and write of weight and second moment),
as `flops_olmoe.least_bytes_per_step`.
"""

from typing import Dict

from perfbench.flops_olmoe import band_keys

FULL = "full_attention"


def _frame(config: Dict) -> int:
    frame = 1
    for size in config["frame_shape"]:
        frame *= size
    return frame


def cache_lens(config: Dict):
    """Each layer's cache length, by its kind."""
    sliding = min(config["memory_len"], config["sliding_window"] - 1)
    return [
        config["memory_len"] if kind == FULL else sliding
        for kind in config["layer_types"][: config["num_hidden_layers"]]
    ]


def forward_flops_per_step(config: Dict) -> Dict[str, int]:
    """Forward operations of one [T+1, B] batch, by part."""
    d, actions = config["hidden_size"], config["num_actions"]
    q_width = config["num_attention_heads"] * config["head_dim"]
    kv_width = config["num_key_value_heads"] * config["head_dim"]
    steps, rows = config["unroll_length"] + 1, config["batch_size"]
    tokens, layers = steps * rows, config["num_hidden_layers"]
    return {
        "projection": tokens * 2 * _frame(config) * d,
        "extras": tokens * 2 * (1 + actions) * d,
        "qkvo": layers * tokens * 2 * d * (2 * q_width + 2 * kv_width),
        "attention": sum(
            rows * band_keys(steps, M) * 4 * q_width
            for M in cache_lens(config)
        ),
        "router": layers * tokens * 2 * d * config["published_num_experts"],
        # tokens x top-k x held / published is a whole number of
        # assignments at the cell's sizes (2,592 x 8 x 16 / 64 = 5,184).
        "experts": (
            layers * tokens * config["num_experts_per_tok"]
            * config["num_experts"] * 3 * 2 * d
            * config["moe_intermediate_size"]
        ) // config["published_num_experts"],
        "heads": tokens * 2 * d * (actions + 1),
    }


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update."""
    parts = forward_flops_per_step(config)
    return 3 * sum(parts.values()) - parts["projection"]


def param_count(config: Dict) -> int:
    """Parameters held on this chip."""
    d, actions = config["hidden_size"], config["num_actions"]
    head_dim = config["head_dim"]
    q_width = config["num_attention_heads"] * head_dim
    kv_width = config["num_key_value_heads"] * head_dim
    per_layer = (
        2 * d * q_width + 2 * d * kv_width  # q, o, k, v, no bias
        + 2 * d + 2 * head_dim  # attn_norm, moe_norm; q_norm, k_norm
        + d * config["published_num_experts"]  # router
        + config["num_experts"] * 3 * d * config["moe_intermediate_size"]
    )
    return (
        _frame(config) * d + d  # projection
        + (1 + actions) * d + d  # extras
        + config["num_hidden_layers"] * per_layer
        + d  # final norm
        + d * (actions + 1) + actions + 1  # heads
    )


def least_bytes_per_step(config: Dict) -> int:
    return 6 * 4 * param_count(config)
