"""The operations and the least bytes one update of the OLMoE-block
policy needs, from the configuration's shapes.

One multiply-add is two operations. Both counts are the algorithm's and
both are lower bounds: nothing for the sort and the gathers of the
dispatch, nothing for norms, RoPE, softmax or the losses, nothing for
whatever the compiler emitted. A share of a peak computed from them that
reads over 100% therefore means a wrong count.

Operations of the forward pass for one token (one step of one row):

    projection  2 x (frame elements) x d: the flat frame times a matrix
    extras      2 x (1 + actions) x d
    per layer:
      qkvo      4 matrices of d x d: 8 d^2
      attention scores and the weighted sum of values over the keys
                inside the band: 2 x 2 x keys x d, where a query at
                unroll step t sees the cache slots m >= t and the steps
                max(0, t - M) .. t, M + 1 keys while t <= M
      router    2 x d x experts
      experts   experts_per_token x 3 matrices of d x width x 2
    heads       2 x d x (actions + 1)

The backward pass is twice the forward (gradient with respect to the
input and to the weights), except for the projection, whose input is the
uint8 frame: it has a weight gradient and no input gradient.

Bytes: the parameters are float32 and every one of them is read in the
forward pass, read in the backward pass, and read and written by the
optimizer together with RMSprop's second moment (read, written): six
passes over 4 bytes a parameter. Activations, gradients and the batch
are left out: they are not a floor, a fused program could keep much of
them on the chip.
"""

from typing import Dict


def _shape(config: Dict):
    frame = 1
    for size in config["frame_shape"]:
        frame *= size
    return (
        frame, config["hidden_size"], config["num_actions"],
        config["num_experts"], config["num_experts_per_tok"],
        config["intermediate_size"], config["num_hidden_layers"],
        config["memory_len"], config["unroll_length"] + 1,
        config["batch_size"],
    )


def band_keys(steps: int, memory_len: int) -> int:
    """Keys inside the band, summed over the unroll's queries."""
    return sum(
        max(0, memory_len - t) + min(t, memory_len) + 1
        for t in range(steps)
    )


def forward_flops_per_step(config: Dict) -> Dict[str, int]:
    """Forward operations of one [T+1, B] batch, by part."""
    frame, d, actions, experts, top_k, width, layers, M, steps, rows = (
        _shape(config)
    )
    tokens = steps * rows
    return {
        "projection": tokens * 2 * frame * d,
        "extras": tokens * 2 * (1 + actions) * d,
        "qkvo": layers * tokens * 8 * d * d,
        "attention": layers * rows * band_keys(steps, M) * 4 * d,
        "router": layers * tokens * 2 * d * experts,
        "experts": layers * tokens * top_k * 3 * 2 * d * width,
        "heads": tokens * 2 * d * (actions + 1),
    }


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update."""
    parts = forward_flops_per_step(config)
    return 3 * sum(parts.values()) - parts["projection"]


def param_count(config: Dict) -> int:
    frame, d, actions, experts, _, width, layers, _, _, _ = _shape(config)
    per_layer = (
        4 * d * d  # q, k, v, o, no bias
        + 4 * d  # attn_norm, q_norm, k_norm, moe_norm
        + d * experts  # router
        + experts * 3 * d * width  # w_gate, w_up, w_down
    )
    return (
        frame * d + d  # projection
        + (1 + actions) * d + d  # extras
        + layers * per_layer
        + d  # final norm
        + d * (actions + 1) + actions + 1  # heads
    )


def least_bytes_per_step(config: Dict) -> int:
    return 6 * 4 * param_count(config)
