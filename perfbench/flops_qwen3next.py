"""The operations and the least bytes one update of the Qwen3-Next-
period policy needs on this chip, from the configuration's shapes.

One multiply-add is two operations. Both counts are what the algorithm
OWES for the share HELD (the configuration's `num_experts` is what this
chip holds of `published_num_experts`) and both are lower bounds:
nothing for the sort and the gathers of the dispatch, nothing for
norms, the softplus, the gates, the convolution's masks, softmax, RoPE
or the losses, nothing for whatever the compiler emitted (a
rematerialised block's second forward pass, the three bf16 passes of a
float32 matmul among it). A share of a peak computed from them that
reads over 100% therefore means a wrong count.

Operations of the forward pass for one token (one step of one row):

    projection  2 x (frame elements) x d: the flat frame times a matrix
    extras      2 x (1 + actions) x d
    D layer (Gated DeltaNet):
      in_proj   d x (2 Hk Dk + 2 Hv Dv) for q, k, v, z and d x 2 Hv for
                b, a
      conv      conv kernel taps over 2 Hk Dk + Hv Dv channels
      scan      the RECURRENCE's three products a value head over its
                [Dk, Dv] state: S'^T k, k u^T and S^T q, 3 x 2 x Dk x
                Dv. The chunked form the program runs does more (the
                keys' [64, 64] products, the triangular solve, a
                [Dk, Dk] matrix a chunk), which is the program's choice
                and not owed
      out_proj  Hv Dv x d
    A layer (gated attention):
      qkvo      q and its gate: d x Hq x 2 hd; k, v: d x Hkv x hd each;
                o: Hq x hd x d
      cache_leg for every cached key inside the band: scores and
                combine, 2 x 2 x Hq x hd
      unroll_leg the same for every key of the unroll inside the band
    moe (every layer):
      router    2 x d x the PUBLISHED number of experts, and the shared
                expert's gate, 2 x d
      experts   the experts HELD here: a token's experts_per_token
                assignments fall on them in the held / published share,
                on average (10 x 32 / 512), each 3 matrices of d x width
      shared    3 matrices of d x shared width, every token
    heads       2 x d x (actions + 1)

The backward pass is twice the forward (gradient with respect to the
input and to the weights) for every product but two. The projection's
input is the uint8 frame: a weight gradient and no input gradient. The
attention cache is data: through the cache leg the backward pass owes
`dP` and `dq`, two products for the forward's two, and nothing for the
cached keys and values.

Bytes: six passes over 4 bytes of every parameter HELD (forward,
backward, the optimizer's read and write of weight and second moment),
as `flops_olmoe.least_bytes_per_step`, and the carried state (the
attention cache, the DeltaNet states and conv tails) read once forward
and once backward.
"""

from typing import Dict

from perfbench.flops_kanana2 import cache_pairs, unroll_pairs
from perfbench.flops_mellum2 import _frame


def _layers(config: Dict):
    """(DeltaNet layers, attention layers) of the depth held."""
    depth = config["num_hidden_layers"]
    attention = depth // config["full_attention_interval"]
    return depth - attention, attention


def _delta_widths(config: Dict):
    Hk, Hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    Dk, Dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    return Hk, Hv, Dk, Dv, 2 * Hk * Dk + Hv * Dv


def forward_flops_per_step(config: Dict) -> Dict[str, int]:
    """Forward operations of one [T+1, B] batch, by part."""
    d, actions = config["hidden_size"], config["num_actions"]
    steps, rows = config["unroll_length"] + 1, config["batch_size"]
    tokens = steps * rows
    delta, attention = _layers(config)
    layers = delta + attention
    Hk, Hv, Dk, Dv, channels = _delta_widths(config)
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, M = config["head_dim"], config["memory_len"]
    width = config["moe_intermediate_size"]
    return {
        "projection": tokens * 2 * _frame(config) * d,
        "extras": tokens * 2 * (1 + actions) * d,
        "delta_in_proj": (
            delta * tokens * 2 * d * (channels + Hv * Dv + 2 * Hv)
        ),
        "delta_conv": (
            delta * tokens * 2 * config["linear_conv_kernel_dim"] * channels
        ),
        "delta_scan": delta * tokens * 3 * 2 * Hv * Dk * Dv,
        "delta_out_proj": delta * tokens * 2 * Hv * Dv * d,
        "qkvo": attention * tokens * 2 * d * hd * (3 * Hq + 2 * Hkv),
        "cache_leg": (
            attention * rows * cache_pairs(steps, M) * 2 * 2 * Hq * hd
        ),
        "unroll_leg": (
            attention * rows * unroll_pairs(steps, M) * 2 * 2 * Hq * hd
        ),
        "router": (
            layers * tokens * 2 * d * (config["published_num_experts"] + 1)
        ),
        # tokens x top-k x held / published is a whole number of
        # assignments at the cell's sizes (4,096 x 10 x 32 / 512 = 2,560).
        "experts": (
            layers * tokens * config["num_experts_per_tok"]
            * config["num_experts"] * 3 * 2 * d * width
        ) // config["published_num_experts"],
        "shared": (
            layers * tokens * 3 * 2 * d
            * config["shared_expert_intermediate_size"]
        ),
        "heads": tokens * 2 * d * (actions + 1),
    }


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update."""
    parts = forward_flops_per_step(config)
    return (
        3 * sum(parts.values()) - parts["projection"] - parts["cache_leg"]
    )


def delta_mixer_param_count(config: Dict) -> int:
    d = config["hidden_size"]
    _, Hv, _, Dv, channels = _delta_widths(config)
    return (
        d * (channels + Hv * Dv)  # in_proj_qkvz
        + d * 2 * Hv  # in_proj_ba
        + config["linear_conv_kernel_dim"] * channels  # taps, no bias
        + 2 * Hv  # dt_bias, A_log
        + Dv  # the gated norm's scale, one for every head
        + Hv * Dv * d  # out_proj
    )


def attention_mixer_param_count(config: Dict) -> int:
    d, hd = config["hidden_size"], config["head_dim"]
    return d * hd * (
        3 * config["num_attention_heads"] + 2 * config["num_key_value_heads"]
    ) + 2 * hd  # and q_norm, k_norm


def moe_param_count(config: Dict) -> int:
    """A layer's MoE part: outside its experts, and the experts held."""
    d = config["hidden_size"]
    return (
        d * config["published_num_experts"]  # router
        + 3 * d * config["shared_expert_intermediate_size"]
        + d  # shared_expert_gate
        + config["num_experts"] * 3 * d * config["moe_intermediate_size"]
    )


def param_count(config: Dict) -> int:
    """Parameters held on this chip."""
    d, actions = config["hidden_size"], config["num_actions"]
    delta, attention = _layers(config)
    return (
        _frame(config) * d + d  # projection
        + (1 + actions) * d + d  # extras
        + delta * delta_mixer_param_count(config)
        + attention * attention_mixer_param_count(config)
        + (delta + attention) * (moe_param_count(config) + 2 * d)  # norms
        + d  # final norm
        + d * (actions + 1) + actions + 1  # heads
    )


def state_bytes(config: Dict) -> int:
    """The carried state the update is handed, float32: the attention
    layers' keys, values and validity, the DeltaNet layers' matrix
    states and conv tails."""
    rows = config["batch_size"]
    _, Hv, Dk, Dv, channels = _delta_widths(config)
    delta, attention = _layers(config)
    window = config["memory_len"] * (
        2 * config["num_key_value_heads"] * config["head_dim"] + 1
    )
    carried = Hv * Dk * Dv + (config["linear_conv_kernel_dim"] - 1) * channels
    return 4 * rows * (attention * window + delta * carried)


def least_bytes_per_step(config: Dict) -> int:
    return 6 * 4 * param_count(config) + 2 * state_bytes(config)
