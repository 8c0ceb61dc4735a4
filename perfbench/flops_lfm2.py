"""The operations and the least bytes one update of the LFM2-cut policy
needs on this chip, from the configuration's shapes.

One multiply-add is two operations. Both counts are what the algorithm
OWES for the share HELD (the configuration's `num_experts` is what this
chip holds of `published_num_experts`) and both are lower bounds:
nothing for the sort and the gathers of the dispatch, nothing for
norms, the two gate products of a conv operator, the convolution's
masks, softmax, RoPE or the losses, nothing for whatever the compiler
emitted (a rematerialised block's second forward pass, the three bf16
passes of a float32 matmul, the zero columns a head of 64 is padded
with among it). A share of a peak computed from them that reads over
100% therefore means a wrong count.

Operations of the forward pass for one token (one step of one row):

    projection  2 x (frame elements) x d: the flat frame times a matrix
    extras      2 x (1 + actions) x d
    conv layer (gated short convolution):
      in_proj   d x 3 d for B, C and u
      taps      conv_L_cache taps over d channels
      out_proj  d x d
    full_attention layer:
      qkvo      q, o: d x Hq x hd each; k, v: d x Hkv x hd each, hd =
                d / Hq = 64: counted at 64, not at the 128 lanes
      cache_leg for every cached key inside the band: scores and
                combine, 2 x 2 x Hq x hd
      unroll_leg the same for every key of the unroll inside the band
    ffn:
      dense     3 matrices of d x intermediate_size, where the layer run
                is one of the `num_dense_layers` leading ones
      router    2 x d x the PUBLISHED number of experts
      experts   the experts HELD here: a token's num_experts_per_tok
                assignments fall on them in the held / published share,
                on average (4 x 8 / 32 = one a token), each 3 matrices
                of d x moe_intermediate_size
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
attention cache, the conv layers' two-step tails) read once forward and
once backward.
"""

from typing import Dict

from perfbench.flops_kanana2 import cache_pairs, unroll_pairs
from perfbench.flops_mellum2 import _frame

CONV, ATTENTION = "conv", "full_attention"


def layers_run(config: Dict):
    """(operator, whether the ffn is dense) of each layer run, in order:
    `layers_run` lists published layers, `layer_types` and `num_dense_
    layers` are the published model's."""
    return [
        (config["layer_types"][layer], layer < config["num_dense_layers"])
        for layer in config["layers_run"]
    ]


def _counts(config: Dict):
    """(conv layers, attention layers, dense layers, MoE layers) run."""
    kinds = layers_run(config)
    assert len(kinds) == config["num_hidden_layers"]
    conv = sum(kind == CONV for kind, _ in kinds)
    dense = sum(is_dense for _, is_dense in kinds)
    return conv, len(kinds) - conv, dense, len(kinds) - dense


def _heads(config: Dict):
    Hq = config["num_attention_heads"]
    return Hq, config["num_key_value_heads"], config["hidden_size"] // Hq


def forward_flops_per_step(config: Dict) -> Dict[str, int]:
    """Forward operations of one [T+1, B] batch, by part."""
    d, actions = config["hidden_size"], config["num_actions"]
    steps, rows = config["unroll_length"] + 1, config["batch_size"]
    tokens = steps * rows
    conv, attention, dense, sparse = _counts(config)
    Hq, Hkv, hd = _heads(config)
    M = config["memory_len"]
    return {
        "projection": tokens * 2 * _frame(config) * d,
        "extras": tokens * 2 * (1 + actions) * d,
        "conv_in_proj": conv * tokens * 2 * d * 3 * d,
        "conv_taps": conv * tokens * 2 * config["conv_L_cache"] * d,
        "conv_out_proj": conv * tokens * 2 * d * d,
        "qkvo": attention * tokens * 2 * d * hd * (2 * Hq + 2 * Hkv),
        "cache_leg": (
            attention * rows * cache_pairs(steps, M) * 2 * 2 * Hq * hd
        ),
        "unroll_leg": (
            attention * rows * unroll_pairs(steps, M) * 2 * 2 * Hq * hd
        ),
        "dense_mlp": dense * tokens * 3 * 2 * d * config["intermediate_size"],
        "router": sparse * tokens * 2 * d * config["published_num_experts"],
        # tokens x top-k x held / published is a whole number of
        # assignments at the cell's sizes (4,096 x 4 x 8 / 32 = 4,096).
        "experts": (
            sparse * tokens * config["num_experts_per_tok"]
            * config["num_experts"] * 3 * 2 * d
            * config["moe_intermediate_size"]
        ) // config["published_num_experts"],
        "heads": tokens * 2 * d * (actions + 1),
    }


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update."""
    parts = forward_flops_per_step(config)
    return (
        3 * sum(parts.values()) - parts["projection"] - parts["cache_leg"]
    )


def conv_operator_param_count(config: Dict) -> int:
    d = config["hidden_size"]
    assert not config["conv_bias"]
    # in_proj, the taps, out_proj
    return d * 3 * d + config["conv_L_cache"] * d + d * d


def attention_operator_param_count(config: Dict) -> int:
    Hq, Hkv, hd = _heads(config)
    d = config["hidden_size"]
    return d * hd * (2 * Hq + 2 * Hkv) + 2 * hd  # and q_norm, k_norm


def ffn_param_count(config: Dict, dense: bool) -> int:
    """A layer's ffn: the dense SwiGLU, or the router, the expert bias
    and the experts held."""
    d = config["hidden_size"]
    if dense:
        return 3 * d * config["intermediate_size"]
    E = config["published_num_experts"]
    return (
        d * E + E * bool(config["use_expert_bias"])
        + config["num_experts"] * 3 * d * config["moe_intermediate_size"]
    )


def param_count(config: Dict) -> int:
    """Parameters held on this chip."""
    d, actions = config["hidden_size"], config["num_actions"]
    layers = sum(
        (
            conv_operator_param_count(config) if kind == CONV
            else attention_operator_param_count(config)
        ) + ffn_param_count(config, dense) + 2 * d  # a layer's two norms
        for kind, dense in layers_run(config)
    )
    return (
        _frame(config) * d + d  # projection
        + (1 + actions) * d + d  # extras
        + layers
        + d  # final norm
        + d * (actions + 1) + actions + 1  # heads
    )


def state_bytes(config: Dict) -> int:
    """The carried state the update is handed, float32: the attention
    layers' keys, values and validity, the conv layers' tails."""
    rows, d = config["batch_size"], config["hidden_size"]
    conv, attention, _, _ = _counts(config)
    _, Hkv, hd = _heads(config)
    window = config["memory_len"] * (2 * Hkv * hd + 1)
    tail = (config["conv_L_cache"] - 1) * d
    return 4 * rows * (attention * window + conv * tail)


def least_bytes_per_step(config: Dict) -> int:
    return 6 * 4 * param_count(config) + 2 * state_bytes(config)
