"""The operations and the least bytes one update of the Granite-4.0-H-
Micro-period policy needs on this chip, from the configuration's shapes.

One multiply-add is two operations. Both counts are what the algorithm
OWES and both are lower bounds: nothing for norms, the softplus, the
gate, the multipliers, the convolution's masks, softmax or the losses,
nothing for whatever the compiler emitted (a rematerialised block's
second forward pass, the bf16 passes of a float32 matmul among it). A
share of a peak computed from them that reads over 100% therefore means
a wrong count.

Operations of the forward pass for one token (one step of one row):

    projection  2 x (frame elements) x d: the flat frame times a matrix
    extras      2 x (1 + actions) x d
    every layer:
      mlp       `shared_mlp`: input_linear d x 2 W and output_linear
                W x d, W = shared_intermediate_size
    mamba layer:
      in_proj   d x (2 H P + 2 G N + H)
      conv      mamba_d_conv taps over H P + 2 G N channels
      scan      the RECURRENCE's two products a head, h += dt x B^T and
                y = h C: 2 x 2 x H x P x N, and the D x skip. The
                chunked form the program runs does more (a chunk's
                [256, 256] matrix of decays against x, and B C^T),
                which is the program's choice and not owed
      out_proj  H P x d
    attention layer:
      qkvo      q: d x Hq x hd; k, v: d x Hkv x hd each; o: Hq x hd x d
      cache_leg for every cached key inside the band: scores and
                combine, 2 x 2 x Hq x hd
      unroll_leg the same for every key of the unroll inside the band
    heads       2 x d x (actions + 1)

The backward pass is twice the forward (gradient with respect to the
input and to the weights) for every product but two. The projection's
input is the uint8 frame: a weight gradient and no input gradient. The
attention cache is data: through the cache leg the backward pass owes
`dP` and `dq`, two products for the forward's two, and nothing for the
cached keys and values.

Bytes: six passes over 4 bytes of every parameter (forward, backward,
the optimizer's read and write of weight and second moment), as
`flops_olmoe.least_bytes_per_step`, and the carried state (the
attention cache, the Mamba states and conv tails) read once forward and
once backward.
"""

from typing import Dict

from perfbench.flops_kanana2 import cache_pairs, unroll_pairs
from perfbench.flops_mellum2 import _frame


def _count(config: Dict, kind: str) -> int:
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    return config["layer_types"].count(kind)


def _mamba_widths(config: Dict):
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    G, N = config["mamba_n_groups"], config["mamba_d_state"]
    return H, P, G, N, H * P + 2 * G * N


def _head_dim(config: Dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def forward_flops_per_step(config: Dict) -> Dict[str, int]:
    """Forward operations of one [T+1, B] batch, by part."""
    d, actions = config["hidden_size"], config["num_actions"]
    steps, rows = config["unroll_length"] + 1, config["batch_size"]
    tokens = steps * rows
    mamba, attention = _count(config, "mamba"), _count(config, "attention")
    H, P, G, N, channels = _mamba_widths(config)
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, M = _head_dim(config), config["memory_len"]
    return {
        "projection": tokens * 2 * _frame(config) * d,
        "extras": tokens * 2 * (1 + actions) * d,
        "mamba_in_proj": mamba * tokens * 2 * d * (H * P + channels + H),
        "mamba_conv": mamba * tokens * 2 * config["mamba_d_conv"] * channels,
        "mamba_scan": mamba * tokens * (2 * 2 * H * P * N + 2 * H * P),
        "mamba_out_proj": mamba * tokens * 2 * H * P * d,
        "qkvo": attention * tokens * 2 * d * hd * (2 * Hq + 2 * Hkv),
        "cache_leg": (
            attention * rows * cache_pairs(steps, M) * 2 * 2 * Hq * hd
        ),
        "unroll_leg": (
            attention * rows * unroll_pairs(steps, M) * 2 * 2 * Hq * hd
        ),
        "mlp": (
            (mamba + attention) * tokens * 2 * 3 * d
            * config["shared_intermediate_size"]
        ),
        "heads": tokens * 2 * d * (actions + 1),
    }


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update."""
    parts = forward_flops_per_step(config)
    return (
        3 * sum(parts.values()) - parts["projection"] - parts["cache_leg"]
    )


def mlp_param_count(config: Dict) -> int:
    d = config["hidden_size"]
    return d + 3 * d * config["shared_intermediate_size"]  # norm, in, out


def mamba_param_count(config: Dict) -> int:
    """A Mamba layer: the mixer, its norm and the `shared_mlp`."""
    d = config["hidden_size"]
    H, P, _, _, channels = _mamba_widths(config)
    return (
        d  # norm
        + d * (H * P + channels + H)  # in_proj
        + (config["mamba_d_conv"] + 1) * channels  # taps and bias
        + 3 * H  # dt_bias, A_log, D
        + H * P  # the gated norm's scale
        + H * P * d  # out_proj
        + mlp_param_count(config)
    )


def attention_param_count(config: Dict) -> int:
    """An attention layer: the mixer, its norm and the `shared_mlp`."""
    d, hd = config["hidden_size"], _head_dim(config)
    return d + d * hd * (
        2 * config["num_attention_heads"] + 2 * config["num_key_value_heads"]
    ) + mlp_param_count(config)


def param_count(config: Dict) -> int:
    """Parameters held on this chip."""
    d, actions = config["hidden_size"], config["num_actions"]
    return (
        _frame(config) * d + d  # projection
        + (1 + actions) * d + d  # extras
        + _count(config, "mamba") * mamba_param_count(config)
        + _count(config, "attention") * attention_param_count(config)
        + d  # final norm
        + d * (actions + 1) + actions + 1  # heads
    )


def state_bytes(config: Dict) -> int:
    """The carried state the update is handed, float32: the attention
    layers' keys, values and validity, the Mamba layers' states and
    conv tails."""
    rows = config["batch_size"]
    H, P, _, N, channels = _mamba_widths(config)
    window = config["memory_len"] * (
        2 * config["num_key_value_heads"] * _head_dim(config) + 1
    )
    carried = H * P * N + (config["mamba_d_conv"] - 1) * channels
    return 4 * rows * (
        _count(config, "attention") * window
        + _count(config, "mamba") * carried
    )


def least_bytes_per_step(config: Dict) -> int:
    return 6 * 4 * param_count(config) + 2 * state_bytes(config)
