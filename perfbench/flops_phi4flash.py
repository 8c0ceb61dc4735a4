"""The operations and the least bytes one update of the Phi-4-mini-
flash-cut policy needs on this chip, from the configuration's shapes.

One multiply-add is two operations. Both counts are what the algorithm
OWES and both are lower bounds: nothing for norms, gates, softmax, the
difference and its norm, the convolution's masks or the losses, nothing
for whatever the compiler emitted (a rematerialised block's second
forward pass, the three bf16 passes of a float32 matmul, the zero half
of a query that reads one half of a 128-wide key among it). A share of
a peak computed from them that reads over 100% therefore means a wrong
count.

What kind a published layer i of `published_num_hidden_layers` = L is
(`kind_of`): i even, i <= L/2 a Mamba-1 layer; i odd, i < L/2 sliding
differential attention; i = L/2 + 1 full differential attention; i
even, i > L/2 a gated memory unit; i odd, i > L/2 + 1 cross attention.

Operations of the forward pass for one token (one step of one row):

    projection  2 x (frame elements) x d: the flat frame times a matrix
    extras      2 x (1 + actions) x d
    mamba layer, D = expand x d, N = d_state, R = dt_rank:
      in_proj   d x 2 D
      conv      d_conv taps over D channels
      x_proj    D x (R + 2 N); dt_proj R x D
      scan      6 a channel and state column: dt A; decay x s; (dt a) x
                B; their sum; s x C and its sum over the columns. The
                exponent is counted apart (`scan_exponents`)
      out_proj  D x d
    attention layer with its own keys:
      qkvo      Wqkv d x (Hq + 2 Hkv) x hd, out_proj d x d
      legs      for every key inside the band, a query head's score
                over hd = 64 and its combine over the value's 2 hd =
                128: 2 x 3 hd a query head
    cross layer: Wq d x d, out_proj d x d, the full layer's legs
    memory unit: in_proj d x D, out_proj D x d
    mlp         3 matrices of d x intermediate_size, every layer
    heads       2 x d x (actions + 1)

The backward pass is twice the forward (gradient with respect to the
input and to the weights) for every product but two. The projection's
input is the uint8 frame: a weight gradient and no input gradient. The
attention caches are data: through a cache leg the backward pass owes
`dP` and `dq`, two products for the forward's two, and nothing for the
cached keys and values.

Bytes: six passes over 4 bytes of every parameter (forward, backward,
the optimizer's read and write of weight and second moment), as
`flops_olmoe.least_bytes_per_step`, and the carried state (two windows,
two Mamba states with their tails) read once forward and once backward.
"""

from typing import Dict

from perfbench.flops_kanana2 import cache_pairs, unroll_pairs
from perfbench.flops_mellum2 import _frame

MAMBA, SLIDING, FULL, MEMORY, CROSS = (
    "mamba", "sliding", "full", "memory", "cross"
)


def kind_of(index: int, published_layers: int) -> str:
    boundary = published_layers // 2
    if index % 2 == 0:
        return MAMBA if index <= boundary else MEMORY
    if index < boundary:
        return SLIDING
    return FULL if index == boundary + 1 else CROSS


def layers_run(config: Dict):
    """(published index, kind) of each layer run, in order."""
    return [
        (index, kind_of(index, config["published_num_hidden_layers"]))
        for index in config["layers_run"]
    ]


def _count(config: Dict, *kinds) -> int:
    run = layers_run(config)
    assert len(run) == config["num_hidden_layers"]
    return sum(kind in kinds for _, kind in run)


def _widths(config: Dict):
    """(d, D, N, R, K, Hq, Hkv, hd)."""
    d, Hq = config["hidden_size"], config["num_attention_heads"]
    return (
        d, config["expand"] * d, config["d_state"], config["dt_rank"],
        config["d_conv"], Hq, config["num_key_value_heads"], d // Hq,
    )


def window_len(config: Dict, kind: str) -> int:
    """Cache slots of a layer that attends: a sliding layer's window
    less the query's own step, the full layer's `memory_len` (which a
    cross layer reads)."""
    M = config["memory_len"]
    return min(M, config["sliding_window"] - 1) if kind == SLIDING else M


def forward_flops_per_step(config: Dict) -> Dict[str, int]:
    """Forward operations of one [T+1, B] batch, by part."""
    d, D, N, R, K, Hq, Hkv, hd = _widths(config)
    actions = config["num_actions"]
    steps, rows = config["unroll_length"] + 1, config["batch_size"]
    tokens = steps * rows
    mamba = _count(config, MAMBA)
    pair = 2 * 3 * Hq * hd  # a query's score and combine against a key

    def legs(pairs_of, *kinds):
        return sum(
            rows * pairs_of(steps, window_len(config, kind)) * pair
            for _, kind in layers_run(config) if kind in kinds
        )

    return {
        "projection": tokens * 2 * _frame(config) * d,
        "extras": tokens * 2 * (1 + actions) * d,
        "mamba_in_proj": mamba * tokens * 2 * d * 2 * D,
        "mamba_conv": mamba * tokens * 2 * K * D,
        "mamba_x_proj": mamba * tokens * 2 * (D * (R + 2 * N) + R * D),
        "scan": mamba * tokens * 6 * D * N,
        "mamba_out_proj": mamba * tokens * 2 * D * d,
        "qkvo": _count(config, SLIDING, FULL) * tokens * 2 * d * (
            (Hq + 2 * Hkv) * hd + d
        ),
        "cross_qo": _count(config, CROSS) * tokens * 2 * d * 2 * d,
        "cache_leg": legs(cache_pairs, SLIDING, FULL, CROSS),
        "unroll_leg": legs(unroll_pairs, SLIDING, FULL, CROSS),
        "memory_unit": _count(config, MEMORY) * tokens * 2 * 2 * d * D,
        "mlp": (
            config["num_hidden_layers"] * tokens * 3 * 2 * d
            * config["intermediate_size"]
        ),
        "heads": tokens * 2 * d * (actions + 1),
    }


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update."""
    parts = forward_flops_per_step(config)
    return (
        3 * sum(parts.values()) - parts["projection"] - parts["cache_leg"]
    )


def scan_counts(config: Dict) -> Dict[str, int]:
    """What the selective scans of one update owe: vector operations
    (`forward_flops_per_step`'s "scan", forward, the forward made again
    for the backward pass, and a backward of twice the forward),
    exponents (one a channel, state column and step, forward and again
    for the backward) and the bytes streamed where the state stays on
    the chip (a, dt and y [T, B, D], B and C [T, B, N], float32: read
    forward, read again and their gradients written backward, y's
    cotangent read)."""
    _, D, N, _, _, _, _, _ = _widths(config)
    steps, rows = config["unroll_length"] + 1, config["batch_size"]
    tokens, mamba = steps * rows, _count(config, MAMBA)
    stream = 4 * tokens * (3 * D + 2 * N)
    return {
        "operations": 4 * forward_flops_per_step(config)["scan"],
        "exponents": 2 * mamba * tokens * D * N,
        "stream_bytes": mamba * (3 * stream - 4 * tokens * D),
    }


def mixer_param_count(config: Dict, kind: str) -> int:
    d, D, N, R, K, Hq, Hkv, hd = _widths(config)
    difference = 4 * hd + 2 * hd  # four lambda vectors, the pair's norm
    if kind == MAMBA:
        return (
            d * 2 * D + K * D + D  # in_proj, the taps and their bias
            + D * (R + 2 * N) + R * D + D  # x_proj, dt_proj and its bias
            + D * N + D + D * d  # A_log, D, out_proj
        )
    if kind == MEMORY:
        return d * D + D * d
    if kind == CROSS:
        return d * Hq * hd + Hq * hd + d * d + d + difference
    width = (Hq + 2 * Hkv) * hd
    return d * width + width + d * d + d + difference


def layer_param_count(config: Dict, kind: str) -> int:
    d = config["hidden_size"]
    assert not config["mlp_bias"]
    # the mixer, the SwiGLU, two LayerNorms (scale and bias)
    return (
        mixer_param_count(config, kind) + 3 * d * config["intermediate_size"]
        + 4 * d
    )


def param_count(config: Dict) -> int:
    """Parameters held on this chip."""
    d, actions = config["hidden_size"], config["num_actions"]
    return (
        _frame(config) * d + d  # projection
        + (1 + actions) * d + d  # extras
        + sum(layer_param_count(config, kind) for _, kind in layers_run(config))
        + 2 * d  # final LayerNorm
        + d * (actions + 1) + actions + 1  # heads
    )


def state_bytes(config: Dict) -> int:
    """The carried state the update is handed, float32: the windows'
    keys, values and validity, the Mamba layers' states and tails."""
    _, D, N, _, K, _, Hkv, hd = _widths(config)
    per_row = sum(
        (N + K - 1) * D if kind == MAMBA
        else window_len(config, kind) * (2 * Hkv * hd + 1)
        for _, kind in layers_run(config) if kind in (MAMBA, SLIDING, FULL)
    )
    return 4 * config["batch_size"] * per_row


def least_bytes_per_step(config: Dict) -> int:
    return 6 * 4 * param_count(config) + 2 * state_bytes(config)
