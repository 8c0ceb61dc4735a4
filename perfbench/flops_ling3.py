"""The operations and the least bytes one update of the Ling-3.0-flash-VL
policy cut needs on this chip, from the configuration's shapes.

One multiply-add is two operations. Both counts are what the algorithm
OWES and both are lower bounds: nothing for norms, the sigmoids, the
gates, the L2 norms, the convolution's masks, the exponentials of the
decays, the group selection, softmax or the losses, nothing for whatever
the compiler emitted (a rematerialised block's second forward pass, the
bf16 passes of a float32 matmul among it). A share of a peak computed
from them that reads over 100% therefore means a wrong count.

Operations of the forward pass for one token (one step of one row):

    projection  2 x (frame elements) x d: the flat frame times a matrix
    extras      2 x (1 + actions) x d
    KDA layer (`layers_run` l with (l + 1) % layer_group_size != 0):
      in_proj   d x (4 H D + 2 H): q, k, v, the decay's f (full rank,
                `no_kda_lora`), beta and the head gate
      conv      short_conv_kernel_size taps over 3 H D channels
      scan      the RECURRENCE's products a head over its [D, D] state:
                the decay of every entry (D x D multiplies, counted as
                half a multiply-add each), the read S'^T k, the update
                k u^T and the output S^T q: 2 x 3 x H x D x D + H x D x
                D. The chunked form the program runs does more (a
                chunk's [64, 64] matrices from sub-blocks, the solve,
                the [D, D] hand-on of every chunk), which is the
                program's choice and not owed
      out_proj  H D x d
    latent layer (the others):
      qkvo      q d x H (nope + rope); kv_a d x (rank + rope); kv_b
                rank x H (nope + v); the head gate d x H; o H v x d
      absorb    the queries into the latent's space and the combined
                latents out of it: H x rank x (nope + v)
      cache_leg for every cached key inside the band (`flops_kanana2.
                cache_pairs`): scores over rank + rope and the combine
                over rank, a head
      unroll_leg for every key of the unroll inside the band: scores
                over nope + rope and the combine over v, a head
    feed-forward part:
      mlp       a leading dense layer: 3 x d x intermediate_size
      router    d x published_num_experts
      experts   the rows the experts HELD here compute: tokens x
                num_experts_per_tok x held / published on an even load,
                3 x d x moe_intermediate_size each
      shared    3 x d x moe_shared_expert_intermediate_size, every token
    heads       2 x d x (actions + 1)

The backward pass is twice the forward (gradient with respect to the
input and to the weights) for every product but two, as `flops_kanana2`:
the projection's input is the uint8 frame (a weight gradient and no
input gradient), and the latent cache is data (through the cache leg
the backward pass owes `dP` and `dq`, two products for the forward's
two).

Bytes: six passes over 4 bytes of every parameter HELD (forward,
backward, the optimizer's read and write of weight and second moment),
as `flops_olmoe.least_bytes_per_step`, and the carried state (the
latent cache, the KDA states and conv tails) read once forward and once
backward.

The one kernel this PR widens: ops/delta_rule.py's chunk-to-chunk pass
under a hand-on a key channel (`chunk_pass(..., hand_on=)`), which a
KDA layer calls once forward, once again rematerialised and once
backward. `chunk_pass_flops` and `chunk_pass_bytes` are one call's, in
that module's own terms (its header's "Forecast and measured"): with P
= 2 Q Dk Dv and R = 2 Q Q Dv a (row, chunk, head), the forward's three
products are 3 P + R ([Kd; q] S, Kl^T V', A V'), the backward's 8 P + 2
R in the reverse walk and 2 P for each of the c - 1 chunks whose
entering state it makes again; bytes are every operand and result once
(a [Q, Q] tile padded to 128 lanes in HBM, the per-step rows and the
hand-on padded to 8 sublanes), and in the backward k, the rows, U, Kd
and the hand-on of c - 1 chunks of c a second time.
"""

from typing import Dict

from perfbench.flops_kanana2 import cache_pairs, unroll_pairs
from perfbench.flops_mellum2 import _frame


def _kinds(config: Dict):
    """(KDA layers, latent layers, dense feed-forward parts, routed
    ones) among the published layers the cut runs."""
    run = config["layers_run"]
    assert len(run) == config["num_hidden_layers"]
    latent = sum((l + 1) % config["layer_group_size"] == 0 for l in run)
    dense = sum(l < config["first_k_dense_replace"] for l in run)
    return len(run) - latent, latent, dense, len(run) - dense


def _latent_widths(config: Dict):
    return (
        config["num_attention_heads"], config["kv_lora_rank"],
        config["qk_nope_head_dim"], config["qk_rope_head_dim"],
        config["v_head_dim"],
    )


def forward_flops_per_step(config: Dict) -> Dict[str, int]:
    """Forward operations of one [T+1, B] batch, by part."""
    d, actions = config["hidden_size"], config["num_actions"]
    steps, rows = config["unroll_length"] + 1, config["batch_size"]
    tokens = steps * rows
    kda, latent, dense, sparse = _kinds(config)
    H, D = config["num_attention_heads"], config["head_dim"]
    _, rank, nope, rope, value = _latent_widths(config)
    M, E = config["memory_len"], config["published_num_experts"]
    width = config["moe_intermediate_size"]
    return {
        "projection": tokens * 2 * _frame(config) * d,
        "extras": tokens * 2 * (1 + actions) * d,
        "kda_in_proj": kda * tokens * 2 * d * (4 * H * D + 2 * H),
        "kda_conv": (
            kda * tokens * 2 * config["short_conv_kernel_size"] * 3 * H * D
        ),
        "kda_scan": kda * tokens * (2 * 3 * H * D * D + H * D * D),
        "kda_out_proj": kda * tokens * 2 * H * D * d,
        "qkvo": latent * tokens * 2 * (
            d * H * (nope + rope) + d * (rank + rope)
            + rank * H * (nope + value) + d * H + H * value * d
        ),
        "absorb": latent * tokens * 2 * H * rank * (nope + value),
        "cache_leg": latent * rows * cache_pairs(steps, M) * 2 * H * (
            (rank + rope) + rank
        ),
        "unroll_leg": latent * rows * unroll_pairs(steps, M) * 2 * H * (
            (nope + rope) + value
        ),
        "mlp": dense * tokens * 3 * 2 * d * config["intermediate_size"],
        "router": sparse * tokens * 2 * d * E,
        # tokens x top-k x held / published is a whole number of
        # assignments at the cell's sizes (2,048 x 8 x 8 / 512 = 256).
        "experts": (
            sparse * tokens * config["num_experts_per_tok"]
            * config["num_experts"] * 3 * 2 * d * width
        ) // E,
        "shared": (
            sparse * tokens * 3 * 2 * d
            * config["moe_shared_expert_intermediate_size"]
        ),
        "heads": tokens * 2 * d * (actions + 1),
    }


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update."""
    parts = forward_flops_per_step(config)
    return (
        3 * sum(parts.values()) - parts["projection"] - parts["cache_leg"]
    )


def kda_param_count(config: Dict) -> int:
    """A KDA mixer with its norm."""
    d, H, D = (
        config["hidden_size"], config["num_attention_heads"],
        config["head_dim"],
    )
    return (
        d  # norm
        + d * (4 * H * D + 2 * H)  # in_proj, in_proj_bg
        + config["short_conv_kernel_size"] * 3 * H * D  # taps, no bias
        + H + H * D  # A_log, dt_bias
        + D  # the output norm's scale
        + H * D * d  # out_proj
    )


def latent_param_count(config: Dict) -> int:
    """The latent mixer with its norm."""
    d = config["hidden_size"]
    H, rank, nope, rope, value = _latent_widths(config)
    return (
        d  # norm
        + d * H * (nope + rope)  # q
        + d * (rank + rope) + rank  # kv_a and its norm
        + rank * H * (nope + value)  # kv_b
        + d * H  # head gate
        + H * value * d  # o
    )


def moe_param_count(config: Dict) -> int:
    """A routed feed-forward part with its norm, the experts HELD."""
    d, E = config["hidden_size"], config["published_num_experts"]
    return (
        d  # norm
        + d * E + E  # router and its selection bias
        + config["num_experts"] * 3 * d * config["moe_intermediate_size"]
        + 3 * d * config["moe_shared_expert_intermediate_size"]
    )


def param_count(config: Dict) -> int:
    """Parameters held on this chip."""
    d, actions = config["hidden_size"], config["num_actions"]
    kda, latent, dense, sparse = _kinds(config)
    return (
        _frame(config) * d + d  # projection
        + (1 + actions) * d + d  # extras
        + kda * kda_param_count(config)
        + latent * latent_param_count(config)
        + dense * (d + 3 * d * config["intermediate_size"])
        + sparse * moe_param_count(config)
        + d  # final norm
        + d * (actions + 1) + actions + 1  # heads
    )


def state_bytes(config: Dict) -> int:
    """The carried state the update is handed, float32: the latent
    layers' latents, rope keys and validity, the KDA layers' states and
    conv tails."""
    kda, latent, _, _ = _kinds(config)
    H, D = config["num_attention_heads"], config["head_dim"]
    window = config["memory_len"] * (
        config["kv_lora_rank"] + config["qk_rope_head_dim"] + 1
    )
    carried = H * D * D + (config["short_conv_kernel_size"] - 1) * 3 * H * D
    return 4 * config["batch_size"] * (latent * window + kda * carried)


def _pass_cells(config: Dict):
    """(rows, chunks, heads, steps a chunk, key width, value width) of
    one KDA layer's chunk-to-chunk pass."""
    steps = config["unroll_length"] + 1
    Q = config["chunk_size"]
    return (
        config["batch_size"], -(-steps // Q), config["num_attention_heads"],
        Q, config["head_dim"], config["head_dim"],
    )


def chunk_pass_flops(config: Dict, backward: bool) -> int:
    """Operations of one call of the pass's forward or backward kernel."""
    rows, chunks, H, Q, Dk, Dv = _pass_cells(config)
    P, R = 2 * Q * Dk * Dv, 2 * Q * Q * Dv
    cells = rows * chunks * H
    if not backward:
        return cells * (3 * P + R)
    return cells * (8 * P + 2 * R) + rows * (chunks - 1) * H * 2 * P


def chunk_pass_bytes(config: Dict, backward: bool) -> int:
    """Bytes one call of the pass's forward or backward kernel moves
    through HBM, float32, tiles padded as HBM lays them."""
    rows, chunks, H, Q, Dk, Dv = _pass_cells(config)
    lanes, sublanes = 128, 8
    cells = rows * chunks * H
    q = k = Q * Dk
    scalars = sublanes * max(Q, lanes)  # f and e, two rows of Q
    A = Q * max(Q, lanes)
    U = O = Q * Dv
    Kd = Q * Dk
    hand = sublanes * Dk
    state = rows * H * Dk * Dv  # the first, the last
    forward = cells * (q + k + scalars + A + U + Kd + hand + O) + 2 * state
    if not backward:
        return 4 * forward
    again = rows * (chunks - 1) * H * (k + scalars + U + Kd + hand)
    return 4 * (
        forward + cells * O + state  # the forward's reads, dO, dlast
        + cells * (q + k + scalars + A + U + Kd + hand) + state  # results
        + again
    )


def least_bytes_per_step(config: Dict) -> int:
    return 6 * 4 * param_count(config) + 2 * state_bytes(config)
