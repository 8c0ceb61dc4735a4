"""The operations and the least bytes one update of the Kanana-2-block
policy needs on this chip, from the configuration's shapes.

One multiply-add is two operations. Both counts are what the algorithm
OWES and both are lower bounds: nothing for the sort and the gathers of
the dispatch, nothing for norms, RoPE, softmax or the losses, nothing
for whatever the compiler emitted (a rematerialised block's second
forward pass among it). A share of a peak computed from them that reads
over 100% therefore means a wrong count.

Operations of the forward pass for one token (one step of one row):

    projection  2 x (frame elements) x d: the flat frame times a matrix
    extras      2 x (1 + actions) x d
    per layer:
      qkvo      q: d x heads x (nope + rope); kv_a: d x (rank + rope);
                kv_b on this unroll's tokens: rank x heads x (nope +
                value); o: heads x value x d
      absorb    q_nope into the latent's space, and the cache leg's
                combine lifted out of it: 2 x heads x nope x rank and
                2 x heads x rank x value
      cache_leg for every cached key inside the band (`cache_pairs`):
                the scores against ONE key of rank + rope for all heads,
                2 x heads x (rank + rope), and the combine over the
                latent, 2 x heads x rank. In absorbed form: a cached key
                is never decompressed
      unroll_leg for every key of the unroll inside the band: scores
                2 x heads x (nope + rope), combine 2 x heads x value
    layer 0:    mlp, gate, up and down: 3 x 2 x d x intermediate_size
    layers >= 1:
      router    2 x d x the PUBLISHED number of routed experts
      experts   the experts HELD here: a token's experts_per_token
                assignments fall on them in the held / published share,
                on average (6 x 16 / 128), each 3 matrices of d x width
      shared    n_shared_experts x width wide, every token: 3 x 2 x d
    heads       2 x d x (actions + 1)

The backward pass is twice the forward (gradient with respect to the
input and to the weights) for every matmul but two. The projection's
input is the uint8 frame: a weight gradient and no input gradient. The
cache is data: through the cache leg the backward pass owes the
gradient of the weights (`dP`) and of the queries (`dq`), two products
for the forward's two, and nothing for the cached latents and keys.

Bytes: six passes over 4 bytes of every parameter HELD (forward,
backward, the optimizer's read and write of weight and second moment),
as `flops_olmoe.least_bytes_per_step`, and the latent caches read once
forward and once backward: the one state of these cells too large to
leave out (1.5 GB against 2.3 GB of weights).
"""

from typing import Dict

from perfbench.flops_mellum2 import _frame


def cache_pairs(steps: int, memory_len: int) -> int:
    """(query, cached key) pairs inside the band, one row: query t sees
    the slots at most memory_len steps back, memory_len - t of them."""
    return sum(max(0, memory_len - t) for t in range(steps))


def unroll_pairs(steps: int, memory_len: int) -> int:
    """(query, unroll key) pairs inside the band, one row: query t sees
    itself and the min(t, memory_len) steps before it."""
    return sum(min(t, memory_len) + 1 for t in range(steps))


def _widths(config: Dict):
    return (
        config["hidden_size"], config["num_attention_heads"],
        config["kv_lora_rank"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"],
    )


def forward_flops_per_step(config: Dict) -> Dict[str, int]:
    """Forward operations of one [T+1, B] batch, by part."""
    d, heads, rank, nope, rope, value = _widths(config)
    actions = config["num_actions"]
    steps, rows = config["unroll_length"] + 1, config["batch_size"]
    tokens, layers = steps * rows, config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    sparse = layers - dense
    width = config["moe_intermediate_size"]
    M = config["memory_len"]
    return {
        "projection": tokens * 2 * _frame(config) * d,
        "extras": tokens * 2 * (1 + actions) * d,
        "qkvo": layers * tokens * 2 * (
            d * heads * (nope + rope) + d * (rank + rope)
            + rank * heads * (nope + value) + heads * value * d
        ),
        "absorb": layers * tokens * 2 * heads * rank * (nope + value),
        "cache_leg": layers * rows * cache_pairs(steps, M) * 2 * heads * (
            (rank + rope) + rank
        ),
        "unroll_leg": layers * rows * unroll_pairs(steps, M) * 2 * heads * (
            (nope + rope) + value
        ),
        "mlp": dense * tokens * 3 * 2 * d * config["intermediate_size"],
        "router": sparse * tokens * 2 * d * config["published_n_routed_experts"],
        # tokens x top-k x held / published is a whole number of
        # assignments at the cell's sizes (2,592 x 6 x 16 / 128 = 1,944).
        "experts": (
            sparse * tokens * config["num_experts_per_tok"]
            * config["n_routed_experts"] * 3 * 2 * d * width
        ) // config["published_n_routed_experts"],
        "shared": (
            sparse * tokens * 3 * 2 * d * config["n_shared_experts"] * width
        ),
        "heads": tokens * 2 * d * (actions + 1),
    }


def train_flops_per_step(config: Dict) -> int:
    """Forward and backward operations of one update."""
    parts = forward_flops_per_step(config)
    return (
        3 * sum(parts.values()) - parts["projection"] - parts["cache_leg"]
    )


def attention_param_count(config: Dict) -> int:
    d, heads, rank, nope, rope, value = _widths(config)
    return (
        d * heads * (nope + rope)  # q
        + d * (rank + rope) + rank  # kv_a and its norm
        + rank * heads * (nope + value)  # kv_b
        + heads * value * d  # o
    )


def param_count(config: Dict) -> int:
    """Parameters held on this chip."""
    d, actions = config["hidden_size"], config["num_actions"]
    width = config["moe_intermediate_size"]
    dense = config["first_k_dense_replace"]
    every_layer = attention_param_count(config) + 2 * d  # the two norms
    moe = (
        d * config["published_n_routed_experts"]  # router
        + config["published_n_routed_experts"]  # its selection bias
        + config["n_routed_experts"] * 3 * d * width  # the experts held
        + 3 * d * config["n_shared_experts"] * width
    )
    return (
        _frame(config) * d + d  # projection
        + (1 + actions) * d + d  # extras
        + config["num_hidden_layers"] * every_layer
        + dense * 3 * d * config["intermediate_size"]
        + (config["num_hidden_layers"] - dense) * moe
        + d  # final norm
        + d * (actions + 1) + actions + 1  # heads
    )


def latent_cache_bytes(config: Dict) -> int:
    """The latents and rope keys the update is handed, float32."""
    return 4 * (
        config["num_hidden_layers"] * config["memory_len"]
        * config["batch_size"]
        * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
    )


def least_bytes_per_step(config: Dict) -> int:
    return 6 * 4 * param_count(config) + 2 * latent_cache_bytes(config)
