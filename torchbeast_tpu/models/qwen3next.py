"""One period of Qwen3-Next-80B-A3B-Instruct as the policy trunk
(`--model qwen3next`).

The family is `TransformerNet`'s scaffolding — observation and extras
projections, band / segment / cache-validity masks, `roll_kv_cache`,
the batch-on-axis-1 state convention, `RecurrentPolicyHead` — with the
layers of Qwen3-Next-80B-A3B-Instruct (config.json, `model_type`
qwen3_next) at their published widths. `norm0(x) = x / sqrt(mean(x^2) +
1e-6) * (1 + w)`, w zeros at init (the family's zero-centred RMSNorm).
A layer is `x = x + mixer(norm0(x)); x = x + moe(norm0(x))`, no biases
anywhere; layer l is softmax attention where `(l + 1) % 4 == 0`
(`full_attention_interval`), else Gated DeltaNet: a period is `DDDA`.

  D  Gated DeltaNet (arXiv:2412.06464). in_proj_qkvz d -> 16 key heads
     x [q 128 | k 128 | v 2 x 128 | z 2 x 128]; in_proj_ba d -> 16 x
     [b 2 | a 2]. [q; k; v] (8,192 channels) = silu(causal depthwise
     conv4, no bias). beta = sigmoid(b); g = -exp(A_log) softplus(a +
     dt_bias), a value head each, float32. q, k L2-normalised over
     their 128 (eps 1e-6), q times 128^-0.5; key head j serves value
     heads 2j, 2j + 1. A value head, state S [128 (k), 128 (v)]:
         S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
         S_t = S' + k_t u_t^T;   o_t = S_t^T q_t
     y = rmsnorm_128(o) * w * silu(z) a value head (w ones at init; the
     norm BEFORE the gate); out_proj 4096 -> d. The layer CARRIES S
     [32, B, 128, 128] and the last 3 inputs of its convolution (a
     `Recurrent` entry of `layer_caches`).
  A  gated attention: q_proj d -> 16 heads x [query 256 | gate 256];
     k, v d -> 2 heads of 256; q, k = norm0 over a head's 256; RoPE
     (theta 1e7, rotate-half) on the FIRST 64 columns of a head
     (`partial_rotary_factor` 0.25); softmax(q k^T / 16) v over [cache;
     unroll]; o_proj(attended * sigmoid(gate)). A window entry as every
     other family's; the cache keeps un-rotated keys and a key's
     position is its time relative to the unroll's first step (models/
     olmoe.py); `dense_transformer_attend`, at the learner's sizes its
     fused pass.
  moe (every layer): softmax over 512, the 10 largest, gates over their
     sum; SwiGLU experts of 512; plus sigmoid(shared_expert_gate x) x
     SwiGLU_512(x) for every token (models/moe.py DroplessMoE). The
     load-balance term is sown at `router_aux_loss_coef`.

and one norm0 after the last layer. In `TransformerNet`'s walk a
published layer is TWO entries, its mixer (a `Recurrent` entry or a
window) and its MoE part (None: it carries nothing), each a block of its
own (`block_{2l}`, `block_{2l + 1}`): `--remat all` then rematerialises
them apart, and a backward pass holds one part's intermediates at a
time (as ONE unit the cell's update compiled to 15.1 GiB beside the
benchmark's copy of the weights: the MoE part moves tokens x 10 rows).

THE DELTA RULE IN CHUNKS, EPISODE ENDS INSIDE THEM (`delta_scan`). The
learner computes the recurrence in chunks of 64 steps. With G the
cumulative sum of g inside a chunk, reach(j, i) "j <= i and no episode
end in (j, i]" (a comparison of the two steps' counts of ends, as
models/nemotron3.py `ssd_scan`), D_ij = exp(G_i - G_j) on reach and 0
off it, and e_i = exp(G_i) where no episode ended in the chunk up to
and including i, else 0:

    L_ij = beta_i (k_i . k_j) D_ij  for j < i;  W = (I + L)^-1
    U = W (beta V);  Kd = W (beta e K);  V' = U - Kd S
    O = (Q e) S + ((Q K^T) D) V'
    S_next = e_C S + (D_C. K)^T V'

for the state S that enters the chunk: the recurrence above term for
term (u_i = v'_i), because `done` at step t zeroes what step t reads of
the state before it, which is D and e. W, U, Kd and (Q K^T) D need no
entering state and are made for all chunks at once (`delta_intra`):
where chunks are 64 steps and a key head has two value heads (`ops/
delta_rule.sides_apply`: the learner's unroll) by ops/delta_rule.py's
three cells, two value heads' [64, 64] systems side by side in a lane
tile, L, K K^T, D and the doubling's levels in VMEM alone and W the one
array kept (PR 69: XLA's ~30 ops a layer and direction were 22.9 of the
cell's 264 ms); elsewhere (acting, tier-1's toy widths) by the `jax.
numpy` lines below, which are also what the cells are held to. The
last three lines, which need S, are the chunk-to-chunk pass (`delta_
inter`), in one of two forms chosen by the shapes alone (`ops/
delta_rule.kernels_apply`):

  - an unroll whose chunks are whole sublane tiles at key and value
    widths of whole lane tiles (the learner's [256, B] at the published
    128 x 128) runs ops/delta_rule.py's kernels: a (row, key head)'s
    states stay in VMEM from chunk to chunk, forward and backward, and
    no [128, 128] matrix a chunk ever crosses HBM (the `jax.numpy`
    form's five of them a chunk and value head were 35 of the cell's
    300 ms for 2 ms of arithmetic; PERF.md section 6, PR 61);
  - anything else (acting at T = 1, a chunk of one step; tier-1's toy
    widths) runs `_pass_in_hbm`: S_next is linear in S, so a chunk's
    part of it is made for all chunks at once (`delta_states`: the
    [128, 128] matrix e_C I - (D_C. K)^T Kd and the offset (D_C. K)^T
    U), the pass is one small matmul a chunk, and O follows for all
    chunks from the states that entered them. It is also what the
    kernels are held to (tests/test_delta_rule_kernel.py).

Both make every product at the precision the family states, three
bfloat16 passes; the kernels cut their float32 tiles in VMEM.
Every exponential is of a difference inside one episode or of a masked
-inf. W by block doubling (`unit_lower_inverse`): the inverse of the
diagonal blocks of size s gives that of size 2s as X - X C X, C the
blocks below the diagonal: ten [64, 64] matmuls a chunk and head,
every intermediate a true inverse of a part of I + L (bounded: the
Neumann product (I - L)(I + L^2)... is the same in exact arithmetic and
is not, its powers of L cancel), and no step-by-step substitution. The
solve is differentiated as one unit: W (I + L) = I gives dW = -W dL W,
so <W_bar, dW> = <-W^T W_bar W^T, dL>: the cotangent of L is that
product's part below the diagonal, two matmuls with W the one residual
(JAX's own of the doubling is twenty and keeps every level), and a
rematerialised block keeps W and does not solve again (`make_block`).
T = 1 is a chunk of one step: the recurrence.

The delta rule with a decay for every KEY CHANNEL of a head (Kimi Delta
Attention) lives in models/ling3.py `kda_scan`: there the decay sits
inside the key contraction, D cannot be laid over K K^T from outside,
and a chunk's matrices are built from sub-blocks so that no exponential
leaves float32. This scalar form is kept as it is and not folded into
that one: ONE decay a head and step is all this family's row publishes,
it needs no sub-blocks and no [Q, Dk] factors (a [Q, Q] matrix of
differences of one cumulative sum), and the cell's compiled update is
what PRs 47-67 measured. What the two share is imported from here
(`unit_lower_inverse`, `SOLVED`, `l2_normalise`) and from
models/nemotron3.py; ops/delta_rule.py's kernels serve both, this one
under the chunk's last f, that one under `hand_on`.

A chip may hold a share of each layer's routed experts (`--expert_share
i/n`, as models/mellum2.py); mixers, router and the shared expert are
whole on every chip. Multi-token prediction is not run: a policy trunk
has neither tokens nor an LM head.

The widths are constants of the family (`PUBLISHED`), not flags; a user
cuts depth (`--num_layers`, whole periods of four), chooses the
attention cache (`--memory_len`) and the share.
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from torchbeast_tpu.models.moe import DroplessMoE, held_experts
from torchbeast_tpu.models.nemotron3 import (
    chunk_plan,
    conv_over_episodes,
    dt_bias_init,
    ends_in_chunks,
    in_chunks,
    reaches,
    uniform_between,
)
from torchbeast_tpu.models.olmoe import rope_rotate
from torchbeast_tpu.models.stats import sow_stat
from torchbeast_tpu.models.transformer import (
    Recurrent,
    TransformerNet,
    count_fused_application,
    rematerialised,
)
from torchbeast_tpu.ops import delta_rule, short_conv
from torchbeast_tpu.ops.attention import (
    dense_transformer_attend,
    fused_pass_applies,
)
from torchbeast_tpu.ops.bf16_terms import terms_traced_under
from torchbeast_tpu.telemetry import device_scope

# https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json
# by the name of the field that carries each. `create_model("qwen3next")`
# reads this table when it is called, so a test shrinks the family here.
PUBLISHED = {
    "d_model": 2048,  # hidden_size
    "num_layers": 48,  # num_hidden_layers
    "attention_interval": 4,  # full_attention_interval
    "num_heads": 16,  # num_attention_heads
    "kv_heads": 2,  # num_key_value_heads
    "head_dim": 256,
    "rotary_factor": 0.25,  # partial_rotary_factor
    "rope_theta": 10000000.0,
    "delta_key_heads": 16,  # linear_num_key_heads
    "delta_value_heads": 32,  # linear_num_value_heads
    "delta_key_dim": 128,  # linear_key_head_dim
    "delta_value_dim": 128,  # linear_value_head_dim
    "conv_kernel": 4,  # linear_conv_kernel_dim
    # The reference implementation's default; config.json has no key.
    "chunk_size": 64,
    "num_experts": 512,
    "experts_per_token": 10,  # num_experts_per_tok
    "expert_width": 512,  # moe_intermediate_size
    "shared_width": 512,  # shared_expert_intermediate_size
    "renormalise": True,  # norm_topk_prob
    "rms_norm_eps": 1e-6,
}

_HIGHEST = jax.lax.Precision.HIGHEST


def _block_doubling(L):
    """(I + L)^-1 for L [..., C, C] strictly lower triangular, by block
    doubling: with X the inverses of I + L's diagonal blocks of size s
    (zeros elsewhere) and C its blocks below them inside the diagonal
    blocks of size 2s, X - X C X is the same for 2s ([[A, 0], [C, D]]^-1
    = [[A^-1, 0], [-D^-1 C A^-1, D^-1]]). Float32 at the highest matmul
    precision whatever the caller traces under; an entry of L that is
    exactly zero (an episode end between its steps) leaves the inverse's
    exactly zero."""
    C = L.shape[-1]
    index = np.arange(C)

    def below(size):
        """L's blocks below the diagonal blocks of `size`, inside those
        of twice the size."""
        block, pair = index // size, index // (2 * size)
        return jnp.where(
            (pair[:, None] == pair[None, :]) & (block[:, None] > block[None, :]),
            L, 0.0,
        )

    # Blocks of one step are their own inverse: X is I, and X - X C X is
    # I - C (for one step alone, I).
    inverse, size = jnp.eye(C, dtype=L.dtype) - below(1), 2
    while size < C:
        inverse = inverse - jnp.matmul(
            jnp.matmul(inverse, below(size), precision=_HIGHEST), inverse,
            precision=_HIGHEST,
        )
        size *= 2
    return inverse


# What a rematerialised DeltaNet block keeps of its forward pass: the
# solve's result, which is all its backward reads (`make_block`).
SOLVED = delta_rule.SOLVED


@jax.custom_vjp
def unit_lower_inverse(L):
    """(I + L)^-1 for L [..., C, C], of which the part strictly below
    the diagonal is read (`_block_doubling`), differentiated as ONE
    unit: the inverse is the only residual and its cotangent two
    products (the module's header), where JAX's of the doubling is
    twenty and keeps every level."""
    return _block_doubling(L)


def _solve_forward(L):
    solved = checkpoint_name(_block_doubling(L), SOLVED)
    return solved, solved


def _solve_backward(solved, cotangent):
    # Traced under the name stack of the call it is the backward of:
    # `delta_scan`'s scopes name these two products too.
    C = solved.shape[-1]
    transposed = jnp.swapaxes(solved, -1, -2)
    return (jnp.where(
        np.tril(np.ones((C, C), bool), -1),
        -jnp.matmul(
            transposed,
            jnp.matmul(cotangent, transposed, precision=_HIGHEST),
            precision=_HIGHEST,
        ),
        0.0,
    ),)


unit_lower_inverse.defvjp(_solve_forward, _solve_backward)


def _pass_in_hbm(q, k, from_start, to_end, weights, values, keys_seen, state):
    """`delta_scan`'s chunk-to-chunk pass as `jax.numpy`: S_next is linear
    in S, so a chunk's part of it is made for all chunks at once
    (`delta_states`), the pass itself (`delta_inter`) is one small matmul
    a chunk, and O follows for all chunks from the states that entered
    them. What `ops/delta_rule.py`'s kernels are held to, and what runs
    where they do not apply. q, k [B, c, Q, Hk, Dk]; the rest and the
    results as `delta_rule.chunk_pass`'s."""
    Dk = k.shape[-1]
    with device_scope("delta_states"):
        # What the chunk's own steps leave in the state at its end, and
        # what it makes of the state it was given: both linear in it.
        keys_left = jnp.einsum("bchpj,bcjhd->bchpjd", to_end, k)
        left = jnp.einsum("bchpjd,bchpjv->bchpdv", keys_left, values)
        handed_on = from_start[..., -1, None, None] * jnp.eye(Dk) - (
            jnp.einsum("bchpjd,bchpje->bchpde", keys_left, keys_seen)
        )  # [B, c, Hk, per, Dk, Dk]
    with device_scope("delta_inter"):
        def pass_on(entering, chunk_parts):
            handed_on_c, left_c = chunk_parts
            leaving = jnp.einsum(
                "bhpde,bhpev->bhpdv", handed_on_c, entering
            ) + left_c
            return leaving, entering

        last, entering = jax.lax.scan(
            pass_on, state, (handed_on.swapaxes(0, 1), left.swapaxes(0, 1)),
        )
        corrected = values - jnp.einsum(
            "bchpid,cbhpdv->bchpiv", keys_seen, entering
        )  # V'
        o = jnp.einsum(
            "bcihd,cbhpdv->bchpiv", q, entering
        ) * from_start[..., None] + jnp.einsum(
            "bchpij,bchpjv->bchpiv", weights, corrected
        )
    return o, last


def delta_scan(q, k, v, g, beta, state, done, chunk):
    """The gated delta rule over an unroll, in chunks, with episode ends
    inside them (the module's header has the algebra).

    q, k [B, T, Hk, Dk] (L2-normalised, q scaled); v [B, T, Hv, Dv],
    value head h reading key head h // (Hv / Hk); g (<= 0), beta
    [B, T, Hv]; state [B, Hv, Dk, Dv], what the unroll starts from; done
    [B, T] bool: the state carried INTO a step where it is set is zeros.
    Returns (o [B, T, Hv, Dv], the state after the last step).

    Everything in float32. The last chunk is padded with steps of g = 0,
    beta = 0 and k = 0, which pass the state on as it is. A chunk of one
    step (T = 1) is the recurrence. What needs no entering state
    (`delta_intra`, the solve) is made for all chunks at once, by
    ops/delta_rule.py's cells where `sides_apply` holds and by the
    `jax.numpy` lines elsewhere; the pass from chunk to chunk is
    ops/delta_rule.py's kernels where `kernels_apply(steps, Q, Dk,
    Dv)` holds (the states in VMEM; episode ends are the zeros in
    `from_start`, `to_end` and `decay` that they multiply by) and
    `_pass_in_hbm` elsewhere: functions of the shapes, no flag."""
    rows, steps, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    per = Hv // Hk
    Q, pad, nc = chunk_plan(steps, chunk)

    def heads_first(a):  # [B, T, Hv] -> [B, c, Hk, per, Q]: steps last
        return in_chunks(a, Q, pad).reshape(rows, nc, Q, Hk, per).transpose(
            0, 1, 3, 4, 2
        )

    q, k = in_chunks(q, Q, pad), in_chunks(k, Q, pad)  # [B, c, Q, Hk, Dk]
    v = in_chunks(v, Q, pad).reshape(rows, nc, Q, Hk, per, Dv)
    beta = heads_first(beta)
    G = jnp.cumsum(heads_first(g), axis=-1)
    ends = ends_in_chunks(done, Q, pad)  # [B, c, Q]

    def along_heads(mask):  # [B, c, ...] -> [B, c, 1, 1, ...]
        return mask[:, :, None, None]

    terms = terms_traced_under()
    in_kernels = delta_rule.kernels_apply(steps, Q, Dk, Dv)
    in_cells = delta_rule.sides_apply(steps, Q, Dk, Dv, per)
    if in_kernels:
        heads_first_q = q.transpose(0, 1, 3, 2, 4)  # [B, c, Hk, Q, Dk]
        heads_first_k = k.transpose(0, 1, 3, 2, 4)
    with device_scope("delta_intra"):
        if not in_cells:
            decay = jnp.exp(jnp.where(
                along_heads(reaches(ends)),
                G[..., :, None] - G[..., None, :], -jnp.inf,
            ))  # [B, c, Hk, per, Q, Q]: D, its diagonal ones
        # What step i still sees of the state that entered the chunk.
        from_start = jnp.where(along_heads(ends == 0), jnp.exp(G), 0.0)
        if in_cells:
            # In VMEM too (ops/delta_rule.py's cells): L, K K^T and D
            # are never arrays, and W is kept side by side.
            weights, values, keys_seen = delta_rule.sides_before_the_state(
                heads_first_q, heads_first_k, v, beta, G, ends, terms
            )
        else:
            between_keys = jnp.einsum("bcihd,bcjhd->bchij", k, k)
            with device_scope("delta_solve"):
                solved = unit_lower_inverse(jnp.where(
                    np.tril(np.ones((Q, Q), bool), -1),
                    beta[..., :, None] * between_keys[:, :, :, None] * decay,
                    0.0,
                ))  # W
            by_beta = solved * beta[..., None, :]
            values = jnp.einsum("bchpij,bcjhpv->bchpiv", by_beta, v)  # U
            keys_seen = jnp.einsum(
                "bchpij,bcjhd->bchpid", by_beta * from_start[..., None, :], k
            )  # Kd
            weights = jnp.einsum(
                "bcihd,bcjhd->bchij", q, k
            )[:, :, :, None] * decay
    entering = state.reshape(rows, Hk, per, Dk, Dv).astype(jnp.float32)
    with device_scope("delta_states"):
        # What the chunk's end still sees of each of its steps.
        to_end = jnp.exp(jnp.where(
            along_heads(ends[:, :, -1:] == ends), G[..., -1:] - G, -jnp.inf
        ))  # [B, c, Hk, per, Q]
    if in_kernels:
        # The state from chunk to chunk in VMEM (ops/delta_rule.py).
        with device_scope("delta_inter"):
            o, last = delta_rule.chunk_pass(
                heads_first_q, heads_first_k, from_start, to_end, weights,
                values, keys_seen, entering, terms,
            )
    else:
        o, last = _pass_in_hbm(
            q, k, from_start, to_end, weights, values, keys_seen, entering
        )
    o = o.transpose(0, 1, 4, 2, 3, 5).reshape(rows, nc * Q, Hv, Dv)
    return o[:, :steps], last.reshape(rows, Hv, Dk, Dv)


class RMSNorm0(nn.Module):
    """x / sqrt(mean(x^2) + eps) * (1 + w) over the last axis, w zeros
    at init: the family's zero-centred norm."""

    epsilon: float

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.epsilon
        ) * (1.0 + scale)


def l2_normalise(x, eps=1e-6):
    """x / sqrt(sum(x^2) + eps) over the last axis (eps: assumed, the
    reference implementation's)."""
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps
    )


def normed_then_gated(o, z, scale, eps):
    """rmsnorm(o) * scale * silu(z) over the last axis (a value head):
    the norm first, then the gate; the other way round from models/
    nemotron3.py `gated_group_norm`."""
    normed = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps
    )
    return normed * scale * nn.silu(z)


def _proj(name, width, dtype):
    return nn.Dense(width, use_bias=False, dtype=dtype, name=name)


class _MoEBlock(nn.Module):
    """x + moe(norm0(x)): the second half of either kind of layer."""

    d_model: int
    num_experts: int
    held: Any  # (first, count) of the routed experts, or None for all
    experts_per_token: int
    expert_width: int
    shared_width: int
    renormalise: bool
    aux_loss_weight: float
    rms_norm_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        rows, steps, d = x.shape
        y = DroplessMoE(
            d_ff=self.expert_width,
            num_experts=self.num_experts,
            top_k=self.experts_per_token,
            aux_loss_weight=self.aux_loss_weight,
            renormalise=self.renormalise,
            held=self.held,
            shared_width=self.shared_width,
            shared_token_gate=True,
            dtype=self.dtype,
            name="moe",
        )(RMSNorm0(self.rms_norm_eps, name="norm")(x).reshape(rows * steps, d))
        return x + y.reshape(rows, steps, d)


class _DeltaNetBlock(nn.Module):
    d_model: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int
    chunk_size: int
    rms_norm_eps: float
    # Whether this block is rematerialised under the policy that keeps
    # its solves' results (`make_block`): what the counter says.
    keeps_solved: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, state, done):
        """x [B, T, d]; state (S [Hv, B, Dk, Dv], the convolution's last
        conv_kernel - 1 inputs [K - 1, B, C]) as the state holds them;
        done [B, T]. Returns (y, (S, tail)) to start the next unroll
        from."""
        rows, steps, _ = x.shape
        Hk, Hv = self.key_heads, self.value_heads
        Dk, Dv, K = self.key_dim, self.value_dim, self.conv_kernel
        per = Hv // Hk
        keys, inner = Hk * Dk, Hv * Dv
        channels = 2 * keys + inner
        carried, tail = state

        with device_scope("deltanet_in_proj"):
            h = RMSNorm0(self.rms_norm_eps, name="norm")(x)
            # The published layout: a key head's q, k, its value heads'
            # v and z side by side; likewise b and a.
            qkvz = _proj(
                "in_proj_qkvz", 2 * keys + 2 * inner, self.dtype
            )(h).reshape(rows, steps, Hk, 2 * Dk + 2 * per * Dv)
            ba = _proj("in_proj_ba", 2 * Hv, self.dtype)(h).reshape(
                rows, steps, Hk, 2 * per
            )
            z = qkvz[..., 2 * Dk + per * Dv :].reshape(rows, steps, Hv, Dv)
            # The convolution's channels: all q, all k, all v.
            joined = jnp.concatenate([
                part.reshape(rows, steps, -1) for part in (
                    qkvz[..., :Dk], qkvz[..., Dk : 2 * Dk],
                    qkvz[..., 2 * Dk : 2 * Dk + per * Dv],
                )
            ], axis=-1)

        with device_scope("deltanet_conv"):
            bound = K ** -0.5
            joined, new_tail = conv_over_episodes(
                joined, tail, done,
                self.param(
                    "conv_kernel", uniform_between(-bound, bound),
                    (K, channels),
                ),
                None,
            )
            joined = nn.silu(joined)

        with device_scope("delta_scan"):
            beta = nn.sigmoid(
                ba[..., :per].reshape(rows, steps, Hv).astype(jnp.float32)
            )
            # Assumed (config.json has no key): the reference
            # implementation's init, softplus(dt_bias) log-uniform in
            # [0.001, 0.1] and A uniform in (0, 16).
            g = -jnp.exp(self.param(
                "A_log", uniform_between(0.0, 16.0, jnp.log), (Hv,)
            )) * nn.softplus(
                ba[..., per:].reshape(rows, steps, Hv).astype(jnp.float32)
                + self.param(
                    "dt_bias", dt_bias_init(0.001, 0.1, 0.0001), (Hv,)
                )
            )
            q, k = (
                l2_normalise(joined[..., cut].reshape(rows, steps, Hk, Dk))
                for cut in (slice(0, keys), slice(keys, 2 * keys))
            )
            o, new_carried = delta_scan(
                q * Dk ** -0.5, k,
                joined[..., 2 * keys :].reshape(rows, steps, Hv, Dv),
                g, beta, carried.transpose(1, 0, 2, 3), done, self.chunk_size,
            )

        with device_scope("deltanet_gate_norm"):
            y = normed_then_gated(
                o, z.astype(jnp.float32),
                self.param("gate_norm", nn.initializers.ones, (Dv,)),
                self.rms_norm_eps,
            )
        with device_scope("deltanet_out_proj"):
            x = x + _proj("out_proj", self.d_model, self.dtype)(
                y.reshape(rows, steps, inner).astype(self.dtype)
            ).astype(jnp.float32)

        if not self.is_initializing():
            # How many such layers and the bytes of state a row carries
            # through them; the chunks the unroll's scan was cut into
            # and the episode ends a row had, which every layer says
            # alike.
            Q, _, chunks = chunk_plan(steps, self.chunk_size)
            for name, value, fold in (
                ("delta_applications", 1.0, "sum"),
                # Those whose chunk-to-chunk pass is ops/delta_rule.py's
                # kernels: the learner's unroll, not a step of acting.
                ("delta_kernel_applications",
                 float(delta_rule.kernels_apply(steps, Q, Dk, Dv)), "sum"),
                # Those whose convolution is ops/short_conv.py's.
                ("conv_kernel_applications",
                 float(short_conv.kernels_apply(steps, channels, K)), "sum"),
                ("delta_state_bytes_per_row",
                 4 * (Hv * Dk * Dv + (K - 1) * channels), "sum"),
                ("delta_chunks", chunks, "same"),
                ("delta_resets_per_row",
                 jnp.mean(jnp.sum(done.astype(jnp.float32), axis=1)),
                 "same"),
            ):
                sow_stat(self, name, value, fold)
            if delta_rule.sides_apply(steps, Q, Dk, Dv, per):
                # Those whose W, U, Kd and A are ops/delta_rule.py's
                # cells' too: nothing a chunk owes is XLA's.
                sow_stat(
                    self, "delta_sides_in_kernel_applications", 1.0, "sum"
                )
            if self.keeps_solved:
                sow_stat(
                    self, "delta_solved_bytes_kept",
                    4 * rows * chunks * Hv * Q * Q, "sum",
                )
        return x, (new_carried.transpose(1, 0, 2, 3), new_tail)


class _GatedAttentionBlock(nn.Module):
    d_model: int
    num_heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    memory_len: int
    rms_norm_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache_state, cache_mask, seq_mask, **_):
        """TransformerNet's block contract for a window entry: x
        [B, T, d]; cache_state (k, v) [M, B, kv_heads, hd] as the state
        holds them; cache_mask [B, T, M], seq_mask [B, T, T]. Returns
        (y, k, v) with this unroll's un-rotated k and v [B, T,
        kv_heads, hd]. It attends over `[cache; k]`, `[cache; v]`
        through `dense_transformer_attend` as models/mellum2.py and for
        its reasons: below that name the learner's shapes take the
        fused pass, whose key operands are time-major as the state is."""
        rows, steps, _ = x.shape
        M, H, Hkv, hd = (
            self.memory_len, self.num_heads, self.kv_heads, self.head_dim
        )
        rotary = self.rotary_dim
        cache = tuple(c.transpose(1, 0, 2, 3) for c in cache_state)
        mask = jnp.concatenate([cache_mask, seq_mask], axis=-1)
        inv_freq = self.rope_theta ** (
            -jnp.arange(rotary // 2, dtype=jnp.float32) / (rotary // 2)
        )

        def rotate(x, times):
            """RoPE on a head's first `rotary` columns, the rest as they
            are."""
            return jnp.concatenate([
                rope_rotate(x[..., :rotary], times, inv_freq),
                x[..., rotary:],
            ], axis=-1).astype(self.dtype)

        with device_scope("attention_full"):
            h = RMSNorm0(self.rms_norm_eps, name="norm")(x)
            # A head's query and gate side by side (the published layout).
            q_gate = _proj("q", H * 2 * hd, self.dtype)(h).reshape(
                rows, steps, H, 2 * hd
            )
            q = RMSNorm0(self.rms_norm_eps, name="q_norm")(q_gate[..., :hd])
            k = RMSNorm0(self.rms_norm_eps, name="k_norm")(
                _proj("k", Hkv * hd, self.dtype)(h).reshape(
                    rows, steps, Hkv, hd
                )
            )
            v = _proj("v", Hkv * hd, self.dtype)(h).reshape(
                rows, steps, Hkv, hd
            )
            k_all = jnp.concatenate([
                rotate(cache[0].astype(k.dtype), jnp.arange(M) - M),
                rotate(k, jnp.arange(steps)),
            ], axis=1)
            v_all = jnp.concatenate([cache[1].astype(v.dtype), v], axis=1)
            # The cache is the learner's data: its M keys take no
            # gradient (as models/mellum2.py).
            attended = dense_transformer_attend(
                rotate(q, jnp.arange(steps)), k_all,
                v_all.astype(self.dtype), mask, None, None, M,
            )
            if fused_pass_applies(q.shape, k_all.shape, None):
                count_fused_application(self)
            with device_scope("attention_gate"):
                attended = attended * nn.sigmoid(
                    q_gate[..., hd:].astype(attended.dtype)
                )
            x = x + _proj("o", self.d_model, self.dtype)(
                attended.reshape(rows, steps, H * hd)
            ).astype(jnp.float32)
        if not self.is_initializing():
            sow_stat(self, "attention_gated_applications", 1.0, "sum")
        return x, k.astype(jnp.float32), v.astype(jnp.float32)


class Qwen3NextNet(TransformerNet):
    # Fields the published table sets, or that the blocks do not read:
    # no flag reaches them (models/__init__.py `takes_flag`).
    flag_refused_fields = ("num_experts",)

    num_layers: int = PUBLISHED["num_layers"]
    attention_interval: int = PUBLISHED["attention_interval"]
    d_model: int = PUBLISHED["d_model"]
    num_heads: int = PUBLISHED["num_heads"]
    kv_heads: int = PUBLISHED["kv_heads"]
    head_dim: int = PUBLISHED["head_dim"]
    rotary_factor: float = PUBLISHED["rotary_factor"]
    rope_theta: float = PUBLISHED["rope_theta"]
    delta_key_heads: int = PUBLISHED["delta_key_heads"]
    delta_value_heads: int = PUBLISHED["delta_value_heads"]
    delta_key_dim: int = PUBLISHED["delta_key_dim"]
    delta_value_dim: int = PUBLISHED["delta_value_dim"]
    conv_kernel: int = PUBLISHED["conv_kernel"]
    chunk_size: int = PUBLISHED["chunk_size"]
    # Not the model's 262,144 positions: the attention layers' rolling
    # cache of the policy's own past. The DeltaNet layers carry a state,
    # not a window, and reach as far back as the episode goes.
    memory_len: int = 4095
    num_experts: int = PUBLISHED["num_experts"]
    experts_per_token: int = PUBLISHED["experts_per_token"]
    expert_width: int = PUBLISHED["expert_width"]
    shared_width: int = PUBLISHED["shared_width"]
    renormalise: bool = PUBLISHED["renormalise"]
    rms_norm_eps: float = PUBLISHED["rms_norm_eps"]
    # (i, n): this chip is share i of the n that divide each layer's
    # routed experts (`--expert_share i/n`). (0, 1): all are here.
    expert_share: Tuple[int, int] = (0, 1)
    # The published `router_aux_loss_coef` (assumed: the key is the
    # Qwen3-MoE key set's, as models/mellum2.py).
    aux_loss_weight: float = 0.001
    # Frames to [-1, 1], for the reason models/olmoe.py gives.
    frame_range: Tuple[float, float] = (-1.0, 1.0)
    # For the reason models/mellum2.py gives: even seeded routing.
    zero_init_extras: bool = True
    # Every matmul of the family in three bf16 passes on the MXU (JAX
    # precision `high`), the grouped expert matmuls and the attention
    # layer's fused pass among them (both make the three passes
    # themselves, from float32 tiles cut into two bf16 terms in VMEM),
    # as models/kanana2.py and models/nemotron3.py and for their
    # reason: what feeds a router is rounded, and the tenth choice
    # among 512 close probabilities decides. The scan's decays are
    # float32 and its solve at the highest. PERF.md section 6 (PR 46)
    # has the readings.
    matmul_precision: str = "high"

    def __call__(self, inputs, core_state, **kwargs):
        # Read when a dot is traced, and kept by its gradient's.
        with jax.default_matmul_precision(self.matmul_precision):
            return super().__call__(inputs, core_state, **kwargs)

    def __post_init__(self):
        period = self.attention_interval
        if self.num_layers < 1 or self.num_layers % period:
            raise ValueError(
                f"--num_layers {self.num_layers}: --model qwen3next is cut "
                f"in whole periods of {period} layers ({period - 1} Gated "
                "DeltaNet, one gated attention)"
            )
        self.held_experts()  # refuses a share that is none
        super().__post_init__()

    @nn.nowrap
    def is_attention(self, layer: int) -> bool:
        """Whether published layer `layer` mixes by attention."""
        return (layer + 1) % self.attention_interval == 0

    @nn.nowrap
    def held_experts(self):
        """(first, count) of the experts this chip holds, None for all."""
        return held_experts(self.expert_share, self.num_experts)

    @nn.nowrap
    def layer_caches(self):
        """Two entries a published layer. Its mixer's: an attention
        layer a window of keys and values, a DeltaNet layer its state
        [Hv, B, Dk, Dv] and its convolution's tail [K - 1, B, 2 Hk Dk +
        Hv Dv]. Then its MoE part's: nothing."""
        carried = Recurrent((
            (self.delta_value_heads, self.delta_key_dim, self.delta_value_dim),
            (self.conv_kernel - 1,
             2 * self.delta_key_heads * self.delta_key_dim
             + self.delta_value_heads * self.delta_value_dim),
        ))
        window = (self.memory_len, self.kv_heads, self.head_dim)
        return tuple(
            entry for layer in range(self.num_layers)
            for entry in (
                window if self.is_attention(layer) else carried, None
            )
        )

    @nn.nowrap
    def make_block(self, name: str, layer: int):
        """Block `layer` of the walk: published layer `layer // 2`'s
        mixer (even) or MoE part (odd)."""
        shared = dict(
            d_model=self.d_model, rms_norm_eps=self.rms_norm_eps,
            dtype=self.dtype, name=name,
        )
        # What a rematerialised block keeps of its forward BESIDE what
        # every family's does (models/transformer.py `rematerialised`:
        # the fused attention pass's two results, this family's
        # attention block's among them).
        also_kept = ()
        if layer % 2:
            cls, fields = _MoEBlock, dict(
                num_experts=self.num_experts, held=self.held_experts(),
                experts_per_token=self.experts_per_token,
                expert_width=self.expert_width,
                shared_width=self.shared_width,
                renormalise=self.renormalise,
                aux_loss_weight=self.aux_loss_weight,
            )
        elif self.is_attention(layer // 2):
            cls, fields = _GatedAttentionBlock, dict(
                num_heads=self.num_heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim,
                rotary_dim=int(self.head_dim * self.rotary_factor),
                rope_theta=self.rope_theta, memory_len=self.memory_len,
            )
        else:
            cls, fields = _DeltaNetBlock, dict(
                key_heads=self.delta_key_heads,
                value_heads=self.delta_value_heads,
                key_dim=self.delta_key_dim, value_dim=self.delta_value_dim,
                conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
                keeps_solved=self.remat,
            )
            # Its second forward does not solve again: the inverses (4
            # Hv Q^2 bytes a row and chunk) are all the solve's backward
            # reads, and cost less to keep than to make at that peak.
            also_kept = (SOLVED,)
        return (rematerialised(cls, *also_kept) if self.remat else cls)(
            **fields, **shared
        )

    @nn.nowrap
    def make_final_norm(self):
        return RMSNorm0(self.rms_norm_eps, name="final_norm")

