"""A Mellum2-12B-A2.5B block as the policy trunk (`--model mellum2`).

The family is `TransformerNet`'s scaffolding — observation and extras
projections, band / segment / cache-validity masks, `roll_kv_cache`,
the `[M, B, heads, D]` state convention, `RecurrentPolicyHead` — with
the block of Mellum2-12B-A2.5B-Instruct (config.json, `model_type`
mellum) at its published widths. Layer `l` is of kind `layer_types[l]`
(sliding, sliding, sliding, full, and again):

    h = rmsnorm(x)
    q = Wq h -> [32, 128];  k = Wk h -> [4, 128];  v = Wv h -> [4, 128]
    q, k = rmsnorm_128(q), rmsnorm_128(k)       per head, one learned scale
    query head j reads key/value head j // 8 (ops/attention.py)
    sliding: a query sees itself and the sliding_window - 1 steps before
             it; RoPE theta 500000
    full:    a query sees itself and every step its cache holds; RoPE
             theta 500000 with YaRN's blended frequencies, cos and sin
             times its attention factor
    x = x + Wo attend(rope(q), rope(k), v)
    x = x + moe(rmsnorm(x))    64 SwiGLU experts of 896, top 8, gates
                               renormalised over the chosen, dropless
                               (models/moe.py)

and one RMSNorm after the last layer. The two kinds of layer carry
caches of their own length: a sliding layer `min(memory_len, window -
1)` slots, a full layer `memory_len`. As in models/olmoe.py, a key's
position is its time relative to the unroll's first step and the cache
holds un-rotated keys, so the learner's batch forward equals the actor's
T=1 forwards through the two rolling caches (tests/test_mellum2.py).

A chip may hold a share of each layer's experts (`--expert_share i/n`:
experts i * 64/n .. (i + 1) * 64/n - 1, the chip's part of a layer that
n chips divide). The layer still routes over all 64; what it adds to x
is its own experts' part of the sum, and nothing stands in for the
other chips or their exchange.

The widths are constants of the family (`PUBLISHED`), not flags; a user
cuts depth (`--num_layers`, whole periods of four), chooses the full
layers' cache (`--memory_len`) and the share. `intermediate_size` 7168
is unused (every layer's MLP is `sparse`), the MTP head has no place in
a policy. What the config does not spell out is noted where it is used.
"""

import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from torchbeast_tpu.models.moe import DroplessMoE, held_experts
from torchbeast_tpu.models.olmoe import rope_rotate
from torchbeast_tpu.models.transformer import (
    TransformerNet,
    count_fused_application,
    rematerialised,
)
from torchbeast_tpu.ops.attention import (
    dense_transformer_attend,
    fused_pass_applies,
)
from torchbeast_tpu.telemetry import device_scope

SLIDING, FULL = "sliding_attention", "full_attention"

# https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json
# by the name of the field that carries each. `create_model("mellum2")`
# reads this table when it is called, so a test shrinks the family here.
PUBLISHED = {
    "d_model": 2304,  # hidden_size
    "num_heads": 32,  # num_attention_heads
    "kv_heads": 4,  # num_key_value_heads
    "head_dim": 128,
    "num_layers": 28,  # num_hidden_layers
    "layer_period": (SLIDING, SLIDING, SLIDING, FULL),  # layer_types, x7
    "sliding_window": 1024,
    "num_experts": 64,
    "experts_per_token": 8,  # num_experts_per_tok
    "expert_width": 896,  # moe_intermediate_size
    "renormalise": True,  # norm_topk_prob
    "rms_norm_eps": 1e-6,
    "rope_theta": 500000.0,  # both kinds' rope_parameters
    # rope_parameters.full_attention: factor, original_max_position_
    # embeddings, beta_fast, beta_slow, attention_factor.
    "yarn": (16.0, 8192, 32.0, 1.0, 1.2772588722239782),
}


def rope_default(theta, dim):
    """inv_freq [dim/2] of plain RoPE: theta^(-2i/dim)."""
    return theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)


def rope_yarn(theta, dim, factor, original, beta_fast, beta_slow):
    """inv_freq [dim/2] of YaRN as the config's `rope_parameters` define
    it: dimensions that turn more than `beta_fast` times over the
    `original` positions keep their frequency, those that turn fewer
    than `beta_slow` times have it divided by `factor`, a linear ramp
    between."""
    plain = rope_default(theta, dim)

    def dimension_of(rotations):
        return dim * math.log(original / (2 * math.pi * rotations)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(dimension_of(beta_fast)), 0)
    high = min(math.ceil(dimension_of(beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3),
        0.0, 1.0,
    )
    return ramp * plain / factor + (1.0 - ramp) * plain


class _Mellum2Block(nn.Module):
    kind: str
    d_model: int
    num_heads: int
    kv_heads: int
    head_dim: int
    memory_len: int  # this layer's cache
    num_experts: int
    held: Any  # (first, count) of the experts, or None for all
    experts_per_token: int
    expert_width: int
    renormalise: bool
    rms_norm_eps: float
    rope_theta: float
    yarn: Tuple[float, ...]
    aux_loss_weight: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache_state, cache_mask, seq_mask, **_):
        """TransformerNet's block contract: x [B, T, d]; cache_state
        (k, v) [M, B, kv_heads, hd] as the state holds them; cache_mask
        [B, T, M], seq_mask [B, T, T]. Returns (y, k, v) with this
        unroll's un-rotated k and v [B, T, kv_heads, hd].

        This block still attends over the concatenation `[cache; k]`,
        `[cache; v]` through `dense_transformer_attend`, which it builds
        here: tests/perfbench/test_perfbench_mellum2.py plants its
        repeated-head fault on this module's name for that function and
        its `k_all`, `v_all`, and a PR that changes the program may not
        change the benchmark's files (PERF.md section 7). Since PR 37
        that is cheap where it was not: below that name the learner's
        shapes take the fused pass (ops/fused_attention.py), whose
        scores stay in VMEM and whose key operands are time-major as
        the state is, so the concatenation costs one cast (and
        rotation) of the cache into `[M+T, B, 4, 128]` forward and one
        rematerialised, no longer passes over [B, 4, 8, T, M+T]; the
        body is told that the cache's M keys take no gradient (ROADMAP
        S8 (i) for what is left). The application is counted
        (`attention_fused_applications`) when the rule takes it."""
        B, T, _ = x.shape
        M, H, Hkv, hd = (
            self.memory_len, self.num_heads, self.kv_heads, self.head_dim
        )
        def norm(name):
            return nn.RMSNorm(epsilon=self.rms_norm_eps, name=name)

        def proj(name, width):
            return nn.Dense(
                width, use_bias=False, dtype=self.dtype, name=name
            )

        if self.kind == FULL:
            *yarn, factor = self.yarn
            inv_freq = rope_yarn(self.rope_theta, hd, *yarn)
        else:
            inv_freq, factor = rope_default(self.rope_theta, hd), 1.0

        scope = "attention_full" if self.kind == FULL else "attention_sliding"
        with device_scope(scope):
            # The cache's own layout and cast are the layer's (2.4 ms a
            # step at 4,095 slots): inside its scope.
            cache = tuple(c.transpose(1, 0, 2, 3) for c in cache_state)
            mask = jnp.concatenate([cache_mask, seq_mask], axis=-1)
            inv_freq = jnp.asarray(inv_freq, jnp.float32)
            h = norm("attn_norm")(x)
            # q/k norm per head over the head's 128, one learned scale:
            # the block of the Qwen3-MoE key set this config shares has
            # it, the config has no key that says so.
            q = norm("q_norm")(proj("q", H * hd)(h).reshape(B, T, H, hd))
            k = norm("k_norm")(proj("k", Hkv * hd)(h).reshape(B, T, Hkv, hd))
            v = proj("v", Hkv * hd)(h).reshape(B, T, Hkv, hd)

            def rotate(x, times):
                return rope_rotate(x, times, inv_freq, factor).astype(
                    self.dtype
                )

            # The cache and the unroll rotated apart, then joined: the
            # backward pass then rotates 81 keys' gradient back, not
            # M + 81 keys' of which the concatenation drops M.
            k_all = jnp.concatenate(
                [
                    rotate(cache[0].astype(k.dtype), jnp.arange(M) - M),
                    rotate(k, jnp.arange(T)),
                ],
                axis=1,
            )
            v_all = jnp.concatenate([cache[1].astype(v.dtype), v], axis=1)
            # The cache is the learner's data: its M keys take no
            # gradient, and the fused pass then makes none for them.
            attended = dense_transformer_attend(
                rotate(q, jnp.arange(T)), k_all, v_all.astype(self.dtype),
                mask, None, None, M,
            )
            if fused_pass_applies(q.shape, k_all.shape, None):
                count_fused_application(self)
            x = x + proj("o", self.d_model)(
                attended.reshape(B, T, H * hd)
            ).astype(jnp.float32)

        y = DroplessMoE(
            d_ff=self.expert_width,
            num_experts=self.num_experts,
            top_k=self.experts_per_token,
            aux_loss_weight=self.aux_loss_weight,
            renormalise=self.renormalise,
            held=self.held,
            dtype=self.dtype,
            name="moe",
        )(norm("moe_norm")(x).reshape(B * T, self.d_model))
        x = x + y.reshape(B, T, self.d_model)
        return x, k.astype(jnp.float32), v.astype(jnp.float32)


class Mellum2Net(TransformerNet):
    # Fields the published table sets, or that the block does not read:
    # no flag reaches them (models/__init__.py `takes_flag`).
    flag_refused_fields = ("num_experts",)

    num_layers: int = PUBLISHED["num_layers"]
    d_model: int = PUBLISHED["d_model"]
    num_heads: int = PUBLISHED["num_heads"]
    kv_heads: int = PUBLISHED["kv_heads"]
    head_dim: int = PUBLISHED["head_dim"]
    layer_period: Tuple[str, ...] = PUBLISHED["layer_period"]
    sliding_window: int = PUBLISHED["sliding_window"]
    # The FULL layers' cache: what of its own past the policy can reach
    # at all. Not the model's 131,072 positions; four windows deep. A
    # sliding layer carries min(memory_len, sliding_window - 1) slots.
    memory_len: int = 4095
    num_experts: int = PUBLISHED["num_experts"]
    experts_per_token: int = PUBLISHED["experts_per_token"]
    expert_width: int = PUBLISHED["expert_width"]
    renormalise: bool = PUBLISHED["renormalise"]
    rms_norm_eps: float = PUBLISHED["rms_norm_eps"]
    rope_theta: float = PUBLISHED["rope_theta"]
    yarn: Tuple[float, ...] = PUBLISHED["yarn"]
    # (i, n): this chip is share i of the n that divide each layer's
    # experts (`--expert_share i/n`). (0, 1): all of them are here.
    expert_share: Tuple[int, int] = (0, 1)
    # Frames to [-1, 1], for the reason models/olmoe.py gives.
    frame_range: Tuple[float, float] = (-1.0, 1.0)
    # The side inputs' projection starts at zero, as an adapter's does:
    # the block starts as the model's own (embedding -> blocks) and the
    # reward and last action enter as training finds use for them. At
    # its usual init the one-hot last action is a third of a token's
    # variance, so the tokens fall into six clusters and a seeded router
    # sees the cluster: the fullest expert draws 2-3 times the mean
    # (1.2-1.5 without), and the experts HELD 21-28% of a layer's rows
    # by seed where their share is 25% (PERF.md, PR 32).
    zero_init_extras: bool = True
    # The default of the Qwen3-MoE key set's `router_aux_loss_coef`; the
    # config has no key for it.
    aux_loss_weight: float = 0.001

    def __post_init__(self):
        period = len(self.layer_period)
        if self.num_layers < 1 or self.num_layers % period:
            raise ValueError(
                f"--num_layers {self.num_layers}: --model mellum2 is cut "
                f"in whole periods of {period} layers "
                f"({', '.join(self.layer_period)})"
            )
        self.held_experts()  # refuses a share that is none
        super().__post_init__()

    @nn.nowrap
    def layer_kind(self, layer: int) -> str:
        return self.layer_period[layer % len(self.layer_period)]

    @nn.nowrap
    def layer_caches(self):
        sliding = min(self.memory_len, self.sliding_window - 1)
        return tuple(
            (
                self.memory_len if self.layer_kind(layer) == FULL
                else sliding,
                self.kv_heads, self.head_dim,
            )
            for layer in range(self.num_layers)
        )

    @nn.nowrap
    def held_experts(self):
        """(first, count) of the experts this chip holds, None for all."""
        return held_experts(self.expert_share, self.num_experts)

    @nn.nowrap
    def make_block(self, name: str, layer: int):
        block_cls = (
            rematerialised(_Mellum2Block) if self.remat else _Mellum2Block
        )
        return block_cls(
            kind=self.layer_kind(layer),
            d_model=self.d_model, num_heads=self.num_heads,
            kv_heads=self.kv_heads, head_dim=self.head_dim,
            memory_len=self.layer_caches()[layer][0],
            num_experts=self.num_experts, held=self.held_experts(),
            experts_per_token=self.experts_per_token,
            expert_width=self.expert_width,
            renormalise=self.renormalise,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            yarn=self.yarn, aux_loss_weight=self.aux_loss_weight,
            dtype=self.dtype, name=name,
        )

    @nn.nowrap
    def make_final_norm(self):
        return nn.RMSNorm(epsilon=self.rms_norm_eps, name="final_norm")
