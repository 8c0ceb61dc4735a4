"""One dense layer and whole periods of LFM2-8B-A1B as the policy trunk
(`--model lfm2`).

The family is `TransformerNet`'s scaffolding — observation and extras
projections, band / segment / cache-validity masks, `roll_kv_cache`,
the batch-on-axis-1 state convention, `RecurrentPolicyHead` — with the
layers of LFM2-8B-A1B (Liquid AI; config.json, `model_type` lfm2_moe)
at their published widths. `norm(x) = x / sqrt(mean(x^2) + 1e-5) * w`,
w ones at init. A layer is `h = x + operator(norm(x)); y = h +
ffn(norm(h))`, no biases anywhere; the operator is what `layer_types`
says, the ffn a dense SwiGLU in the first `num_dense_layers` layers and
a mixture of experts after.

  c  gated short convolution (`conv_L_cache` 3, `conv_bias` false).
     in_proj d -> [B | C | u], 3 d; y = out_proj(C * conv3(B * u)), the
     convolution causal and depthwise over the PRODUCT B * u, no
     activation: the two products are the gates. The layer CARRIES the
     last conv_kernel - 1 = 2 values of B * u, [2, B, d] (a `Recurrent`
     entry of `layer_caches` that is nothing but a tail): 16 KB a row
     where every other family's mixer carries megabytes. `done` at step
     t cuts the taps that lie before t (`conv_over_episodes`, shared
     with models/nemotron3.py and models/qwen3next.py: here K = 3, the
     input a gated product, the output read without a silu).
  A  grouped-query attention: 32 query heads on 8 key/value heads of
     64, bias-free q / k / v / o; an RMSNorm with a learned [64] scale
     on every q and k head BEFORE RoPE (theta 1e6, rotate-half over the
     whole head); softmax(q k^T / 8) v over [cache; unroll]. A window
     entry as every other family's; the cache keeps un-rotated (normed)
     keys and a key's position is its time relative to the unroll's
     first step (models/olmoe.py); `dense_transformer_attend`, at the
     learner's sizes its fused pass, which pads a head of 64 to the 128
     lanes with zero columns (ops/fused_attention.py).
  ffn  dense: w2(silu(w1 x) * w3 x) of 7168. MoE: s = sigmoid(W_r x)
     over 32; the 4 largest of s + expert_bias are chosen
     (`use_expert_bias`; the bias takes no gradient and moves by the
     load, DeepSeek-V3's rule as models/kanana2.py: assumed, config.json
     has no key for rule or speed); the gates are s at the chosen over
     (their sum + 1e-6) (`norm_topk_prob`), times `routed_scaling_
     factor` 1; SwiGLU experts of 1792, no shared expert (models/moe.py
     DroplessMoE).

and one norm after the last layer. A published layer is ONE block of
`TransformerNet`'s walk, operator and ffn together (`block_{l}`): the
cell's update with every block rematerialised compiles under the
rule's 15.0 GiB as it is (PERF.md section 6, PR 53), so Qwen3-Next's
reason for two blocks a layer does not arise.

The published order is `c c A c c c A c c c A c c c A c c c A c c A c
c`, no whole number of periods, its first two layers dense.
`--num_layers 24` builds it; a cut builds the LAST leading dense layer
(published layer 1: a conv operator over the dense SwiGLU) and then
whole periods `A c c c` (published layers 2-5 first): 1 + 4k layers.

A chip may hold a share of each layer's routed experts (`--expert_share
i/n`, as models/mellum2.py); operators, router and the dense layer are
whole on every chip.

The widths are constants of the family (`PUBLISHED`), not flags; a user
cuts depth (`--num_layers`), chooses the attention cache (`--memory_
len`) and the share.
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.moe import DroplessMoE, held_experts
from torchbeast_tpu.models.nemotron3 import (
    conv_over_episodes,
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
from torchbeast_tpu.ops import short_conv
from torchbeast_tpu.ops.attention import (
    dense_transformer_attend,
    fused_pass_applies,
)
from torchbeast_tpu.telemetry import device_scope

CONV, ATTENTION = "conv", "full_attention"

# https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json by
# the name of the field that carries each. `create_model("lfm2")` reads
# this table when it is called, so a test shrinks the family here.
PUBLISHED = {
    "d_model": 2048,  # hidden_size
    "num_layers": 24,  # num_hidden_layers
    "layer_types": (CONV, CONV, ATTENTION) + (CONV, CONV, CONV, ATTENTION) * 4
    + (CONV, CONV, ATTENTION, CONV, CONV),
    # What a cut repeats after its one dense layer: published layers
    # 2-5, the published 1 : 3 (config.json has the 24 kinds alone).
    "layer_period": (ATTENTION, CONV, CONV, CONV),
    "num_dense_layers": 2,
    "num_heads": 32,  # num_attention_heads
    "kv_heads": 8,  # num_key_value_heads
    "head_dim": 64,  # hidden_size / num_attention_heads
    "conv_kernel": 3,  # conv_L_cache
    "conv_bias": False,
    "dense_width": 7168,  # intermediate_size
    "expert_width": 1792,  # moe_intermediate_size
    "num_experts": 32,
    "experts_per_token": 4,  # num_experts_per_tok
    "renormalise": True,  # norm_topk_prob
    "routed_scaling": 1.0,  # routed_scaling_factor
    "use_expert_bias": True,
    "norm_eps": 1e-5,
    "rope_theta": 1000000.0,
}


def _norm(name, eps):
    return nn.RMSNorm(epsilon=eps, name=name)


def _proj(name, width, dtype):
    return nn.Dense(width, use_bias=False, dtype=dtype, name=name)


def _feed_forward(block, x):
    """`x + ffn(norm(x))` of either kind of layer: the second half of
    `block`, whose fields say which ffn and how wide."""
    rows, steps, d = x.shape
    h = _norm("ffn_norm", block.norm_eps)(x)
    if block.dense_width:
        with device_scope("dense_mlp"):
            hidden = nn.silu(
                _proj("w1", block.dense_width, block.dtype)(h)
            ) * _proj("w3", block.dense_width, block.dtype)(h)
            return x + _proj("w2", d, block.dtype)(hidden).astype(
                jnp.float32
            )
    y = DroplessMoE(
        d_ff=block.expert_width,
        num_experts=block.num_experts,
        top_k=block.experts_per_token,
        aux_loss_weight=0.0,  # the bias balances; no auxiliary loss
        renormalise=block.renormalise,
        gate_sum_floor=block.gate_sum_floor,
        held=block.held,
        scoring="sigmoid",
        selection_bias=block.use_expert_bias,
        bias_update_rate=block.bias_update_rate,
        routed_scaling=block.routed_scaling,
        dtype=block.dtype,
        name="moe",
    )(h.reshape(rows * steps, d))
    return x + y.reshape(rows, steps, d)


class _Layer(nn.Module):
    """What both kinds of layer state of their ffn (`_feed_forward`):
    `dense_width` > 0 a dense SwiGLU of that width, else the experts."""

    d_model: int
    norm_eps: float
    dense_width: int
    num_experts: int
    held: Any  # (first, count) of the routed experts, or None for all
    experts_per_token: int
    expert_width: int
    renormalise: bool
    gate_sum_floor: float
    routed_scaling: float
    use_expert_bias: bool
    bias_update_rate: float
    dtype: Any


class _ConvBlock(_Layer):
    conv_kernel: int

    @nn.compact
    def __call__(self, x, state, done):
        """x [B, T, d]; state (the convolution's last conv_kernel - 1
        inputs [K - 1, B, d], values of the product B * u),) as the
        state holds it; done [B, T]. Returns (y, (tail,)) to start the
        next unroll from."""
        d, K = self.d_model, self.conv_kernel
        (tail,) = state
        with device_scope("conv_operator"):
            with device_scope("conv_in_proj"):
                h = _norm("operator_norm", self.norm_eps)(x)
                gate_in, gate_out, u = jnp.split(
                    _proj("in_proj", 3 * d, self.dtype)(h).astype(
                        jnp.float32
                    ),
                    3, axis=-1,
                )  # the published order: B, C, x
            with device_scope("conv_gate_taps"):
                bound = K ** -0.5
                conv, new_tail = conv_over_episodes(
                    gate_in * u, tail, done,
                    self.param(
                        "conv_kernel", uniform_between(-bound, bound), (K, d)
                    ),
                    None,  # `conv_bias` false
                )
                gated = gate_out * conv
            with device_scope("conv_out_proj"):
                x = x + _proj("out_proj", d, self.dtype)(
                    gated.astype(self.dtype)
                ).astype(jnp.float32)
        if not self.is_initializing():
            # How many such layers and the bytes of state a row carries
            # through them; the episode ends a row had, which every
            # layer says alike.
            for name, value, fold in (
                ("conv_layers", 1.0, "sum"),
                # Those whose taps are ops/short_conv.py's kernels.
                ("conv_kernel_applications",
                 float(short_conv.kernels_apply(x.shape[1], d, K)), "sum"),
                ("conv_state_bytes_per_row", 4 * (K - 1) * d, "sum"),
                ("conv_resets_per_row",
                 jnp.mean(jnp.sum(done.astype(jnp.float32), axis=1)),
                 "same"),
            ):
                sow_stat(self, name, value, fold)
        return _feed_forward(self, x), (new_tail,)


class _AttentionBlock(_Layer):
    num_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    memory_len: int

    @nn.compact
    def __call__(self, x, cache_state, cache_mask, seq_mask, **_):
        """TransformerNet's block contract for a window entry: x
        [B, T, d]; cache_state (k, v) [M, B, kv_heads, hd] as the state
        holds them; cache_mask [B, T, M], seq_mask [B, T, T]. Returns
        (y, k, v) with this unroll's normed, un-rotated k and v [B, T,
        kv_heads, hd]. It attends over `[cache; k]`, `[cache; v]`
        through `dense_transformer_attend` as models/mellum2.py and for
        its reasons: below that name the learner's shapes take the
        fused pass, whose key operands are time-major as the state is."""
        rows, steps, _ = x.shape
        M, H, Hkv, hd = (
            self.memory_len, self.num_heads, self.kv_heads, self.head_dim
        )
        cache = tuple(c.transpose(1, 0, 2, 3) for c in cache_state)
        mask = jnp.concatenate([cache_mask, seq_mask], axis=-1)
        inv_freq = self.rope_theta ** (
            -jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2)
        )

        def rotate(x, times):
            return rope_rotate(x, times, inv_freq).astype(self.dtype)

        with device_scope("attention"):
            h = _norm("operator_norm", self.norm_eps)(x)
            q = _norm("q_norm", self.norm_eps)(
                _proj("q", H * hd, self.dtype)(h).reshape(rows, steps, H, hd)
            )
            k = _norm("k_norm", self.norm_eps)(
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
            x = x + _proj("o", self.d_model, self.dtype)(
                attended.reshape(rows, steps, H * hd)
            ).astype(jnp.float32)
        return (
            _feed_forward(self, x), k.astype(jnp.float32),
            v.astype(jnp.float32),
        )


class Lfm2Net(TransformerNet):
    # Fields the published table sets, or that the blocks do not read:
    # no flag reaches them (models/__init__.py `takes_flag`).
    flag_refused_fields = ("num_experts",)

    num_layers: int = PUBLISHED["num_layers"]
    layer_types: Tuple[str, ...] = PUBLISHED["layer_types"]
    layer_period: Tuple[str, ...] = PUBLISHED["layer_period"]
    num_dense_layers: int = PUBLISHED["num_dense_layers"]
    d_model: int = PUBLISHED["d_model"]
    num_heads: int = PUBLISHED["num_heads"]
    kv_heads: int = PUBLISHED["kv_heads"]
    head_dim: int = PUBLISHED["head_dim"]
    conv_kernel: int = PUBLISHED["conv_kernel"]
    conv_bias: bool = PUBLISHED["conv_bias"]
    dense_width: int = PUBLISHED["dense_width"]
    # Not the model's 128,000 positions: the attention layers' rolling
    # cache of the policy's own past. The conv layers carry two values
    # of a product, not a window, and see no further back than those.
    memory_len: int = 4095
    num_experts: int = PUBLISHED["num_experts"]
    experts_per_token: int = PUBLISHED["experts_per_token"]
    expert_width: int = PUBLISHED["expert_width"]
    renormalise: bool = PUBLISHED["renormalise"]
    routed_scaling: float = PUBLISHED["routed_scaling"]
    use_expert_bias: bool = PUBLISHED["use_expert_bias"]
    norm_eps: float = PUBLISHED["norm_eps"]
    rope_theta: float = PUBLISHED["rope_theta"]
    # (i, n): this chip is share i of the n that divide each layer's
    # routed experts (`--expert_share i/n`). (0, 1): all are here.
    expert_share: Tuple[int, int] = (0, 1)
    # What the chosen scores' sum is raised by before the gates are
    # divided by it: the reference implementation's 1e-6 (config.json
    # has no key).
    gate_sum_floor: float = 1e-6
    # DeepSeek-V3's bias update speed, as models/kanana2.py; config.json
    # has no key for the rule or the speed.
    bias_update_rate: float = 0.001
    # Frames to [-1, 1], for the reason models/olmoe.py gives.
    frame_range: Tuple[float, float] = (-1.0, 1.0)
    # For the reason models/mellum2.py gives: even seeded routing.
    zero_init_extras: bool = True
    # Every matmul of the family in three bf16 passes on the MXU (JAX
    # precision `high`), the grouped expert matmuls and the attention
    # layer's fused pass among them (both make the three passes
    # themselves, from float32 tiles cut into two bf16 terms in VMEM),
    # as models/kanana2.py, models/nemotron3.py and models/qwen3next.py
    # and for their reason:
    # what feeds a router is rounded, and the fourth choice among 32
    # close scores decides. PERF.md section 6 (PR 53) has the readings.
    matmul_precision: str = "high"

    def __call__(self, inputs, core_state, **kwargs):
        # Read when a dot is traced, and kept by its gradient's.
        with jax.default_matmul_precision(self.matmul_precision):
            return super().__call__(inputs, core_state, **kwargs)

    def __post_init__(self):
        if self.conv_bias:
            raise ValueError(
                "conv_bias: the published convolution has none, and a "
                "biased one is not built"
            )
        self.layers()  # refuses a depth that is no cut of the model
        self.held_experts()  # and a share that is none
        super().__post_init__()

    @nn.nowrap
    def layers(self) -> Tuple[Tuple[str, bool], ...]:
        """(operator, whether its ffn is dense) a layer: the published
        order when all its layers are asked for, else the last leading
        dense layer and whole periods of `layer_period` after it."""
        if self.num_layers == len(self.layer_types):
            return tuple(
                (kind, layer < self.num_dense_layers)
                for layer, kind in enumerate(self.layer_types)
            )
        period = len(self.layer_period)
        periods, rest = divmod(self.num_layers - 1, period)
        if periods < 1 or rest:
            raise ValueError(
                f"--num_layers {self.num_layers}: --model lfm2 is cut as "
                f"its last leading dense layer and whole periods of "
                f"{period} layers after it ({' '.join(self.layer_period)}): "
                f"1 + {period}k layers, or is all {len(self.layer_types)}"
            )
        lead = self.layer_types[self.num_dense_layers - 1]
        return ((lead, True),) + tuple(
            (kind, False) for kind in self.layer_period * periods
        )

    @nn.nowrap
    def held_experts(self):
        """(first, count) of the experts this chip holds, None for all."""
        return held_experts(self.expert_share, self.num_experts)

    @nn.nowrap
    def layer_caches(self):
        """An entry a published layer: an attention layer a window of
        keys and values, a conv layer the tail of its convolution alone,
        [K - 1, B, d]."""
        carried = Recurrent(((self.conv_kernel - 1, self.d_model),))
        window = (self.memory_len, self.kv_heads, self.head_dim)
        return tuple(
            window if kind == ATTENTION else carried
            for kind, _ in self.layers()
        )

    @nn.nowrap
    def make_block(self, name: str, layer: int):
        kind, dense = self.layers()[layer]
        shared = dict(
            d_model=self.d_model, norm_eps=self.norm_eps,
            dense_width=self.dense_width if dense else 0,
            num_experts=self.num_experts, held=self.held_experts(),
            experts_per_token=self.experts_per_token,
            expert_width=self.expert_width, renormalise=self.renormalise,
            gate_sum_floor=self.gate_sum_floor,
            routed_scaling=self.routed_scaling,
            use_expert_bias=self.use_expert_bias,
            bias_update_rate=self.bias_update_rate,
            dtype=self.dtype, name=name,
        )
        if kind == ATTENTION:
            cls, fields = _AttentionBlock, dict(
                num_heads=self.num_heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, rope_theta=self.rope_theta,
                memory_len=self.memory_len,
            )
        elif kind == CONV:
            cls, fields = _ConvBlock, dict(conv_kernel=self.conv_kernel)
        else:
            raise ValueError(f"layer_types: unknown operator {kind!r}")
        return (rematerialised(cls) if self.remat else cls)(
            **fields, **shared
        )

    @nn.nowrap
    def make_final_norm(self):
        return _norm("final_norm", self.norm_eps)
