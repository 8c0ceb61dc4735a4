"""One period of Granite-4.0-H-Micro as the policy trunk (`--model
granite4`).

The family is `TransformerNet`'s scaffolding — observation and extras
projections, band / segment / cache-validity masks, `roll_kv_cache`,
the batch-on-axis-1 state convention, `RecurrentPolicyHead` — with the
layers of ibm-granite/granite-4.0-h-micro (config.json, `model_type`
granitemoehybrid) at their published widths. Float32, `rmsnorm` eps
1e-5, no bias but the convolution's. The encoder's output enters the
layers times `embedding_multiplier` 12 (`TransformerNet.input_scale`).
A layer is a mixer AND a feed-forward part, each branch times
`residual_multiplier` 0.22 before it joins the stream:

    x = x + 0.22 * mixer_l(rmsnorm(x))          mixer by layer_types[l]
    [g | v] = W_in rmsnorm(x)                   `shared_mlp`, 2048 ->
    x = x + 0.22 * W_out (silu(g) * v)          2 x 8192 -> 2048

  mamba      the Mamba-2 mixer of models/nemotron3.py (`mamba_mixer`,
             the one copy): 64 heads of 64 on ONE B/C group that every
             head reads, state 128, conv 4, chunks of 256; the gated
             norm over all 4096. Carries its state [64, B, 64, 128] and
             the convolution's last 3 inputs [3, B, 4352] (a
             `Recurrent` entry of `layer_caches`).
  attention  32 query heads of 64 on 8 key/value heads, NO positional
             embedding (`position_embedding_type` "nope"), softmax(q
             k^T * attention_multiplier) v over [cache; unroll] with
             `attention_multiplier` 0.015625 = 1/64, which is NOT
             64^-0.5 (`dense_transformer_attend(..., scale=)`;
             `attention_mixer` of models/nemotron3.py). A window entry
             of `--memory_len` slots.

and after the last layer one RMSNorm and the heads, the policy logits
divided by `logits_scaling` 8 (`RecurrentPolicyHead.logits_scale`), the
baseline not. `num_local_experts` 0: no router and no expert; the
`shared_mlp` is the whole feed-forward part.

Layers 0-9 of the published 40 are `MMMMM*MMMM` and layers 10-19,
20-29, 30-39 repeat them letter for letter: `--num_layers` takes whole
periods of 10, or all 40 in the published order. `done` at step t
zeroes the Mamba state carried into t and the taps before t and cuts
the attention cache, as models/nemotron3.py says; the learner's chunked
scan and an actor's T=1 steps agree through the carried state
(tests/test_granite4.py).

The widths are constants of the family (`PUBLISHED`), not flags; a user
cuts depth (`--num_layers`) and chooses the attention cache
(`--memory_len`). What the config does not spell out is noted where it
is used.
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.nemotron3 import (
    attention_mixer,
    cache_and_mask,
    count_mamba_application,
    mamba_mixer,
)
from torchbeast_tpu.models.stats import sow_stat
from torchbeast_tpu.models.transformer import (
    Recurrent,
    TransformerNet,
    rematerialised,
)
from torchbeast_tpu.telemetry import device_scope

MAMBA, ATTENTION = "mamba", "attention"
_PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4

# https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
# by the name of the field that carries each. `create_model("granite4")`
# reads this table when it is called, so a test shrinks the family here.
PUBLISHED = {
    "d_model": 2048,  # hidden_size
    "num_layers": 40,  # num_hidden_layers
    "layer_types": _PERIOD * 4,  # layer_types, as published
    "layer_period": _PERIOD,  # layer_types[0:10], what a cut is made of
    "num_heads": 32,  # num_attention_heads
    "kv_heads": 8,  # num_key_value_heads
    "head_dim": 64,  # hidden_size / num_attention_heads (no key)
    "mamba_heads": 64,  # mamba_n_heads
    "mamba_head_dim": 64,  # mamba_d_head
    "mamba_groups": 1,  # mamba_n_groups
    "state_size": 128,  # mamba_d_state
    "conv_kernel": 4,  # mamba_d_conv
    "chunk_size": 256,  # mamba_chunk_size
    "mlp_width": 8192,  # shared_intermediate_size
    "input_scale": 12.0,  # embedding_multiplier
    "attention_multiplier": 0.015625,
    "residual_multiplier": 0.22,
    "logits_scale": 1.0 / 8,  # 1 / logits_scaling
    "rms_norm_eps": 1e-5,
    # ASSUMED: config.json has no key for the time step's limits; the
    # Mamba-2 reference implementation's defaults, as models/nemotron3.py.
    "time_step_min": 0.001,
    "time_step_max": 0.1,
    "time_step_floor": 0.0001,
}


def _norm(name, eps):
    return nn.RMSNorm(epsilon=eps, name=name)


def shared_mlp(module, x):
    """x + residual_multiplier * W_out (silu(g) * v), [g | v] = W_in
    rmsnorm(x): the layer's second sublayer, in the compact method of
    `module` (which states `d_model`, `mlp_width`, `residual_multiplier`,
    `rms_norm_eps`, `dtype`). `input_linear` is ONE matrix, the gate's
    half first, as published."""
    width = module.mlp_width
    with device_scope("dense_mlp"):
        joined = nn.Dense(
            2 * width, use_bias=False, dtype=module.dtype,
            name="input_linear",
        )(_norm("mlp_norm", module.rms_norm_eps)(x))
        hidden = nn.silu(joined[..., :width]) * joined[..., width:]
        x = x + module.residual_multiplier * nn.Dense(
            module.d_model, use_bias=False, dtype=module.dtype,
            name="output_linear",
        )(hidden).astype(jnp.float32)
    sow_stat(module, "mlp_applications", 1.0, "sum")
    return x


class _MambaLayer(nn.Module):
    d_model: int
    heads: int
    head_dim: int
    groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    time_step: Tuple[float, float, float]  # min, max, floor
    mlp_width: int
    residual_multiplier: float
    rms_norm_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, state, done):
        """x [B, T, d]; state and done as `mamba_mixer` takes them.
        Returns (y, (h, tail)) to start the next unroll from."""
        with device_scope("mamba_in_proj"):
            h = _norm("norm", self.rms_norm_eps)(x)
        branch, new_state = mamba_mixer(self, h, state, done)
        with device_scope("mamba_out_proj"):
            x = x + self.residual_multiplier * branch
        count_mamba_application(self, done)
        return shared_mlp(self, x), new_state


class _AttentionLayer(nn.Module):
    d_model: int
    num_heads: int
    kv_heads: int
    head_dim: int
    memory_len: int
    attention_multiplier: float
    mlp_width: int
    residual_multiplier: float
    rms_norm_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache_state, cache_mask, seq_mask, **_):
        """TransformerNet's block contract for a window entry: x
        [B, T, d]; cache_state (k, v) [M, B, kv_heads, hd] as the state
        holds them; cache_mask [B, T, M], seq_mask [B, T, T]. Returns
        (y, k, v), this unroll's keys and values [B, T, kv_heads, hd]."""
        cache, mask = cache_and_mask(cache_state, cache_mask, seq_mask)
        with device_scope("attention_full"):
            branch, k, v = attention_mixer(
                self, _norm("norm", self.rms_norm_eps)(x), cache, mask,
                scale=self.attention_multiplier,
            )
            x = x + self.residual_multiplier * branch
        sow_stat(self, "attention_unrotated_applications", 1.0, "sum")
        return shared_mlp(self, x), k, v


class Granite4Net(TransformerNet):
    # Fields the published table sets, or that the layers do not read:
    # no flag reaches them (models/__init__.py `takes_flag`).
    flag_refused_fields = ("num_experts",)

    num_layers: int = PUBLISHED["num_layers"]
    layer_types: Tuple[str, ...] = PUBLISHED["layer_types"]
    layer_period: Tuple[str, ...] = PUBLISHED["layer_period"]
    d_model: int = PUBLISHED["d_model"]
    num_heads: int = PUBLISHED["num_heads"]
    kv_heads: int = PUBLISHED["kv_heads"]
    # ASSUMED: hidden_size / num_attention_heads; config.json has no
    # `head_dim`.
    head_dim: int = PUBLISHED["head_dim"]
    mamba_heads: int = PUBLISHED["mamba_heads"]
    mamba_head_dim: int = PUBLISHED["mamba_head_dim"]
    mamba_groups: int = PUBLISHED["mamba_groups"]
    state_size: int = PUBLISHED["state_size"]
    conv_kernel: int = PUBLISHED["conv_kernel"]
    chunk_size: int = PUBLISHED["chunk_size"]
    mlp_width: int = PUBLISHED["mlp_width"]
    # Not the model's 131,072 positions: the attention layers' rolling
    # cache of the policy's own past. The Mamba layers carry a state,
    # not a window, and reach as far back as the episode goes.
    memory_len: int = 4095
    # `embedding_multiplier`: the encoder's output (what stands in the
    # embedding's place) times 12, and (ASSUMED, as models/trinity.py)
    # the observation projection's init over it, so that the stream
    # starts where the other families' does.
    input_scale: float = PUBLISHED["input_scale"]
    attention_multiplier: float = PUBLISHED["attention_multiplier"]
    residual_multiplier: float = PUBLISHED["residual_multiplier"]
    logits_scale: float = PUBLISHED["logits_scale"]
    rms_norm_eps: float = PUBLISHED["rms_norm_eps"]
    time_step_min: float = PUBLISHED["time_step_min"]
    time_step_max: float = PUBLISHED["time_step_max"]
    time_step_floor: float = PUBLISHED["time_step_floor"]
    # Frames to [-1, 1], for the reason models/olmoe.py gives.
    frame_range: Tuple[float, float] = (-1.0, 1.0)
    # As the other families'; there is no router here for the side
    # inputs to cluster.
    zero_init_extras: bool = True
    # Every matmul of the family at this JAX precision. Decided on the
    # chip against the float32 reference (PERF.md section 6, PR 64).
    matmul_precision: str = "high"

    def __call__(self, inputs, core_state, **kwargs):
        # Read when a dot is traced, and kept by its gradient's.
        with jax.default_matmul_precision(self.matmul_precision):
            return super().__call__(inputs, core_state, **kwargs)

    def __post_init__(self):
        self.pattern()  # refuses a depth that is no whole periods
        super().__post_init__()

    @nn.nowrap
    def pattern(self) -> Tuple[str, ...]:
        """A kind a layer: the published order when all its layers are
        asked for, else whole periods of `layer_period`."""
        if self.num_layers == len(self.layer_types):
            return self.layer_types
        period = len(self.layer_period)
        if self.num_layers < 1 or self.num_layers % period:
            raise ValueError(
                f"--num_layers {self.num_layers}: --model granite4 is cut "
                f"in whole periods of {period} layers "
                f"({', '.join(self.layer_period)}), or is all "
                f"{len(self.layer_types)}"
            )
        return self.layer_period * (self.num_layers // period)

    @nn.nowrap
    def layer_caches(self):
        """By the layer's kind: attention a window of keys and values,
        Mamba-2 a state [H, B, P, N] and its convolution's tail
        [K - 1, B, H P + 2 G N]."""
        carried = Recurrent((
            (self.mamba_heads, self.mamba_head_dim, self.state_size),
            (self.conv_kernel - 1,
             self.mamba_heads * self.mamba_head_dim
             + 2 * self.mamba_groups * self.state_size),
        ))
        by_kind = {
            ATTENTION: (self.memory_len, self.kv_heads, self.head_dim),
            MAMBA: carried,
        }
        return tuple(by_kind[kind] for kind in self.pattern())

    @nn.nowrap
    def make_block(self, name: str, layer: int):
        shared = dict(
            d_model=self.d_model, mlp_width=self.mlp_width,
            residual_multiplier=self.residual_multiplier,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype, name=name,
        )
        if self.pattern()[layer] == MAMBA:
            cls, fields = _MambaLayer, dict(
                heads=self.mamba_heads, head_dim=self.mamba_head_dim,
                groups=self.mamba_groups, state_size=self.state_size,
                conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
                time_step=(
                    self.time_step_min, self.time_step_max,
                    self.time_step_floor,
                ),
            )
        else:
            cls, fields = _AttentionLayer, dict(
                num_heads=self.num_heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, memory_len=self.memory_len,
                attention_multiplier=self.attention_multiplier,
            )
        return (rematerialised(cls) if self.remat else cls)(
            **fields, **shared
        )

    @nn.nowrap
    def make_final_norm(self):
        return _norm("final_norm", self.rms_norm_eps)
