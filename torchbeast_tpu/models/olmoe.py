"""An OLMoE-1B-7B block as the policy trunk (`--model olmoe`).

The family is `TransformerNet`'s scaffolding — observation and extras
projections, band / segment / cache-validity masks, `roll_kv_cache`, the
`[M, B, H, D]` state convention, `RecurrentPolicyHead` — with the block
of OLMoE-1B-7B-0125-Instruct (config.json, `model_type` olmoe) at its
published widths. Per layer:

    h = rmsnorm(x)
    q = rmsnorm_d(Wq h), k = rmsnorm_d(Wk h), v = Wv h      (no bias)
    x = x + Wo attend(rope(q), rope(cache.k) | rope(k), cache.v | v)
                                  16 heads; the cache and the unroll are
                                  two legs of one softmax
    x = x + moe(rmsnorm(x))       64 SwiGLU experts, top 8, dropless,
                                  gates not renormalised (models/moe.py)

and one RMSNorm after the last layer. RoPE (theta 10000, rotate-half) is
applied where scores are formed, with a key's position its time relative
to the unroll's first step (cache slot m of M is m - M, unroll step j is
j); the cache holds un-rotated keys, so scores depend on time differences
alone and the learner's batch forward equals the actor's T=1 forwards
through the rolling cache (tests/test_olmoe.py).

The widths are constants of the family (`PUBLISHED`), not flags; a user
cuts depth (`--num_layers`) and chooses the window (`--memory_len`).
What the config does not spell out is noted where it is used.
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.moe import DroplessMoE
from torchbeast_tpu.models.transformer import (
    TransformerNet,
    count_two_leg_application,
)
from torchbeast_tpu.ops.attention import cached_transformer_attend
from torchbeast_tpu.telemetry import device_scope

# https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json
# by the name of the field that carries each. `expert_width` is the
# config's `intermediate_size`, read as the width of ONE expert (the
# config has no key of its own for it). `create_model("olmoe")` reads
# this table when it is called, so a test shrinks the family here.
PUBLISHED = {
    "d_model": 2048,  # hidden_size
    "num_heads": 16,  # num_attention_heads = num_key_value_heads (MHA)
    "num_layers": 16,  # num_hidden_layers
    "num_experts": 64,
    "experts_per_token": 8,  # num_experts_per_tok
    "expert_width": 1024,  # intermediate_size
    "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0,
}


def _cos_sin(positions, inv_freq, factor):
    """cos and sin [S, D/2] of RoPE's angles; `factor` scales both
    (YaRN's attention factor, models/mellum2.py)."""
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return cos, sin


def rope_rotate(x, positions, inv_freq, factor=1.0):
    """Rotate-half RoPE at the given frequencies. x [B, S, H, D];
    positions [S] (may be negative: only differences between a query's
    and a key's reach the scores); inv_freq [D/2].

    x * [cos, cos] + [-x2, x1] * [sin, sin], a half at a time. Written
    so, the chip's compiler keeps the halves apart and lets the scores
    contract each (no rotated copy of x, no joined result): the fastest
    of four forms for an unroll's queries and keys, and for Mellum2's
    `[cache; k]` (172.4 ms an update for 176.7; PERF.md, PR 35)."""
    half = x.shape[-1] // 2
    cos, sin = (
        t[None, :, None, :] for t in _cos_sin(positions, inv_freq, factor)
    )
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def rope_rotate_state(x, positions, inv_freq, factor=1.0):
    """`rope_rotate` for a cache as the state holds it, x [S, B, H, D]:
    the same products and sums as ONE expression over the halves as a
    pair axis, [S, B, H, 2, D/2]: x * cos + (the pair swapped) *
    [-sin, sin]. The compiler makes of it one pass that reads the cache
    and writes the rotated keys whole, which the three matmuls a block
    application makes on them then read at full width; from the form
    above it makes two padded halves of a cache (811 ms an update of
    the Ouro cell for 841; PERF.md, PR 35). For the unroll's own few
    keys it is the other way round."""
    half = x.shape[-1] // 2
    cos, sin = (
        t[:, None, None, None, :]
        for t in _cos_sin(positions, inv_freq, factor)
    )
    pairs = x.reshape(x.shape[:-1] + (2, half))
    signed_sin = jnp.concatenate([-sin, sin], axis=-2)
    return (pairs * cos + jnp.flip(pairs, axis=-2) * signed_sin).reshape(
        x.shape
    )


def _inv_freq(theta, dim):
    """theta^(-2i/D), i < D/2."""
    half = dim // 2
    return theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


def rope(x, positions, theta):
    """Rotate-half RoPE of x [B, S, H, D] at theta^(-2i/D)."""
    return rope_rotate(x, positions, _inv_freq(theta, x.shape[-1]))


def rope_state(x, positions, theta):
    """`rope` for a cache as the state holds it, x [S, B, H, D]."""
    return rope_rotate_state(x, positions, _inv_freq(theta, x.shape[-1]))


def rope_cached_attend(q, k, v, cache_state, cache_mask, seq_mask, theta,
                       dtype):
    """A RoPE block's attention (this family's and models/ouro.py's):
    un-rotated q, k, v [B, T, H, hd] of the unroll and the cache (k, v)
    [M, B, H, hd] as the state holds it, un-rotated too, through
    ops/attention.cached_transformer_attend. A key's position is its
    time relative to the unroll's first step: step j is j, the cache's
    slots come with theirs (slot m of M: m - M)."""
    steps = jnp.arange(q.shape[1])
    cache_k, cache_v = cache_state
    return cached_transformer_attend(
        rope(q, steps, theta).astype(dtype),
        rope(k, steps, theta).astype(dtype),
        v.astype(dtype),
        cache_k.astype(dtype), cache_v.astype(dtype), cache_mask, seq_mask,
        place_cache_keys=lambda keys, times: rope_state(keys, times, theta),
    )


class _OLMoEBlock(nn.Module):
    d_model: int
    num_heads: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    rms_norm_eps: float
    rope_theta: float
    aux_loss_weight: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache_state, cache_mask, seq_mask, **_):
        """TransformerNet's block contract: x [B, T, d]; cache_state
        (k, v) [M, B, H, hd], read where the state holds them;
        cache_mask [B, T, M], seq_mask [B, T, T]. Returns (y, k, v) with
        this unroll's un-rotated k and v [B, T, H, hd]."""
        B, T, _ = x.shape
        H = self.num_heads
        hd = self.d_model // H

        def norm(name):
            return nn.RMSNorm(epsilon=self.rms_norm_eps, name=name)

        def proj(name):
            return nn.Dense(
                self.d_model, use_bias=False, dtype=self.dtype, name=name
            )

        with device_scope("attention"):
            h = norm("attn_norm")(x)
            # q/k norm over the whole projected width before the split
            # into heads: OLMoE's block has it, its config.json does not
            # say so.
            q = norm("q_norm")(proj("q")(h)).reshape(B, T, H, hd)
            k = norm("k_norm")(proj("k")(h)).reshape(B, T, H, hd)
            v = proj("v")(h).reshape(B, T, H, hd)
            attended = rope_cached_attend(
                q, k, v, cache_state, cache_mask, seq_mask,
                self.rope_theta, self.dtype,
            )
            count_two_leg_application(self)
            x = x + proj("o")(
                attended.reshape(B, T, self.d_model)
            ).astype(jnp.float32)

        y = DroplessMoE(
            d_ff=self.expert_width,
            num_experts=self.num_experts,
            top_k=self.experts_per_token,
            aux_loss_weight=self.aux_loss_weight,
            dtype=self.dtype,
            name="moe",
        )(norm("moe_norm")(x).reshape(B * T, self.d_model))
        x = x + y.reshape(B, T, self.d_model)
        return x, k.astype(jnp.float32), v.astype(jnp.float32)


class OLMoENet(TransformerNet):
    # Fields the published table sets, or that the block does not read:
    # no flag reaches them (models/__init__.py `takes_flag`).
    flag_refused_fields = ("num_experts",)
    # `make_block` below does not read `remat`: `--remat` has no lever.
    remat_lever = None

    num_layers: int = PUBLISHED["num_layers"]
    d_model: int = PUBLISHED["d_model"]
    num_heads: int = PUBLISHED["num_heads"]
    # Not the model's 4,096 positions of full causal attention: a policy
    # attends over a window of its own past, carried as the rolling cache.
    memory_len: int = 128
    num_experts: int = PUBLISHED["num_experts"]
    experts_per_token: int = PUBLISHED["experts_per_token"]
    expert_width: int = PUBLISHED["expert_width"]
    rms_norm_eps: float = PUBLISHED["rms_norm_eps"]
    rope_theta: float = PUBLISHED["rope_theta"]
    # The frame scaled to [-1, 1], not [0, 1]: RMSNorm keeps an offset
    # that all tokens share, and the frames' mean of 0.5 put one on
    # every token larger than what tells them apart, so every token's
    # router saw much the same input (one expert took every token at
    # seeded weights; a token embedding has no such offset). On the
    # chip a uint8 frame is multiplied as the integers 2u - 255 and the
    # scale 1/255 lands on the projection's result (models/
    # transformer.py `frame_projection`); a symmetric range needs no
    # shift there.
    frame_range: Tuple[float, float] = (-1.0, 1.0)
    # OLMoE's paper trains with a load-balance weight of 0.01 (and a
    # router z-loss, left out here); neither is in config.json.
    aux_loss_weight: float = 0.01

    @nn.nowrap
    def make_block(self, name: str, layer: int):
        del layer  # every layer is the same block
        return _OLMoEBlock(
            d_model=self.d_model, num_heads=self.num_heads,
            num_experts=self.num_experts,
            experts_per_token=self.experts_per_token,
            expert_width=self.expert_width,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            aux_loss_weight=self.aux_loss_weight,
            dtype=self.dtype, name=name,
        )

    @nn.nowrap
    def make_final_norm(self):
        return nn.RMSNorm(epsilon=self.rms_norm_eps, name="final_norm")
