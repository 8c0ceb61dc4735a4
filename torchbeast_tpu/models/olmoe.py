"""An OLMoE-1B-7B block as the policy trunk (`--model olmoe`).

The family is `TransformerNet`'s scaffolding — observation and extras
projections, band / segment / cache-validity masks, `roll_kv_cache`, the
`[M, B, H, D]` state convention, `RecurrentPolicyHead` — with the block
of OLMoE-1B-7B-0125-Instruct (config.json, `model_type` olmoe) at its
published widths. Per layer:

    h = rmsnorm(x)
    q = rmsnorm_d(Wq h), k = rmsnorm_d(Wk h), v = Wv h      (no bias)
    x = x + Wo attend(rope(q), rope(k), v)                  (16 heads)
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
from torchbeast_tpu.models.transformer import TransformerNet
from torchbeast_tpu.ops.attention import dense_transformer_attend

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


def rope_rotate(x, positions, inv_freq, factor=1.0):
    """Rotate-half RoPE at the given frequencies. x [B, S, H, D];
    positions [S] (may be negative: only differences between a query's
    and a key's reach the scores); inv_freq [D/2]. `factor` scales cos
    and sin (YaRN's attention factor, models/mellum2.py)."""
    half = x.shape[-1] // 2
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.tile(jnp.cos(angles), 2)[None, :, None, :]
    sin = jnp.tile(jnp.sin(angles), 2)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def rope(x, positions, theta):
    """Rotate-half RoPE at the frequencies theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    return rope_rotate(x, positions, inv_freq)


class _OLMoEBlock(nn.Module):
    d_model: int
    num_heads: int
    memory_len: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    rms_norm_eps: float
    rope_theta: float
    aux_loss_weight: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache, mask, offsets, **_):
        """TransformerNet's block contract: x [B, T, d]; cache (k, v)
        [B, M, H, hd]; mask [B, T, M+T]. Returns (y, k, v) with this
        unroll's un-rotated k and v [B, T, H, hd]."""
        B, T, _ = x.shape
        M, H = self.memory_len, self.num_heads
        hd = self.d_model // H

        def norm(name):
            return nn.RMSNorm(epsilon=self.rms_norm_eps, name=name)

        def proj(name):
            return nn.Dense(
                self.d_model, use_bias=False, dtype=self.dtype, name=name
            )

        with jax.named_scope("attention"):
            h = norm("attn_norm")(x)
            # q/k norm over the whole projected width before the split
            # into heads: OLMoE's block has it, its config.json does not
            # say so.
            q = norm("q_norm")(proj("q")(h)).reshape(B, T, H, hd)
            k = norm("k_norm")(proj("k")(h)).reshape(B, T, H, hd)
            v = proj("v")(h).reshape(B, T, H, hd)
            k_all = jnp.concatenate([cache[0].astype(k.dtype), k], axis=1)
            v_all = jnp.concatenate([cache[1].astype(v.dtype), v], axis=1)
            key_time = jnp.concatenate([jnp.arange(M) - M, jnp.arange(T)])
            attended = dense_transformer_attend(
                rope(q, jnp.arange(T), self.rope_theta).astype(self.dtype),
                rope(k_all, key_time, self.rope_theta).astype(self.dtype),
                v_all.astype(self.dtype), mask, offsets, None,
            )
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
    flag_refused_fields = ("num_experts", "attention_impl")
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
    # seeded weights; a token embedding has no such offset).
    frame_range: Tuple[float, float] = (-1.0, 1.0)
    # OLMoE's paper trains with a load-balance weight of 0.01 (and a
    # router z-loss, left out here); neither is in config.json.
    aux_loss_weight: float = 0.01

    @nn.nowrap
    def make_block(self, name: str, layer: int):
        del layer  # every layer is the same block
        return _OLMoEBlock(
            d_model=self.d_model, num_heads=self.num_heads,
            memory_len=self.memory_len,
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
