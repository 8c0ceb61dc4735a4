"""An Ouro-2.6B looped block as the policy trunk (`--model ouro`).

The family is `TransformerNet`'s scaffolding — observation and extras
projections, band / segment / cache-validity masks, `roll_kv_cache`,
the `[M, B, heads, D]` state convention, `RecurrentPolicyHead` — with
the block of Ouro-2.6B (ByteDance; config.json, `model_type` ouro;
arXiv:2510.25741) at its published widths, and its loop: the whole
stack of L layers is applied `passes` (the config's `total_ut_steps`,
4) times with the SAME weights.

    for pass u = 0 .. passes - 1:
      for layer l = 0 .. L - 1:
        h = n1_l(x)
        q, k, v = Wq_l h, Wk_l h, Wv_l h       16 heads of 128, no bias
        a = Wo_l attend(rope(q), rope(cache[u][l].k) | rope(k),
                        cache[u][l].v | v)      theta 1e6; the cache
                                                and the unroll are two
                                                legs of one softmax
        x = x + n2_l(a)                         RMSNorm on the branch's
        m = Wdown_l(silu(Wgate_l n3_l(x)) * Wup_l n3_l(x))    OUTPUT too
        x = x + n4_l(m)
      x = final_norm(x)                         after EVERY pass
      lambda_u = sigmoid(w_exit . x + b_exit)   exit gate
    heads(x after the last pass)

A pass's keys and values are functions of that pass's hidden state, so
no two passes share them: the model carries `passes x L` caches for L
blocks' weights, `layer_caches()` and the state list them pass-major
(entry u * L + l), and `block_passes()` tells the scaffolding that
block l serves entry u * L + l. As in models/olmoe.py a key's position
is its time relative to the unroll's first step and a cache holds
un-rotated keys, so the learner's batch forward equals the actor's T=1
forwards through the `passes x L` rolling caches (tests/test_ouro.py).

`early_exit_threshold` is 1.0: no pass is skipped. The exit gate is
computed and its distribution over the passes logged (`loop_expected_
exit_pass`, `loop_exit_p_last` of the update's stats: `end_pass`);
no gradient reaches it here (Ouro trains it with an expected loss over
the exits, which an IMPALA loss on the last pass's output does not
carry over).

The widths and `passes` are constants of the family (`PUBLISHED`), not
flags; a user cuts depth (`--num_layers`), chooses the window
(`--memory_len`) and rematerialises the blocks (`--remat all`).
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.olmoe import rope_cached_attend
from torchbeast_tpu.models.stats import sow_stat
from torchbeast_tpu.models.transformer import (
    TransformerNet,
    count_two_leg_application,
)
from torchbeast_tpu.telemetry import device_scope

# https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json by
# the name of the field that carries each. `create_model("ouro")` reads
# this table when it is called, so a test shrinks the family here.
PUBLISHED = {
    "d_model": 2048,  # hidden_size
    "num_heads": 16,  # num_attention_heads = num_key_value_heads (MHA)
    "head_dim": 128,
    "mlp_width": 5632,  # intermediate_size
    "num_layers": 48,  # num_hidden_layers
    "passes": 4,  # total_ut_steps
    "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0,
}


class _OuroBlock(nn.Module):
    d_model: int
    num_heads: int
    head_dim: int
    mlp_width: int
    rms_norm_eps: float
    rope_theta: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache_state, cache_mask, seq_mask, **_):
        """TransformerNet's block contract: x [B, T, d]; cache_state
        (k, v) [M, B, H, hd], the state's own buffers, read where they
        lie (what a rematerialised block keeps for its backward pass is
        then the state's buffer too: transposed copies, kept, were 3.98
        GiB at the cell's sizes); cache_mask [B, T, M], seq_mask
        [B, T, T]. Returns (y, k, v) with this unroll's un-rotated k and
        v [B, T, H, hd]. The norms carry the names of the model's
        public modeling file."""
        B, T, _ = x.shape
        H, hd = self.num_heads, self.head_dim

        def norm(name):
            return nn.RMSNorm(epsilon=self.rms_norm_eps, name=name)

        def proj(name, width):
            return nn.Dense(
                width, use_bias=False, dtype=self.dtype, name=name
            )

        with device_scope("attention"):
            h = norm("input_layernorm")(x)
            q = proj("q", H * hd)(h).reshape(B, T, H, hd)
            k = proj("k", H * hd)(h).reshape(B, T, H, hd)
            v = proj("v", H * hd)(h).reshape(B, T, H, hd)
            attended = rope_cached_attend(
                q, k, v, cache_state, cache_mask, seq_mask,
                self.rope_theta, self.dtype,
            )
            x = x + norm("input_layernorm_2")(
                proj("o", self.d_model)(
                    attended.reshape(B, T, H * hd)
                ).astype(jnp.float32)
            )
        with device_scope("mlp"):
            h = norm("post_attention_layernorm")(x)
            hidden = nn.silu(proj("gate", self.mlp_width)(h)) * proj(
                "up", self.mlp_width
            )(h)
            x = x + norm("post_attention_layernorm_2")(
                proj("down", self.d_model)(hidden).astype(jnp.float32)
            )
        return x, k.astype(jnp.float32), v.astype(jnp.float32)


class OuroNet(TransformerNet):
    # Fields the published table sets, or that the block does not read:
    # no flag reaches them (models/__init__.py `takes_flag`).
    flag_refused_fields = ("num_experts",)

    num_layers: int = PUBLISHED["num_layers"]
    d_model: int = PUBLISHED["d_model"]
    num_heads: int = PUBLISHED["num_heads"]
    head_dim: int = PUBLISHED["head_dim"]
    mlp_width: int = PUBLISHED["mlp_width"]
    passes: int = PUBLISHED["passes"]
    # Not the model's 65,536 positions of full causal attention: a
    # policy attends over a window of its own past, carried as the
    # rolling caches, one for every application of a block.
    memory_len: int = 255
    rms_norm_eps: float = PUBLISHED["rms_norm_eps"]
    rope_theta: float = PUBLISHED["rope_theta"]
    # Frames to [-1, 1], for the reason models/olmoe.py gives.
    frame_range: Tuple[float, float] = (-1.0, 1.0)
    # Every matmul of the family in three bf16 passes on the MXU, not
    # JAX's default one. One pass rounds each operand to 8 bits; through
    # the observation projection's 28,224-long sums and 32 block
    # applications that moved the loss by 2.5e-3 of its scale (sigma
    # over 22 seeded batches on the chip, 5.4e-3 the worst; PERF.md,
    # PR 34) from the float32 model's, 1.6e-5 with three. No part alone
    # carries it: the projection, the SwiGLUs and attention each leave
    # over 1e-3 when the other two are exact. The price is the step's:
    # 0.87 s an update for 0.42 at the cell's sizes.
    matmul_precision: str = "high"

    def __call__(self, inputs, core_state, **kwargs):
        # Read when a dot is traced, and kept by its gradient's.
        with jax.default_matmul_precision(self.matmul_precision):
            return super().__call__(inputs, core_state, **kwargs)

    @nn.nowrap
    def layer_caches(self):
        """Pass-major: entry u * num_layers + l is pass u's cache of
        layer l."""
        return (
            (self.memory_len, self.num_heads, self.head_dim),
        ) * (self.passes * self.num_layers)

    @nn.nowrap
    def block_passes(self):
        return (tuple(range(self.num_layers)),) * self.passes

    @nn.nowrap
    def make_block(self, name: str, layer: int):
        """Block `layer`, to be applied once a pass. Its weights are
        this module's parameter `name` (the block's own tree), handed
        to each application by value and tied to the hidden state
        between two of them: see `apply`."""
        del layer  # every layer is the same block
        block_cls = nn.remat(_OuroBlock) if self.remat else _OuroBlock
        block = block_cls(
            d_model=self.d_model, num_heads=self.num_heads,
            head_dim=self.head_dim, mlp_width=self.mlp_width,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            dtype=self.dtype, parent=None,
        )
        weights = None

        def apply(x, *args, **kwargs):
            nonlocal weights
            if weights is None:
                weights = self.param(
                    name,
                    lambda rng: block.init(rng, x, *args, **kwargs)["params"],
                )
            # The barrier's transpose is a barrier: in the backward pass
            # this application's weight gradient is added to the sum of
            # the later ones' before the pass before it is entered. Left
            # to XLA the adds fuse into the optimizer's read, and every
            # pass's weight gradients live until the last is there:
            # (passes - 1) x 1.53 GiB at the cell's sizes.
            weights, x = jax.lax.optimization_barrier((weights, x))
            # Counted here: the block is applied apart from this module
            # and what it sowed would be dropped.
            count_two_leg_application(self)
            return block.apply({"params": weights}, x, *args, **kwargs)

        return apply

    @nn.nowrap
    def make_final_norm(self):
        """What follows every pass: the one norm, then the exit gate on
        its output. The gate is a logged statistic (models/stats.py):
        its input is cut from the gradient. From the passes' gates
        lambda_u the distribution over exits is p_u = lambda_u
        prod_{j<u} (1 - lambda_j), the last pass taking the rest: the
        last pass sows the pass a token would leave after, counted from
        1 and averaged over the batch, and the mass left to the last
        pass."""
        norm = nn.RMSNorm(epsilon=self.rms_norm_eps, name="final_norm")
        gate = nn.Dense(1, name="exit_gate")
        caches = self.passes * self.num_layers
        # k, v and the validity column of every cache, float32.
        cache_bytes = caches * 4 * self.memory_len * (
            2 * self.num_heads * self.head_dim + 1
        )

        for name, value in (
            ("loop_passes", self.passes),
            ("loop_block_applications", caches),
            ("loop_cache_bytes_per_row", cache_bytes),
        ):
            sow_stat(self, name, value, "same")
        gates = []  # one a pass, in order

        def end_pass(x):
            with device_scope("pass_norm"):
                x = norm(x)
            gates.append(nn.sigmoid(gate(jax.lax.stop_gradient(x)))[..., 0])
            if len(gates) == self.passes and not self.is_initializing():
                # Not leaving at pass u, for every pass but the last;
                # their running product is still being in after it.
                stays = 1.0 - jnp.stack(gates)[:-1]
                sow_stat(
                    self, "loop_expected_exit_pass",
                    1.0 + jnp.mean(
                        jnp.sum(jnp.cumprod(stays, axis=0), axis=0)
                    ),
                    "same",
                )
                sow_stat(
                    self, "loop_exit_p_last",
                    jnp.mean(jnp.prod(stays, axis=0)), "same",
                )
            return x

        return end_pass
