"""A slice of Phi-4-mini-flash-reasoning around its stage boundary as
the policy trunk (`--model phi4flash`).

The family is `TransformerNet`'s scaffolding — observation and extras
projections, band / segment / cache-validity masks, `roll_kv_cache`,
the batch-on-axis-1 state convention, `RecurrentPolicyHead` — with the
layers of Phi-4-mini-flash-reasoning (Microsoft; config.json, `model_
type` phi4flash; the SambaY decoder-hybrid-decoder of arXiv:2507.06607,
its attention after arXiv:2410.05258) at their published widths. A
layer is `h = x + mixer(LN1(x)); y = h + W_down(silu(W_gate u) * (W_up
u)), u = LN2(h)`, LayerNorm with scale and bias (eps 1e-5), the SwiGLU
of 10240 without a bias. The mixer is what the layer's PUBLISHED index
`i` of 32 says (`kind_of`), with `L/2` = 16 the stage boundary:

  mamba   `i` even, `i <= 16`. Mamba-1: [a, z] = W_in u (5120 each); a'
     = silu(conv4(a) + b), causal and depthwise; [d, B_t, C_t] = W_x a'
     (160, 16, 16); dt = softplus(W_dt d + b_dt); A = -exp(A_log), one
     decay a channel AND a state column; s_t = exp(dt_t A) s_{t-1} +
     (dt_t a'_t) B_t^T; m_t = s_t C_t + D a'_t; out W_out (m silu(z)).
     `selective_scan` below; `conv_over_episodes` of models/nemotron3.py
     (K = 4). The layer CARRIES s and the convolution's three-step tail
     (a `Recurrent` entry). LAYER 16 ALSO HANDS ON `m`, the scan's
     output before the gate, for this unroll's steps.
  sliding  `i` odd, `i < 16`. Differential attention over a window of
     512 keys (511 cached slots and the query's own step).
  full    `i` = 17. The same over its whole cache (`--memory_len`), AND
     HANDS ON its keys and values as it attended over them: its cache
     before rolling, this unroll's keys and values, the two masks.
  memory  `i` even, `i >= 18`. A gated memory unit: W_out (m silu(W_in
     u)), m layer 16's for the same steps. No state.
  cross   `i` odd, `i >= 19`. Differential attention whose queries are
     its own (W_q u + b) and whose keys, values and masks are layer
     17's, cache leg and unroll leg. No cache entry, no key or value
     weights.

Differential attention: W_qkv u + b -> 40 query heads of 64, 20 key
heads and 20 value heads of 64; as 20 query pairs (q1, q2) = heads (2j,
2j + 1), 10 key pairs (k1, k2), 10 values v = [v1; v2] of 128, two
query pairs to a key pair; o = softmax(q1 k1^T / 8) v - lambda
softmax(q2 k2^T / 8) v; lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
lambda_init(i), lambda_init(i) = 0.8 - 0.6 exp(-0.3 i) with the
PUBLISHED i; RMSNorm over o's 128 (a learned scale) times (1 - lambda_
init); the 20 x 128 through out_proj + b. No positional embedding: a
score depends on the band and the masks alone. It runs as ONE grouped
attention of 40 query heads on 10 key/value heads of 128: a key pair
side by side IS a 128-wide key [k1; k2], a query reads its half of it
([q1; 0] or [0; q2]: the other half's products are zeros), and the
value is the pair's own 128. So `dense_transformer_attend` computes it,
at the learner's sizes by its fused pass (ops/fused_attention.py, heads
of 128 as models/mellum2.py's), and the state's [M, B, 20, 64] leaves
are read as [M, B, 10, 128] where they lie.

What later layers read is handed on by the walk (models/transformer.py
`layer_shares`): the values are inputs and outputs of the blocks, so a
rematerialised block (`--remat all`) recomputes from them and their
gradients sum over their readers; the T=1 act step hands them on as the
unroll does.

`--num_layers n` (even, 6 or more) builds the n published layers around
the boundary: the pair (16, 17) that hands on, ceil((n/2 - 1) / 2)
pairs (mamba, sliding) before it and the rest, pairs (memory, cross),
after it; 32 is the published model, 6 is layers 14-19.

The widths are constants of the family (`PUBLISHED`), not flags; a user
cuts depth (`--num_layers`) and chooses the full layer's cache
(`--memory_len`).
"""

import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.nemotron3 import (
    chunk_plan,
    conv_over_episodes,
    dt_bias_init,
    uniform_between,
)
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
from torchbeast_tpu.ops.selective_scan import (
    STEP_BLOCK,
    kernels_apply,
    selective_scan_kernels,
)
from torchbeast_tpu.telemetry import device_scope

MAMBA, SLIDING, FULL, MEMORY, CROSS = (
    "mamba", "sliding", "full", "memory", "cross"
)
# The names layer 16 and layer 17 hand their values on under.
SHARED_MEMORY, SHARED_KV = "memory", "keys_values"

# https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/
# config.json, in two tables that together hold it key for key: the keys
# the family reads, under the class's field names (`create_model(
# "phi4flash")` reads this table when it is called, so a test shrinks
# the family here), and below it the keys nothing here reads.
PUBLISHED = {
    "d_model": 2560,  # hidden_size
    "num_layers": 32,  # num_hidden_layers
    "num_heads": 40,  # num_attention_heads
    "num_key_value_heads": 20,
    "intermediate_size": 10240,
    "layer_norm_eps": 1e-5,
    "mb_per_layer": 2,  # a Mamba layer every second layer
    "sliding_window": 512,
    "mlp_bias": False,
    # What config.json has no key for (perfbench/configs/phi4flash_3b8_
    # policy.json `assumed` names each one's source): Mamba-1's own
    # defaults, and the published hidden_size / 16.
    "d_state": 16,
    "d_conv": 4,
    "expand": 2,
    "dt_rank": 160,
    "time_step": (0.001, 0.1, 0.0001),  # dt_min, dt_max, dt_init_floor
    "lambda_std": 0.1,
}
# No field, no flag: silu is what the blocks build, the cache rolls, and
# there is no vocabulary or dropout here.
PUBLISHED_UNREAD = {
    "hidden_act": "silu",
    "max_position_embeddings": 262144,
    "embd_pdrop": 0,
    "resid_pdrop": 0,
    "model_type": "phi4flash",
    "tie_word_embeddings": True,
    "lm_head_bias": False,
    "vocab_size": 200064,
}

# Steps of a chunk of the `lax.scan` regime of the selective scan: the
# states at the chunks' boundaries are what its backward pass keeps
# (T / 16 of [B, N, D]), and a chunk's inside is made again there.
SCAN_CHUNK = 16


def lambda_init(published_index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


def selective_scan(a, dt, A, B_in, C_in, state, done):
    """Mamba-1's recurrence over an unroll, episode ends inside it.

    a, dt [B, T, D] (the convolved input and its step); A [N, D]
    (negative); B_in, C_in [B, T, N]; state [B, N, D], the state before
    the unroll; done [B, T]. Returns (y [B, T, D] with y_t = s_t C_t,
    the state after the last step, and the pieces the steps were walked
    in), float32:

        s_t = exp(dt_t A) keep_t s_{t-1} + (dt_t a_t) B_t^T

    keep_t = 0 where `done` is set at t, a multiplier on the decay.
    The decay differs by channel and by state column, so no chunk of it
    is a matmul (models/nemotron3.py `ssd_scan` has one scalar a head):
    the steps run one after another. One function, two regimes, chosen
    by the shapes (`ops/selective_scan.kernels_apply`, no flag):

    - an unroll of more than one step over whole blocks of 512 channels
      (the learner at the published widths, whatever its unroll): two
      Mosaic kernels with the state in VMEM, forward and backward, the
      steps in blocks of 128 (ops/selective_scan.py);
    - else (acting at T = 1, toy widths): a `lax.scan` over chunks of
      `SCAN_CHUNK` steps around a `lax.scan` over a chunk's steps, the
      state [B, N, D] with the channels on the lanes. A chunk is
      rematerialised: differentiated, the outer scan keeps the states
      at the chunks' boundaries and a chunk's [chunk, B, N, D] exists
      while its own backward pass runs. T = 1 is one chunk of one step,
      the recurrence itself.

    Neither makes the unroll's [T, B, N, D]."""
    rows, steps, _ = a.shape
    if kernels_apply(steps, a.shape[2], A.shape[0]):
        y, last = selective_scan_kernels(a, dt, A, B_in, C_in, state, done)
        return y, last, -(-steps // STEP_BLOCK)
    Q, pad, chunks = chunk_plan(steps, SCAN_CHUNK)

    def in_time(x):
        """[B, T, ...] -> [chunks, Q, B, ...] float32; a padded step has
        dt = 0 and keeps: it passes the state on as it is."""
        x = jnp.pad(
            x.astype(jnp.float32),
            ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
        )
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((chunks, Q) + x.shape[1:])

    def step(s, inputs):
        a_t, dt_t, B_t, C_t, done_t = inputs
        decay = jnp.exp(dt_t[:, None, :] * A) * (1.0 - done_t)[:, None, None]
        s = decay * s + (dt_t * a_t)[:, None, :] * B_t[:, :, None]
        return s, jnp.sum(s * C_t[:, :, None], axis=1)

    @jax.checkpoint
    def one_chunk(s, inputs):
        return jax.lax.scan(step, s, inputs)

    last, y = jax.lax.scan(
        one_chunk, state.astype(jnp.float32),
        tuple(in_time(x) for x in (a, dt, B_in, C_in, done)),
    )
    y = y.reshape((chunks * Q, rows) + y.shape[3:])[:steps]
    return jnp.moveaxis(y, 0, 1), last, chunks


def _layer_norm(name, eps):
    return nn.LayerNorm(epsilon=eps, name=name)


def _proj(name, width, dtype, use_bias=False, **init):
    return nn.Dense(width, use_bias=use_bias, dtype=dtype, name=name, **init)


class _Layer(nn.Module):
    """What every kind of layer states of itself: the widths of its
    SwiGLU (`_mlp`) and of its norms."""

    d_model: int
    intermediate_size: int
    layer_norm_eps: float
    dtype: Any

    def _mlp(self, x):
        """`x + W_down(silu(W_gate u) * (W_up u))`, u = LN2(x): the
        second half of every layer."""
        with device_scope("mlp"):
            u = _layer_norm("mlp_norm", self.layer_norm_eps)(x)
            hidden = nn.silu(
                _proj("gate_proj", self.intermediate_size, self.dtype)(u)
            ) * _proj("up_proj", self.intermediate_size, self.dtype)(u)
            return x + _proj("down_proj", self.d_model, self.dtype)(
                hidden
            ).astype(jnp.float32)

    def _mixer_input(self, x):
        return _layer_norm("mixer_norm", self.layer_norm_eps)(x)


class _MambaBlock(_Layer):
    d_state: int
    d_conv: int
    expand: int
    dt_rank: int
    time_step: Tuple[float, float, float]
    hands_on: bool  # layer 16: the scan's output goes to later layers

    @nn.compact
    def __call__(self, x, state, done):
        """x [B, T, d]; state (s [N, B, D], the convolution's last
        d_conv - 1 inputs [K - 1, B, D]) as the state holds them; done
        [B, T]. Returns (y, (s, tail)) to start the next unroll from,
        and after them the memory m [B, T, D] where the layer hands it
        on."""
        N, K, R = self.d_state, self.d_conv, self.dt_rank
        D = self.expand * self.d_model
        carried, tail = state
        with device_scope("mamba1_in_proj"):
            joined = _proj("in_proj", 2 * D, self.dtype)(self._mixer_input(x))
            a, z = joined[..., :D], joined[..., D:].astype(jnp.float32)
        with device_scope("mamba1_conv"):
            bound = 1.0 / math.sqrt(K)
            a, new_tail = conv_over_episodes(
                a, tail, done,
                self.param(
                    "conv_kernel", uniform_between(-bound, bound), (K, D)
                ),
                self.param("conv_bias", uniform_between(-bound, bound), (D,)),
            )
            a = nn.silu(a)
        with device_scope("mamba1_x_proj"):
            joined = _proj("x_proj", R + 2 * N, self.dtype)(
                a.astype(self.dtype)
            ).astype(jnp.float32)
            bound = R ** -0.5
            dt = nn.softplus(_proj(
                "dt_proj", D, self.dtype, use_bias=True,
                kernel_init=uniform_between(-bound, bound),
                bias_init=dt_bias_init(*self.time_step),
            )(joined[..., :R]).astype(jnp.float32))
        with device_scope("selective_scan"):
            # Mamba-1's init: column n of every channel decays at n + 1.
            A = -jnp.exp(self.param(
                "A_log",
                lambda key, shape: jnp.log(jnp.broadcast_to(
                    jnp.arange(1.0, N + 1)[:, None], shape
                )),
                (N, D),
            ))
            y, new_carried, pieces = selective_scan(
                a, dt, A, joined[..., R : R + N], joined[..., R + N :],
                carried.transpose(1, 0, 2), done,
            )
            memory = y + self.param("D", nn.initializers.ones, (D,)) * a
        with device_scope("mamba1_out_proj"):
            x = x + _proj("out_proj", self.d_model, self.dtype)(
                (memory * nn.silu(z)).astype(self.dtype)
            ).astype(jnp.float32)
        if not self.is_initializing():
            # As models/nemotron3.py sows them: how many such layers and
            # the bytes of state a row carries through them; the pieces
            # the unroll's scan walked its steps in (the kernels' step
            # blocks, or the `lax.scan`'s chunks) and the episode ends a
            # row had, which every layer says alike.
            steps = x.shape[1]
            for name, value, fold in (
                ("ssm_applications", 1.0, "sum"),
                # Those whose convolution is ops/short_conv.py's kernels.
                ("conv_kernel_applications",
                 float(short_conv.kernels_apply(steps, D, K)), "sum"),
                ("ssm_state_bytes_per_row", 4 * (N * D + (K - 1) * D), "sum"),
                ("ssm_chunks", pieces, "same"),
                ("ssm_resets_per_row",
                 jnp.mean(jnp.sum(done.astype(jnp.float32), axis=1)),
                 "same"),
            ):
                sow_stat(self, name, value, fold)
            if self.hands_on:
                # What a row's later layers are handed of this unroll.
                sow_stat(self, "shared_bytes_per_row", 4 * steps * D, "sum")
        new_state = (new_carried.transpose(1, 0, 2), new_tail)
        if self.hands_on:
            return self._mlp(x), new_state, memory
        return self._mlp(x), new_state


class _DifferentialAttention(_Layer):
    """The two kinds of layer that attend (`_AttentionBlock`, `_Cross
    Block`) differ in where keys, values and masks come from; the
    queries, the difference and the output are this."""

    num_heads: int
    num_key_value_heads: int
    lambda_init: float
    lambda_std: float
    span: str  # attention_sliding | attention_full | attention_cross
    memory_len: int  # slots of the cache attended over

    def _attend(self, q, cache_state, k, v, cache_mask, seq_mask):
        """q [B, T, H, hd] (a pair's two queries are heads 2j, 2j + 1);
        cache_state (k, v) [M, B, Hkv, hd] as the state holds them; k, v
        [B, T, Hkv, hd], the unroll's; cache_mask [B, T, M], seq_mask
        [B, T, T]. Returns the mixer's output [B, T, d]."""
        rows, steps, H, hd = q.shape
        pairs, wide = H // 2, 2 * hd
        kv_pairs = self.num_key_value_heads // 2
        cache_len = self.memory_len

        def side_by_side(cached, new):
            """[B, M + T, Hkv / 2, 2 hd]: heads (2g, 2g + 1) are one
            wide head where they lie."""
            cached = cached.reshape(
                (cache_len, rows, kv_pairs, wide)
            ).transpose(1, 0, 2, 3)
            new = new.reshape(rows, steps, kv_pairs, wide)
            return jnp.concatenate([cached.astype(new.dtype), new], axis=1)

        # A query reads its own half of the wide key: [q1; 0], [0; q2].
        # sqrt(2) because the body's scale is (2 hd)^-0.5 and the
        # scores' is hd^-0.5.
        q = (q * math.sqrt(2.0)).reshape(rows, steps, pairs, 2, hd)
        nothing = jnp.zeros_like(q[:, :, :, 0])
        q = jnp.stack([
            jnp.concatenate([q[:, :, :, 0], nothing], axis=-1),
            jnp.concatenate([nothing, q[:, :, :, 1]], axis=-1),
        ], axis=3).reshape(rows, steps, H, wide)
        k_all = side_by_side(cache_state[0], k)
        attended = dense_transformer_attend(
            q.astype(self.dtype), k_all.astype(self.dtype),
            side_by_side(cache_state[1], v).astype(self.dtype),
            jnp.concatenate([cache_mask, seq_mask], axis=-1), None, None,
            cache_len,
        )  # the cache is the learner's data: no gradient (models/mellum2.py)
        if fused_pass_applies(q.shape, k_all.shape, None):
            count_fused_application(self)
        with device_scope("attention_difference"):
            def vector(name):
                return self.param(
                    name, nn.initializers.normal(self.lambda_std), (hd,)
                )

            lam = (
                jnp.exp(jnp.sum(vector("lambda_q1") * vector("lambda_k1")))
                - jnp.exp(jnp.sum(vector("lambda_q2") * vector("lambda_k2")))
                + self.lambda_init
            )
            attended = attended.astype(jnp.float32).reshape(
                rows, steps, pairs, 2, wide
            )
            o = attended[:, :, :, 0] - lam * attended[:, :, :, 1]
            o = nn.RMSNorm(epsilon=self.layer_norm_eps, name="subln")(o) * (
                1.0 - self.lambda_init
            )
        sow_stat(self, "attention_differential_applications", 1.0, "sum")
        return _proj("out_proj", self.d_model, self.dtype, use_bias=True)(
            o.reshape(rows, steps, pairs * wide).astype(self.dtype)
        ).astype(jnp.float32)


class _AttentionBlock(_DifferentialAttention):
    hands_on: bool  # layer 17: the walk hands its keys and values on

    @nn.compact
    def __call__(self, x, cache_state, cache_mask, seq_mask, **_):
        """TransformerNet's block contract for a window entry: x
        [B, T, d]; cache_state (k, v) [M, B, Hkv, hd] as the state holds
        them; cache_mask [B, T, M], seq_mask [B, T, T]. Returns (y, k,
        v) with this unroll's k and v [B, T, Hkv, hd]."""
        rows, steps, _ = x.shape
        H, Hkv = self.num_heads, self.num_key_value_heads
        hd = self.d_model // H
        with device_scope(self.span):
            joined = _proj(
                "Wqkv", (H + 2 * Hkv) * hd, self.dtype, use_bias=True
            )(self._mixer_input(x)).astype(jnp.float32)
            q, k, v = (
                part.reshape(rows, steps, -1, hd) for part in jnp.split(
                    joined, [H * hd, (H + Hkv) * hd], axis=-1
                )
            )
            x = x + self._attend(q, cache_state, k, v, cache_mask, seq_mask)
        if self.hands_on and not self.is_initializing():
            sow_stat(
                self, "shared_bytes_per_row",
                4 * 2 * (self.memory_len + steps) * Hkv * hd, "sum",
            )
        return self._mlp(x), k, v


class _CrossBlock(_DifferentialAttention):
    @nn.compact
    def __call__(self, x, keys_values):
        """x [B, T, d]; keys_values: what the full layer attended over,
        as the walk hands it on: ((k, v) [M, B, Hkv, hd], the cache
        BEFORE this unroll; k, v [B, T, Hkv, hd], this unroll's;
        cache_mask [B, T, M], seq_mask [B, T, T])."""
        rows, steps, _ = x.shape
        hd = self.d_model // self.num_heads
        with device_scope(self.span):
            q = _proj("Wq", self.num_heads * hd, self.dtype, use_bias=True)(
                self._mixer_input(x)
            ).astype(jnp.float32).reshape(rows, steps, self.num_heads, hd)
            x = x + self._attend(q, *keys_values)
        if not self.is_initializing():
            sow_stat(self, "shared_kv_readers", 1.0, "sum")
        return self._mlp(x)


class _MemoryBlock(_Layer):
    expand: int

    @nn.compact
    def __call__(self, x, memory):
        """x [B, T, d]; memory [B, T, D]: layer 16's scan output for the
        same steps."""
        D = self.expand * self.d_model
        with device_scope("memory_unit"):
            gate = nn.silu(
                _proj("in_proj", D, self.dtype)(self._mixer_input(x))
            ).astype(jnp.float32)
            x = x + _proj("out_proj", self.d_model, self.dtype)(
                (memory * gate).astype(self.dtype)
            ).astype(jnp.float32)
        if not self.is_initializing():
            sow_stat(self, "shared_memory_readers", 1.0, "sum")
        return self._mlp(x)


class Phi4FlashNet(TransformerNet):
    # Fields the published table sets, or that the blocks do not read:
    # no flag reaches them (models/__init__.py `takes_flag`).
    flag_refused_fields = ("num_experts",)

    num_layers: int = PUBLISHED["num_layers"]
    d_model: int = PUBLISHED["d_model"]
    num_heads: int = PUBLISHED["num_heads"]
    num_key_value_heads: int = PUBLISHED["num_key_value_heads"]
    intermediate_size: int = PUBLISHED["intermediate_size"]
    layer_norm_eps: float = PUBLISHED["layer_norm_eps"]
    mb_per_layer: int = PUBLISHED["mb_per_layer"]
    sliding_window: int = PUBLISHED["sliding_window"]
    mlp_bias: bool = PUBLISHED["mlp_bias"]
    # The published depth, which says where the stage boundary lies
    # whatever `num_layers` a cut runs.
    published_layers: int = PUBLISHED["num_layers"]
    d_state: int = PUBLISHED["d_state"]
    d_conv: int = PUBLISHED["d_conv"]
    expand: int = PUBLISHED["expand"]
    dt_rank: int = PUBLISHED["dt_rank"]
    time_step: Tuple[float, float, float] = PUBLISHED["time_step"]
    lambda_std: float = PUBLISHED["lambda_std"]
    # Not the model's 262,144 positions: the full layer's rolling cache
    # of the policy's own past. A sliding layer carries `sliding_window`
    # - 1 slots (fewer where this is fewer), a Mamba layer a state.
    memory_len: int = 4095
    # Frames to [-1, 1], for the reason models/olmoe.py gives.
    frame_range: Tuple[float, float] = (-1.0, 1.0)
    # As the other published families (models/mellum2.py says why there;
    # here it keeps the cells alike).
    zero_init_extras: bool = True
    # Every matmul of the family in three bf16 passes on the MXU (JAX
    # precision `high`), the attention layers' fused pass among them
    # (two bf16 terms cut in VMEM), as models/lfm2.py and its kin: what
    # the configuration states is float32. One pass a product reads
    # within the benchmark's check of the loss as well (PERF.md section
    # 6, PR 55), which is why that check does not choose: the states an
    # unroll leaves differ by two orders between the two (section 7).
    # The scan itself is elementwise float32 either way.
    matmul_precision: str = "high"

    def __call__(self, inputs, core_state, **kwargs):
        # Read when a dot is traced, and kept by its gradient's.
        with jax.default_matmul_precision(self.matmul_precision):
            return super().__call__(inputs, core_state, **kwargs)

    def __post_init__(self):
        if self.mlp_bias or self.mb_per_layer != 2:
            raise ValueError(
                "mlp_bias, mb_per_layer: the published SwiGLU without a "
                "bias and a Mamba layer every second layer are what is "
                "built"
            )
        if self.num_heads % 4 or self.num_key_value_heads * 2 != self.num_heads:
            raise ValueError(
                "num_heads, num_key_value_heads: query heads pair, and "
                "two query pairs read a key pair"
            )
        self.published_indices()  # refuses a depth that is no cut
        super().__post_init__()

    @nn.nowrap
    def boundary(self) -> int:
        """The published index of the layer that hands on its memory;
        the layer after it hands on its keys and values."""
        return self.published_layers // 2

    @nn.nowrap
    def published_indices(self) -> Tuple[int, ...]:
        """The published index of each layer run: the pair at the stage
        boundary that hands its values on (`boundary()` and the layer
        after it), ceil of half the other pairs before it and
        the rest after it. All `published_layers` are the model."""
        pairs, odd = divmod(self.num_layers, 2)
        boundary = self.boundary()
        before = pairs // 2  # ceil((pairs - 1) / 2)
        first = boundary - 2 * before
        if odd or pairs < 3 or first < 0 or (
            first + self.num_layers > self.published_layers
        ):
            raise ValueError(
                f"--num_layers {self.num_layers}: --model phi4flash is cut "
                f"as whole pairs of layers around its stage boundary "
                f"(published layers {boundary} and {boundary + 1}, which "
                f"hand their values on), at least one pair before and one "
                f"after: an even number from 6 to {self.published_layers}"
            )
        return tuple(range(first, first + self.num_layers))

    @nn.nowrap
    def kind_of(self, published_index: int) -> str:
        boundary = self.boundary()
        if published_index % 2 == 0:
            return MAMBA if published_index <= boundary else MEMORY
        if published_index < boundary:
            return SLIDING
        return FULL if published_index == boundary + 1 else CROSS

    @nn.nowrap
    def kinds(self) -> Tuple[str, ...]:
        return tuple(self.kind_of(i) for i in self.published_indices())

    @nn.nowrap
    def _window(self, kind: str) -> int:
        if kind == SLIDING:
            return min(self.memory_len, self.sliding_window - 1)
        return self.memory_len

    @nn.nowrap
    def layer_caches(self):
        """An entry a published layer: a window of keys and values for a
        layer that attends over its own, a Mamba layer's state [N, B, D]
        (the channels on the lanes) and tail [K - 1, B, D], nothing for
        the layers that read another's."""
        D = self.expand * self.d_model
        carried = Recurrent(((self.d_state, D), (self.d_conv - 1, D)))
        head = (self.num_key_value_heads, self.d_model // self.num_heads)
        return tuple(
            carried if kind == MAMBA
            else (self._window(kind),) + head if kind in (SLIDING, FULL)
            else None
            for kind in self.kinds()
        )

    @nn.nowrap
    def layer_shares(self):
        boundary = self.boundary()
        gives = {boundary: (SHARED_MEMORY,), boundary + 1: (SHARED_KV,)}
        takes = {MEMORY: (SHARED_MEMORY,), CROSS: (SHARED_KV,)}
        return tuple(
            (gives.get(i, ()), takes.get(self.kind_of(i), ()))
            for i in self.published_indices()
        )

    @nn.nowrap
    def make_block(self, name: str, layer: int):
        index = self.published_indices()[layer]
        kind = self.kind_of(index)
        shared = dict(
            d_model=self.d_model, intermediate_size=self.intermediate_size,
            layer_norm_eps=self.layer_norm_eps, dtype=self.dtype, name=name,
        )
        if kind == MAMBA:
            cls, fields = _MambaBlock, dict(
                d_state=self.d_state, d_conv=self.d_conv, expand=self.expand,
                dt_rank=self.dt_rank, time_step=self.time_step,
                hands_on=index == self.boundary(),
            )
        elif kind == MEMORY:
            cls, fields = _MemoryBlock, dict(expand=self.expand)
        else:
            cls = _CrossBlock if kind == CROSS else _AttentionBlock
            fields = dict(
                num_heads=self.num_heads,
                num_key_value_heads=self.num_key_value_heads,
                lambda_init=lambda_init(index), lambda_std=self.lambda_std,
                span="attention_" + kind, memory_len=self._window(kind),
            )
            if kind != CROSS:
                fields["hands_on"] = kind == FULL
        return (rematerialised(cls) if self.remat else cls)(
            **fields, **shared
        )

    @nn.nowrap
    def make_final_norm(self):
        return _layer_norm("final_norm", self.layer_norm_eps)
