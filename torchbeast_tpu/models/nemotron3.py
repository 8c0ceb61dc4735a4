"""One period of NVIDIA-Nemotron-3-Super-120B-A12B as the policy trunk
(`--model nemotron3`).

The family is `TransformerNet`'s scaffolding — observation and extras
projections, band / segment / cache-validity masks, `roll_kv_cache`,
the batch-on-axis-1 state convention, `RecurrentPolicyHead` — with the
layers of NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (config.json,
`model_type` nemotron_h) at their published widths. A layer is ONE
mixer or ONE feed-forward part alone, `x = x + f(rmsnorm(x))` (eps
1e-5, no biases but the convolution's), by its letter in the pattern:

  M  Mamba-2 (state-space duality, arXiv:2405.21060). in_proj d ->
     [z | xBC | dt]; xBC = silu(causal depthwise conv4(xBC) + bias),
     split x [H, P], B, C [G, N] (head h reads group h // (H / G));
     dt = softplus(dt + dt_bias); A = -exp(A_log) a head;
         h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
         y_t = h_t C_t + D x_t
     y = rmsnorm_per_group(y * silu(z)) * w (the gate first, the norm
     inside each of the G groups); out_proj -> d. The layer CARRIES its
     state h [H, P, N] and the last conv_kernel - 1 inputs of its
     convolution (a `Recurrent` entry of `layer_caches`): no window,
     nothing rolled.
  *  attention: 32 query heads of 128 on 2 key/value heads, softmax(q
     k^T / sqrt(128)) v over [cache; unroll], no positional embedding
     (nemotron_h's attention applies none), o -> d. A window entry as
     every other family's; ops/attention.dense_transformer_attend, and
     at the learner's sizes its fused pass (`fused_pass_applies`).
  E  latent mixture of experts (models/moe.py DroplessMoE): s =
     sigmoid(W_r u) over 512; the 22 largest of s + b; g = 5 s / (sum
     of the chosen s + 1e-20); l = W_down u (d -> 1024); experts
     W2_e relu(W1_e l)^2 in the latent; out = W_up (sum g_e E_e(l)) +
     W2_s relu(W1_s u)^2 (the shared expert on the full width,
     unscaled). Carries nothing (a None entry).

and one RMSNorm after the last layer.

EPISODE ENDS INSIDE THE CHUNKED SCAN. The learner computes the
recurrence in chunks of 128 steps (`ssd_scan`): within a chunk as a
masked matrix of decays (`ssd_intra`), the chunk's own contribution to
the state it leaves (`ssd_states`), and the state passed from chunk to
chunk (`ssd_inter`). `done` at step t zeroes the state carried INTO t
and the convolution's taps that lie before t, for that row: in the
chunked form a source step j reaches step i only where no episode ended
in (j, i], which is a comparison of the two steps' counts of ends laid
over the decay exp(cs_i - cs_j) (cumulative sums of dt A in float32);
the state entering a chunk reaches step i only where no episode ended
in the chunk up to i; a chunk hands on its entering state only if no
episode ended in it. The backward pass is the transpose of those
products, so a gradient crosses no episode end either. An actor's T=1
step is the same function with a chunk of one step, which is the
recurrence itself: the learner's batch forward equals the actor's
steps through the carried state (tests/test_nemotron3.py).

A chip may hold a SHARE OF EACH MIXER'S HEADS (`--mixer_share i/n`):
H / n Mamba heads with their G / n groups of B and C (so that the
grouped norm stays within the chip) and their part of `in_proj`, the
convolution and `out_proj`; 32 / n query heads with the key/value head
they read. The partial `out_proj` / `o` result is what goes on; nothing
stands in for the other chips. And a share of each layer's routed
experts (`--expert_share i/n`, as models/kanana2.py).

`b` (`e_score_correction_bias`) moves as Kanana-2's: by `bias_update_
rate` x sign(mean load - load) after the optimizer's step (assumed:
config.json has no key for the rule). Multi-token prediction
(`num_nextn_predict_layers` 1) is not run: a policy trunk has neither
tokens nor an LM head.

The widths are constants of the family (`PUBLISHED`), not flags; a user
cuts depth (`--num_layers`, whole periods of `layer_period`, or all 88
in the published order), chooses the attention cache (`--memory_len`)
and the two shares.
"""

import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.moe import DroplessMoE, held_experts
from torchbeast_tpu.models.stats import sow_stat
from torchbeast_tpu.models.transformer import (
    Recurrent,
    TransformerNet,
    count_fused_application,
    rematerialised,
)
from torchbeast_tpu.ops import short_conv
from torchbeast_tpu.ops import ssd_scan as scan_kernels
from torchbeast_tpu.ops.attention import (
    dense_transformer_attend,
    fused_pass_applies,
)
from torchbeast_tpu.ops.bf16_terms import terms_traced_under
from torchbeast_tpu.telemetry import device_scope

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"

# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json
# by the name of the field that carries each. `create_model("nemotron3")`
# reads this table when it is called, so a test shrinks the family here.
PUBLISHED = {
    "d_model": 4096,  # hidden_size
    "num_layers": 88,  # num_hidden_layers
    "layer_pattern": (  # hybrid_override_pattern
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
    ),
    # The period a cut of depth is made of: the published layers 25-35,
    # the pattern's own ratio of 8 : 40 : 40.
    "layer_period": "*EMEMEMEMEM",
    "num_heads": 32,  # num_attention_heads
    "kv_heads": 2,  # num_key_value_heads
    "head_dim": 128,
    "mamba_heads": 128,  # mamba_num_heads
    "mamba_head_dim": 64,
    "mamba_groups": 8,  # n_groups
    "state_size": 128,  # ssm_state_size
    "conv_kernel": 4,
    "chunk_size": 128,
    "num_experts": 512,  # n_routed_experts
    "experts_per_token": 22,  # num_experts_per_tok
    "expert_width": 2688,  # moe_intermediate_size
    "latent_width": 1024,  # moe_latent_size
    "shared_width": 5376,  # n_shared_experts 1 x moe_shared_expert_...
    "renormalise": True,  # norm_topk_prob
    "routed_scaling": 5.0,  # routed_scaling_factor
    "rms_norm_eps": 1e-5,  # layer_norm_epsilon
    "time_step_min": 0.001,
    "time_step_max": 0.1,
    "time_step_floor": 0.0001,
}


def chunk_plan(steps, chunk):
    """(steps a chunk, padded steps, chunks) of an unroll cut into chunks
    of `chunk` steps: one chunk of the unroll's own length where it is
    the shorter (T = 1: a chunk of one step, the recurrence)."""
    Q = min(chunk, steps)
    pad = -steps % Q
    return Q, pad, (steps + pad) // Q


def in_chunks(a, Q, pad):
    """a [B, T, ...] -> [B, c, Q, ...], float32 (`done` stays bool), the
    last chunk padded with zeros: steps that pass a state on as it is
    (this module's scan and models/qwen3next.py's)."""
    a = a.astype(jnp.float32) if a.dtype != bool else a
    a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    return a.reshape((a.shape[0], a.shape[1] // Q, Q) + a.shape[2:])


def ends_in_chunks(done, Q, pad):
    """Episodes ended in the chunk up to and including each step:
    [B, c, Q] of done [B, T]."""
    return jnp.cumsum(in_chunks(done, Q, pad).astype(jnp.int32), axis=2)


def reaches(ends):
    """[B, c, Q, Q]: source step j reaches step i of its chunk, j <= i
    and no episode end in (j, i] (their counts of ends are equal)."""
    Q = ends.shape[-1]
    return jnp.tril(jnp.ones((Q, Q), bool)) & (
        ends[:, :, :, None] == ends[:, :, None, :]
    )


def ssd_scan(x, dt, A, B_in, C_in, state, done, chunk):
    """The Mamba-2 recurrence over an unroll, in chunks, with episode
    ends inside them.

    x [B, T, H, P]; dt [B, T, H] (after the softplus); A [H] (negative);
    B_in, C_in [B, T, G, N], head h reading group h // (H / G); state
    [B, H, P, N], what the unroll starts from; done [B, T] bool: the
    state carried INTO a step where it is set is zeros. Returns (y
    [B, T, H, P] without the D x skip, the state after the last step).

        h_t = (0 if done_t else exp(dt_t A) h_{t-1}) + dt_t x_t B_t^T
        y_t = h_t C_t

    Everything in float32; the decays are exponentials of differences
    of cumulative sums of dt A within a chunk (never of a sum across an
    episode end: where one lies between two steps the comparison of
    their counts of ends says 0, and the difference is not read).
    The last chunk is padded with steps of dt = 0, which pass the state
    on as it is. A chunk of one step (T = 1) is the recurrence.

    Two forms, chosen by the shapes alone (`ops/ssd_scan.kernels_apply`):
    a learner's unroll at published widths runs ops/ssd_scan.py's two
    Mosaic kernels (a head block's state in VMEM from chunk to chunk, x,
    B, C and y read and written as [B, T, H P] and [B, T, G N]); acting
    at T = 1 and toy widths run the `jax.numpy` form below, which is
    what the kernels are held to (tests/test_ssd_scan_kernel.py)."""
    rows, steps, H, P = x.shape
    G, N = B_in.shape[2:]
    per = H // G
    Q, pad, nc = chunk_plan(steps, chunk)
    if scan_kernels.kernels_apply(steps, Q, H, P, G, N):
        return scan_kernels.scan(
            x, dt, A, B_in, C_in, state, done, Q, terms_traced_under()
        )

    def chunks(a):
        return in_chunks(a, Q, pad)

    x = chunks(x).reshape(rows, nc, Q, G, per, P)
    dt = chunks(dt).reshape(rows, nc, Q, G, per)
    B_in, C_in = chunks(B_in), chunks(C_in)
    ends = ends_in_chunks(done, Q, pad)  # [B, c, Q]
    # [B, c, G, per, Q]: steps last, the lanes' axis.
    dt_last = dt.transpose(0, 1, 3, 4, 2)
    cs = jnp.cumsum(dt_last * A.reshape(G, per, 1), axis=-1)

    def along_heads(mask):  # [B, c, ...] -> [B, c, 1, 1, ...]
        return mask[:, :, None, None]

    with device_scope("ssd_intra"):
        decay = jnp.exp(jnp.where(
            along_heads(reaches(ends)),
            cs[..., :, None] - cs[..., None, :], -jnp.inf,
        ))  # [B, c, G, per, Q, Q]
        scores = jnp.einsum("bcign,bcjgn->bcgij", C_in, B_in)
        weights = scores[:, :, :, None] * decay * dt_last[..., None, :]
        y = jnp.einsum("bcghij,bcjghp->bcighp", weights, x)
    with device_scope("ssd_states"):
        # What the chunk's own steps leave in the state at its end, and
        # what it hands on of the state it was given.
        to_end = jnp.exp(jnp.where(
            along_heads(ends[:, :, -1:] == ends),
            cs[..., -1:] - cs, -jnp.inf,
        )) * dt_last  # [B, c, G, per, Q]
        left = jnp.einsum(
            "bcjghp,bcjgn->bcghpn",
            x * to_end.transpose(0, 1, 4, 2, 3)[..., None], B_in,
        )
        handed_on = jnp.where(
            along_heads(ends[:, :, -1] == 0), jnp.exp(cs[..., -1]), 0.0
        )  # [B, c, G, per]
    with device_scope("ssd_inter"):
        def pass_on(entering, chunk_parts):
            left_c, handed_on_c = chunk_parts
            leaving = handed_on_c[..., None, None] * entering + left_c
            return leaving, entering

        last, entering = jax.lax.scan(
            pass_on, state.reshape(rows, G, per, P, N).astype(jnp.float32),
            (left.swapaxes(0, 1), handed_on.swapaxes(0, 1)),
        )
        from_start = jnp.where(
            along_heads(ends == 0), jnp.exp(cs), 0.0
        )  # [B, c, G, per, Q]
        y = y + jnp.einsum(
            "bcign,cbghpn->bcighp", C_in, entering
        ) * from_start.transpose(0, 1, 4, 2, 3)[..., None]
    y = y.reshape(rows, nc * Q, H, P)[:, :steps]
    return y, last.reshape(rows, H, P, N)


def conv_over_episodes(inputs, tail, done, taps, bias):
    """The causal depthwise convolution of a Mamba-2 mixer over an
    unroll, as shifted adds, with episode ends inside it.

    inputs [B, T, C]; tail [K - 1, B, C], the K - 1 inputs before the
    unroll as the state holds them; done [B, T]; taps [K, C] (the last
    is the step's own); bias [C], or None for a convolution without one
    (models/qwen3next.py). A tap is read only where no episode
    ended between its step and the step it is read at. Returns (the
    convolution [B, T, C] in float32, before the silu; the tail the
    next unroll starts from, cut at the last episode end).

    Two forms, chosen by the shapes alone (`ops/short_conv.kernels_
    apply`): a learner's unroll at published widths runs ops/short_conv.
    py's two Mosaic kernels, one pass over the array a direction;
    acting at T = 1 and toy widths run the `jax.numpy` form below, which
    is what the kernels are held to (tests/test_short_conv.py)."""
    rows, steps, channels = inputs.shape
    K = taps.shape[0]
    if short_conv.kernels_apply(steps, channels, K):
        may = short_conv.reach(done, K)
        # The K - 1 steps before the next unroll's first, each kept
        # where no episode ended after it.
        recent = jnp.concatenate(
            [tail.transpose(1, 0, 2), inputs[:, 1 - K :].astype(jnp.float32)],
            axis=1,
        )[:, 1 - K :]
        kept = may[:, -1:] >= jnp.arange(K - 2, -1, -1)
        return (
            short_conv.short_conv(inputs, tail, may, taps, bias),
            jnp.where(kept[..., None], recent, 0.0).transpose(1, 0, 2),
        )
    # Episodes ended up to and including each step; the carried tail
    # lies before all of them.
    ends = jnp.cumsum(done.astype(jnp.int32), axis=1)  # [B, T]
    ends_of = jnp.concatenate(
        [jnp.zeros((rows, K - 1), ends.dtype), ends], axis=1
    )
    inputs = jnp.concatenate(
        [tail.transpose(1, 0, 2), inputs.astype(jnp.float32)], axis=1
    )  # [B, K - 1 + T, C]: times -(K - 1) .. T - 1
    conv = 0.0 if bias is None else bias.astype(jnp.float32)
    for k in range(K):
        conv = conv + taps[k] * jnp.where(
            (ends_of[:, k : k + steps] == ends)[..., None],
            inputs[:, k : k + steps], 0.0,
        )
    new_tail = jnp.where(
        (ends_of[:, steps:] == ends[:, -1:])[..., None],
        inputs[:, steps:], 0.0,
    ).transpose(1, 0, 2)
    return conv, new_tail


def gated_group_norm(y, z, scale, groups, eps):
    """rmsnorm_per_group(y * silu(z)) * scale: the gate first, then the
    norm inside each of the `groups` equal parts of the last axis, each
    cut out as a slice: y is then never [.., groups, width], which the
    chip lays out apart from [.., groups * width] (a copy in and a copy
    out of every mixer, 3.3 ms of Nemotron-3's step once the scan's
    kernels hand y over flat; PERF.md section 6, PR 65)."""
    gated = y * nn.silu(z)
    width = y.shape[-1] // groups
    parts = [
        gated[..., g * width : (g + 1) * width] for g in range(groups)
    ]
    return jnp.concatenate([
        part * jax.lax.rsqrt(
            jnp.mean(jnp.square(part), axis=-1, keepdims=True) + eps
        )
        for part in parts
    ], axis=-1) * scale


def dt_bias_init(low, high, floor):
    """The bias whose softplus is log-uniform in [low, high] (clipped
    at `floor`): what the config's `time_step_*` keys seed."""
    def init(key, shape, dtype=jnp.float32):
        step = jnp.exp(
            jax.random.uniform(key, shape, dtype)
            * (math.log(high) - math.log(low)) + math.log(low)
        )
        step = jnp.maximum(step, floor)
        return step + jnp.log(-jnp.expm1(-step))

    return init


def uniform_between(low, high, transform=lambda value: value):
    def init(key, shape, dtype=jnp.float32):
        return transform(jax.random.uniform(key, shape, dtype, low, high))

    return init


def _norm(name, eps):
    return nn.RMSNorm(epsilon=eps, name=name)


def _proj(name, width, dtype):
    return nn.Dense(width, use_bias=False, dtype=dtype, name=name)


def mamba_mixer(module, h, state, done):
    """A Mamba-2 mixer from `in_proj` to `out_proj`, in the compact
    method of `module`, which holds the mixer's parameters beside its
    own and states its sizes (`d_model`, `heads`, `head_dim`, `groups`,
    `state_size`, `conv_kernel`, `chunk_size`, `rms_norm_eps`,
    `time_step` (min, max, floor), `dtype`): `_MambaBlock` below, and a
    layer of models/granite4.py.

    h [B, T, d], ALREADY NORMED; state (h [H, B, P, N], the
    convolution's last conv_kernel - 1 inputs [K - 1, B, C]) as the
    state holds them; done [B, T]. Returns (the mixer's branch [B, T,
    d] in float32, what the caller adds to its residual stream as its
    layer says; (h, tail) to start the next unroll from)."""
    rows, steps, _ = h.shape
    H, P, G, N = (
        module.heads, module.head_dim, module.groups, module.state_size
    )
    K = module.conv_kernel
    inner = H * P
    channels = inner + 2 * G * N
    carried, tail = state

    with device_scope("mamba_in_proj"):
        joined = _proj("in_proj", 2 * inner + 2 * G * N + H, module.dtype)(h)
        z = joined[..., :inner]
        xBC = joined[..., inner : inner + channels]
        dt = joined[..., inner + channels :]

    with device_scope("mamba_conv"):
        bound = 1.0 / math.sqrt(K)
        xBC, new_tail = conv_over_episodes(
            xBC, tail, done,
            module.param(
                "conv_kernel", uniform_between(-bound, bound), (K, channels)
            ),
            module.param(
                "conv_bias", uniform_between(-bound, bound), (channels,)
            ),
        )
        xBC = nn.silu(xBC)

    with device_scope("ssd_scan"):
        dt = nn.softplus(
            dt.astype(jnp.float32)
            + module.param("dt_bias", dt_bias_init(*module.time_step), (H,))
        )
        A = -jnp.exp(module.param(
            "A_log", uniform_between(1.0, 16.0, jnp.log), (H,)
        ))
        heads_x = xBC[..., :inner].reshape(rows, steps, H, P)
        y, new_carried = ssd_scan(
            heads_x, dt, A,
            xBC[..., inner : inner + G * N].reshape(rows, steps, G, N),
            xBC[..., inner + G * N :].reshape(rows, steps, G, N),
            carried.transpose(1, 0, 2, 3), done, module.chunk_size,
        )
        y = y + module.param("D", nn.initializers.ones, (H,))[
            :, None
        ] * heads_x

    with device_scope("mamba_gate_norm"):
        y = gated_group_norm(
            y.reshape(rows, steps, inner), z.astype(jnp.float32),
            module.param("gate_norm", nn.initializers.ones, (inner,)),
            G, module.rms_norm_eps,
        )
    with device_scope("mamba_out_proj"):
        branch = _proj("out_proj", module.d_model, module.dtype)(
            y.astype(module.dtype)
        ).astype(jnp.float32)

    return branch, (new_carried.transpose(1, 0, 2, 3), new_tail)


def count_mamba_application(module, done):
    """One `mamba_mixer` application of `module` over done [B, T], for
    the update's stats: how many such layers and the bytes of state a
    row carries through them; the chunks the unroll's scan was cut into
    and the episode ends a row had, which every layer says alike. Its
    caller says so once its branch has joined the stream."""
    if module.is_initializing():
        return
    H, P, G, N = (
        module.heads, module.head_dim, module.groups, module.state_size
    )
    steps = done.shape[1]
    Q = min(module.chunk_size, steps)
    for name, value, fold in (
        ("ssm_applications", 1.0, "sum"),
        # Those whose scan is ops/ssd_scan.py's kernels.
        ("ssm_kernel_applications",
         float(scan_kernels.kernels_apply(steps, Q, H, P, G, N)), "sum"),
        # Those whose convolution is ops/short_conv.py's kernels.
        ("conv_kernel_applications",
         float(short_conv.kernels_apply(
             steps, H * P + 2 * G * N, module.conv_kernel
         )), "sum"),
        ("ssm_state_bytes_per_row",
         4 * (H * P * N + (module.conv_kernel - 1) * (H * P + 2 * G * N)),
         "sum"),
        ("ssm_chunks", -(-steps // Q), "same"),
        ("ssm_resets_per_row",
         jnp.mean(jnp.sum(done.astype(jnp.float32), axis=1)), "same"),
    ):
        sow_stat(module, name, value, fold)


class _MambaBlock(nn.Module):
    d_model: int
    heads: int  # held here
    head_dim: int
    groups: int  # held here
    state_size: int
    conv_kernel: int
    chunk_size: int
    rms_norm_eps: float
    time_step: Tuple[float, float, float]  # min, max, floor
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, state, done):
        """x [B, T, d]; state and done as `mamba_mixer` takes them.
        Returns (y, (h, tail)) to start the next unroll from."""
        with device_scope("mamba_in_proj"):
            h = _norm("norm", self.rms_norm_eps)(x)
        branch, new_state = mamba_mixer(self, h, state, done)
        with device_scope("mamba_out_proj"):
            x = x + branch
        count_mamba_application(self, done)
        return x, new_state


def cache_and_mask(cache_state, cache_mask, seq_mask):
    """What `attention_mixer` attends over, of the block contract's
    arguments: the cache (k, v) batch-first, [B, M, kv_heads, hd], and
    one mask over [cache; unroll], [B, T, M + T]."""
    cache = tuple(c.transpose(1, 0, 2, 3) for c in cache_state)
    return cache, jnp.concatenate([cache_mask, seq_mask], axis=-1)


def attention_mixer(module, h, cache, mask, scale=None):
    """A position-free grouped-query attention from its projections to
    `o`, in the compact method of `module`, which holds the parameters
    beside its own and states `d_model`, `num_heads`, `kv_heads`,
    `head_dim`, `memory_len`, `dtype`: `_AttentionBlock` below, and a
    layer of models/granite4.py (which also names `scale`, what the
    scores are multiplied by; None is head_dim^-0.5).

    h [B, T, d], ALREADY NORMED; cache and mask as `cache_and_mask`
    makes them. Returns (the branch [B, T, d] in float32, k, v: this
    unroll's keys and values [B, T, kv_heads, hd]). No positional
    embedding: a key is what it was when cached."""
    rows, steps, _ = h.shape
    H, Hkv, hd = module.num_heads, module.kv_heads, module.head_dim
    q = _proj("q", H * hd, module.dtype)(h).reshape(rows, steps, H, hd)
    k, v = (
        _proj(name, Hkv * hd, module.dtype)(h).reshape(rows, steps, Hkv, hd)
        for name in ("k", "v")
    )
    k_all = jnp.concatenate([cache[0].astype(k.dtype), k], axis=1)
    v_all = jnp.concatenate([cache[1].astype(v.dtype), v], axis=1)
    # The cache is the learner's data: its M keys take no gradient (as
    # models/mellum2.py).
    attended = dense_transformer_attend(
        q, k_all, v_all, mask, None, None, module.memory_len, scale=scale
    )
    if fused_pass_applies(q.shape, k_all.shape, None):
        count_fused_application(module)
    branch = _proj("o", module.d_model, module.dtype)(
        attended.reshape(rows, steps, H * hd)
    ).astype(jnp.float32)
    return branch, k.astype(jnp.float32), v.astype(jnp.float32)


class _AttentionBlock(nn.Module):
    d_model: int
    num_heads: int  # held here
    kv_heads: int  # held here
    head_dim: int
    memory_len: int
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
                self, _norm("norm", self.rms_norm_eps)(x), cache, mask
            )
            x = x + branch
        return x, k, v


class _LatentMoEBlock(nn.Module):
    d_model: int
    num_experts: int
    held: Any  # (first, count) of the routed experts, or None for all
    experts_per_token: int
    expert_width: int
    latent_width: int
    shared_width: int
    renormalise: bool
    routed_scaling: float
    bias_update_rate: float
    rms_norm_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        rows, steps, d = x.shape
        y = DroplessMoE(
            d_ff=self.expert_width,
            num_experts=self.num_experts,
            top_k=self.experts_per_token,
            aux_loss_weight=0.0,  # the bias balances; no auxiliary loss
            renormalise=self.renormalise,
            held=self.held,
            scoring="sigmoid",
            selection_bias=True,
            bias_update_rate=self.bias_update_rate,
            routed_scaling=self.routed_scaling,
            shared_width=self.shared_width,
            gated=False,
            activation="relu2",
            latent_width=self.latent_width,
            dtype=self.dtype,
            name="moe",
        )(_norm("norm", self.rms_norm_eps)(x).reshape(rows * steps, d))
        return x + y.reshape(rows, steps, d)


def held_mixers(mixer_share, mamba_heads, mamba_groups, num_heads, kv_heads):
    """What share i of n holds of each mixer (`--mixer_share i/n`):
    (Mamba heads, their B/C groups, query heads, key/value heads). n
    divides the groups (a head comes with its group, so the grouped
    norm stays on the chip) and the query heads; the key/value heads
    are divided too, or where n is a multiple of them the one head the
    chip's query heads read is held."""
    share, of = mixer_share
    if (
        not 0 <= share < of or mamba_groups % of or num_heads % of
        or mamba_heads % mamba_groups or (kv_heads % of and of % kv_heads)
    ):
        raise ValueError(
            f"--mixer_share {share}/{of}: share i of n takes 0 <= i < n, "
            f"and n divides the {mamba_groups} groups of the Mamba heads "
            f"and the {num_heads} query heads on {kv_heads} key/value heads"
        )
    return (
        mamba_heads // of, mamba_groups // of, num_heads // of,
        max(1, kv_heads // of),
    )


class Nemotron3Net(TransformerNet):
    # Fields the published table sets, or that the blocks do not read:
    # no flag reaches them (models/__init__.py `takes_flag`).
    flag_refused_fields = ("num_experts",)

    num_layers: int = PUBLISHED["num_layers"]
    layer_pattern: str = PUBLISHED["layer_pattern"]
    layer_period: str = PUBLISHED["layer_period"]
    d_model: int = PUBLISHED["d_model"]
    num_heads: int = PUBLISHED["num_heads"]
    kv_heads: int = PUBLISHED["kv_heads"]
    head_dim: int = PUBLISHED["head_dim"]
    mamba_heads: int = PUBLISHED["mamba_heads"]
    mamba_head_dim: int = PUBLISHED["mamba_head_dim"]
    mamba_groups: int = PUBLISHED["mamba_groups"]
    state_size: int = PUBLISHED["state_size"]
    conv_kernel: int = PUBLISHED["conv_kernel"]
    chunk_size: int = PUBLISHED["chunk_size"]
    # Not the model's 262,144 positions: the attention layers' rolling
    # cache of the policy's own past. The Mamba layers carry a state,
    # not a window, and reach as far back as the episode goes.
    memory_len: int = 4095
    num_experts: int = PUBLISHED["num_experts"]
    experts_per_token: int = PUBLISHED["experts_per_token"]
    expert_width: int = PUBLISHED["expert_width"]
    latent_width: int = PUBLISHED["latent_width"]
    shared_width: int = PUBLISHED["shared_width"]
    renormalise: bool = PUBLISHED["renormalise"]
    routed_scaling: float = PUBLISHED["routed_scaling"]
    rms_norm_eps: float = PUBLISHED["rms_norm_eps"]
    time_step_min: float = PUBLISHED["time_step_min"]
    time_step_max: float = PUBLISHED["time_step_max"]
    time_step_floor: float = PUBLISHED["time_step_floor"]
    # (i, n): this chip is share i of the n that divide each layer's
    # routed experts (`--expert_share i/n`), and of the n that divide
    # each mixer's heads (`--mixer_share i/n`). (0, 1): all are here.
    expert_share: Tuple[int, int] = (0, 1)
    mixer_share: Tuple[int, int] = (0, 1)
    # DeepSeek-V3's bias update speed, as models/kanana2.py; config.json
    # has no key for the rule or the speed.
    bias_update_rate: float = 0.001
    # Frames to [-1, 1], for the reason models/olmoe.py gives.
    frame_range: Tuple[float, float] = (-1.0, 1.0)
    # For the reason models/mellum2.py gives: even seeded routing.
    zero_init_extras: bool = True
    # Every matmul of the family in three bf16 passes on the MXU (JAX
    # precision `high`), the grouped expert matmuls among them (models/
    # moe.py), as models/kanana2.py and for its reason: what feeds a
    # router is rounded, and here the 22nd choice among 512 close
    # scores decides. PERF.md section 6 (PR 42) has the readings.
    matmul_precision: str = "high"
    # The attention layer's two products over the keys follow it: in
    # `dense_transformer_attend`'s fused regime the kernels then read
    # float32 operands, cut them into two bf16 terms in VMEM and make
    # the same three passes (`fused_attend`'s `terms`, 2 under `high`).
    # At one bfloat16 pass the layer, first of the period, rounds what
    # all five routers read to 1e-3, and one seed in 90 read 7.9e-3 of
    # the loss's scale on the chip (PERF.md section 6).

    def __call__(self, inputs, core_state, **kwargs):
        # Read when a dot is traced, and kept by its gradient's.
        with jax.default_matmul_precision(self.matmul_precision):
            return super().__call__(inputs, core_state, **kwargs)

    def __post_init__(self):
        self.pattern()  # refuses a depth that is no whole periods
        self.held_experts()  # and a share that is none
        self.held_mixers()
        super().__post_init__()

    @nn.nowrap
    def pattern(self) -> str:
        """A letter a layer: the published order when all its layers
        are asked for, else whole periods of `layer_period`."""
        if self.num_layers == len(self.layer_pattern):
            return self.layer_pattern
        period = len(self.layer_period)
        if self.num_layers < 1 or self.num_layers % period:
            raise ValueError(
                f"--num_layers {self.num_layers}: --model nemotron3 is cut "
                f"in whole periods of {period} layers ({self.layer_period}), "
                f"or is all {len(self.layer_pattern)}"
            )
        return self.layer_period * (self.num_layers // period)

    @nn.nowrap
    def held_experts(self):
        """(first, count) of the experts this chip holds, None for all."""
        return held_experts(self.expert_share, self.num_experts)

    @nn.nowrap
    def held_mixers(self):
        return held_mixers(
            self.mixer_share, self.mamba_heads, self.mamba_groups,
            self.num_heads, self.kv_heads,
        )

    @nn.nowrap
    def layer_caches(self):
        """By the layer's letter: attention a window of keys and values,
        Mamba-2 a state [H, B, P, N] and its convolution's tail
        [K - 1, B, H P + 2 G N], the experts nothing."""
        heads, groups, _, kv_heads = self.held_mixers()
        carried = Recurrent((
            (heads, self.mamba_head_dim, self.state_size),
            (self.conv_kernel - 1,
             heads * self.mamba_head_dim + 2 * groups * self.state_size),
        ))
        by_letter = {
            ATTENTION: (self.memory_len, kv_heads, self.head_dim),
            MAMBA: carried,
            EXPERTS: None,
        }
        return tuple(by_letter[letter] for letter in self.pattern())

    @nn.nowrap
    def make_block(self, name: str, layer: int):
        letter = self.pattern()[layer]
        heads, groups, query_heads, kv_heads = self.held_mixers()
        shared = dict(
            d_model=self.d_model, rms_norm_eps=self.rms_norm_eps,
            dtype=self.dtype, name=name,
        )
        if letter == MAMBA:
            cls, fields = _MambaBlock, dict(
                heads=heads, head_dim=self.mamba_head_dim, groups=groups,
                state_size=self.state_size, conv_kernel=self.conv_kernel,
                chunk_size=self.chunk_size,
                time_step=(
                    self.time_step_min, self.time_step_max,
                    self.time_step_floor,
                ),
            )
        elif letter == ATTENTION:
            cls, fields = _AttentionBlock, dict(
                num_heads=query_heads, kv_heads=kv_heads,
                head_dim=self.head_dim, memory_len=self.memory_len,
            )
        else:
            cls, fields = _LatentMoEBlock, dict(
                num_experts=self.num_experts, held=self.held_experts(),
                experts_per_token=self.experts_per_token,
                expert_width=self.expert_width,
                latent_width=self.latent_width,
                shared_width=self.shared_width,
                renormalise=self.renormalise,
                routed_scaling=self.routed_scaling,
                bias_update_rate=self.bias_update_rate,
            )
        return (rematerialised(cls) if self.remat else cls)(
            **fields, **shared
        )

    @nn.nowrap
    def make_final_norm(self):
        return _norm("final_norm", self.rms_norm_eps)
