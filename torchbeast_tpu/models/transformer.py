"""Transformer policy with episode-aware KV-cache memory.

A long-context model family beyond the reference's conv+LSTM nets: the core
attends causally over the unroll AND over a rolling key/value cache carried
across unrolls as the recurrent state (so acting at T=1 still sees up to
`memory_len` past steps). Episode boundaries are enforced everywhere:

- within the unroll, attention is masked to the current segment
  (ops/attention.segment_ids_from_done — state "resets where done" exactly
  like the LSTM cores);
- cache entries are visible only while NO done has occurred in the unroll
  up to the query step;
- the cache written back keeps only entries from the final segment.

Attention is windowed to the last `memory_len` steps via a band mask over
the cache's slots and the unroll's steps (built a leg at a time,
ops/attention.band_by_leg) — EXACTLY the semantics of stepwise
acting with rolling cache eviction, so the learner's batch forward and the
actor's T=1 forwards agree bit-for-bit at any unroll length or cache fill
(pinned by tests/test_transformer.py). Positions enter through a learned
RELATIVE bias over offsets 0..memory_len (absolute positions would break
cache consistency).

The cache pytree uses the framework-wide state convention (batch on axis
1: k/v [M, B, H, D], valid [M, B]), so the queues/batcher/collectors carry
it exactly like LSTM state. The walk hands a block its cache in that
layout and rolls it in that layout (`roll_kv_cache(..., axis=0)`): no
batch-first copy of a cache is made on the way in or out; a block that
wants one (`_Block`, models/mellum2.py) makes it.

Sequence parallelism: construct with `mesh=` (a jax Mesh with a `seq`
axis) and unrolls whose T is divisible by the axis size run their
in-unroll attention as RING attention (ops/attention.
ring_transformer_attention) — K/V blocks rotate over ICI while queries
stay put, with the band mask, segment mask, relative bias, and KV-cache
leg softmax-merged online so numerics match the dense path (pinned by
tests/test_transformer.py::test_ring_path_*). Short unrolls (acting at
T=1) automatically use the dense path with the SAME parameters, so one
model serves both.
"""

import contextlib
import functools
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchbeast_tpu.models.cores import RecurrentPolicyHead
from torchbeast_tpu.models.stats import sow_stat
from torchbeast_tpu.ops.bf16_terms import bf16_terms, terms_traced_under
from torchbeast_tpu.ops.attention import (
    band_by_leg,
    band_relative_offsets,
    dense_transformer_attend,
    ring_transformer_attention,
    roll_kv_cache,
    segment_ids_from_done,
    ulysses_transformer_attention,
)
from torchbeast_tpu.ops.fused_attention import KEPT_FORWARD
from torchbeast_tpu.telemetry import device_scope


@functools.lru_cache(maxsize=None)
def _keeping(*also_kept):
    """ONE policy object a set of names: jax shares a block's inner
    functions across blocks (and the lowering emits them once) only
    under the same policy object, as it does under `nn.remat`'s None."""
    return jax.checkpoint_policies.save_only_these_names(
        *KEPT_FORWARD, *also_kept
    )


@functools.lru_cache(maxsize=None)
def rematerialised(block_cls, *also_kept):
    """`block_cls` as a family's `make_block` instantiates it under
    `--remat all`: `nn.remat` around it under the policy that keeps, of
    a block's forward pass, the fused attention pass's two results
    (ops/fused_attention.py `KEPT_FORWARD`: the output as the backward
    kernel reads it and a float a row of log-sum-exp, 46 MB a layer in
    the Trinity cell, so the block's second forward does not call the
    forward kernel again, 4.5 to 8.1 ms a call there) and whatever the
    family names besides (`also_kept`: models/qwen3next.py's solves).
    A block that takes the dense body names nothing and is
    rematerialised whole, as under no policy. The class is a subclass
    that says so of itself (`keeps_forward_results`, read by
    `count_fused_application`); one a block class and set of names."""
    kept = type(
        block_cls.__name__, (block_cls,), {"keeps_forward_results": True}
    )
    return nn.remat(kept, policy=_keeping(*also_kept))


def _count_application(module: nn.Module, name: str) -> None:
    sow_stat(module, "attention_" + name, 1.0, "sum")


def count_two_leg_application(module: nn.Module) -> None:
    """One block application traced through ops/attention.
    cached_transformer_attend, for the update's stats (`attention_two_
    leg_applications`, learner.compute_loss; poly's gauge `attention.
    two_leg_applications`): 32 in the Ouro cell, 2 in OLMoE's. The path
    is compiled in, so the count is the trace's, not the device's."""
    _count_application(module, "two_leg_applications")


def count_fused_application(module: nn.Module) -> None:
    """One block application whose `dense_transformer_attend` took the
    fused pass (ops/attention.py `fused_pass_applies`, which the block
    asks with the shapes it hands over): `attention_fused_applications`,
    4 in the Mellum2 cell, no such key where no block took it. Beside
    it `attention_products_cut_in_kernel`: those of them whose products
    the kernels make from float32 tiles cut into bfloat16 terms in VMEM,
    which is under a caller that traces at more than one term (`high`,
    `highest`: 1 in the LFM2, Qwen3-Next and Nemotron-3 cells). Not
    sown where there is none: a sown zero would be one more output of
    Mellum2's update, which this leaves as it was. And `attention_
    forward_results_kept`: those whose block is `rematerialised` under
    the policy that keeps the pass's forward results, which is all of
    them under `--remat all` (5 in the Trinity cell) and none without."""
    _count_application(module, "fused_applications")
    if terms_traced_under() > 1:
        _count_application(module, "products_cut_in_kernel")
    if getattr(module, "keeps_forward_results", False):
        _count_application(module, "forward_results_kept")


def count_latent_application(module: nn.Module) -> None:
    """One block application traced through ops/attention.
    latent_cached_attend: `attention_latent_applications`, 5 in the
    Kanana-2 cell."""
    _count_application(module, "latent_applications")


def count_latent_fused_application(module: nn.Module) -> None:
    """One block application whose `latent_cached_attend` computed its
    cache leg by the fused pass (ops/attention.py `fused_latent_leg_
    applies`, asked by the block with the shapes it hands over):
    `attention_latent_fused_applications`, 5 in the Kanana-2 cell, no
    such key where no block took it."""
    _count_application(module, "latent_fused_applications")


def scaled_frames(frame, frame_range, dtype):
    """Frames [T, B, ...] as the [T * B, F] values of `frame_range` in
    `dtype`, merged time-major: the float expression's left operand."""
    T, B = frame.shape[:2]
    x = frame.reshape((T * B, -1)).astype(dtype) / 255.0
    low, high = frame_range
    if (low, high) != (0.0, 1.0):
        x = low + (high - low) * x
    return x


def _symmetric(frame_range):
    low, high = frame_range
    return low + high == 0


def integers_to_range(frame_range):
    """(scale, shift): uint8 frames scaled to `frame_range` are `scale
    * frame_integers(frame) + shift`."""
    low, high = frame_range
    if _symmetric(frame_range):
        return (high - low) / 510.0, 0.0
    return (high - low) / 255.0, float(low)


def frame_integers(frame, frame_range):
    """uint8 frames [rows, steps, ...] as the [rows, steps, F] integers
    the product multiplies, which bfloat16 holds exactly. Where the
    range is symmetric they are 2u - 255, the odd integers of [-255,
    255] (eight significant bits, as 0..255 has), and nothing is
    shifted after: the operand is then 255 times the very value the
    float expression multiplies, so the weights' terms are rounded
    against magnitudes no larger than the float path's and nothing is
    added back that the product must cancel. Any other range takes u
    itself and a shift of `low`, which reaches the result as `low *
    colsum(W)` (nothing where low is 0)."""
    u = frame.reshape(frame.shape[:2] + (-1,))
    if _symmetric(frame_range):
        u = 2 * u.astype(jnp.int32) - 255
    return u.astype(jnp.bfloat16)


def _one_pass(spec, lhs, rhs):
    """bfloat16 x bfloat16 -> float32: one pass of the MXU whatever
    `jax.default_matmul_precision` the caller traces under."""
    return jnp.einsum(
        spec, lhs, rhs, precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )


def _sum_of_passes(spec, operand, cut):
    """`operand` against each bfloat16 term of `cut`, the smallest
    term's product first, summed in float32."""
    passes = [_one_pass(spec, operand, term) for term in reversed(cut)]
    return functools.reduce(jnp.add, passes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _integer_frames_times(frame, kernel, frame_range, terms):
    """The integers of `frame` [T, B, ...] (`frame_integers`) times the
    float32 `kernel` [F, d] -> [B, T, d] float32. The kernel is cut in
    `terms` bfloat16 terms (ops/bf16_terms.py) and the frames are ONE,
    exact: `terms` passes of the MXU, where a float32 operand that no
    longer looks like an integer costs three under `high` and six under
    `highest`.
    The columns are contracted with batch and time BOTH FREE (the
    uint8 frames read batch-first: a layout for the compiler to choose,
    the cast to bfloat16 part of it), so no [T * B] merge fixes an
    order and nothing is transposed after; on the v5e that beats either
    merge at every cell's shapes (PERF.md section 6, PR 52: merged
    time-major, the order the frames are held in, it is twice as slow
    at 4,096 rows).

    Differentiated as one unit: the kernel's cotangent is the same
    integers against the result's cotangent cut in `terms` terms (by
    autodiff the cotangent would go through dots at the caller's
    precision, three passes under `high`), the frames get none, and the
    rule's one residual is the uint8 frame, an input of the step."""
    operand = frame_integers(frame.swapaxes(0, 1), frame_range)
    return _sum_of_passes("btf,fd->btd", operand, bf16_terms(kernel, terms))


def _integer_frames_times_fwd(frame, kernel, frame_range, terms):
    return _integer_frames_times(frame, kernel, frame_range, terms), frame


def _integer_frames_times_bwd(frame_range, terms, frame, dy):
    # The same expression as the forward's, on the residual uint8 frame:
    # XLA shares the forward's bfloat16 operand (2 bytes a frame's byte
    # held through the step, where the float path held 4). Making it
    # again behind an `optimization_barrier` was tried: 0.2 GiB lower at
    # 4,096 rows and 1.3-2.4 ms a step slower in every cell (PERF.md
    # section 6, PR 52).
    operand = frame_integers(frame.swapaxes(0, 1), frame_range)
    return None, _sum_of_passes("btf,btd->fd", operand, bf16_terms(dy, terms))


_integer_frames_times.defvjp(
    _integer_frames_times_fwd, _integer_frames_times_bwd
)


def frame_projection(frame, kernel, bias, frame_range, terms):
    """`Dense_0` on uint8 frames as the chip computes it: frame [T, B,
    ...] uint8, kernel [F, d], bias [d] -> [B, T, d] float32, equal to
    `scaled(frame) @ kernel + bias` at `terms` bfloat16 terms of the
    kernel (1 at JAX's default matmul precision, 2 under `high`, 3
    under `highest`). The frame enters the product as the integers it
    is; the scale and the shift of `frame_range` are linear, `(a u + b)
    W = a (u W) + b colsum(W)`, and are applied to the [B, T, d]
    result, in float32: no float32 copy of the frames is made."""
    scale, shift = integers_to_range(frame_range)
    kernel = kernel.astype(jnp.float32)
    offset = bias.astype(jnp.float32)
    if shift:
        offset = offset + shift * jnp.sum(kernel, axis=0)
    return scale * _integer_frames_times(
        frame, kernel, frame_range, terms
    ) + offset


class Recurrent(NamedTuple):
    """A `layer_caches()` entry that is no window: a state the layer's
    mixer carries from step to step (models/nemotron3.py: a Mamba-2
    state and the tail of its convolution). `leaves` are the leaves'
    shapes with the batch left out; the state holds each as [first, B,
    *rest] (batch on axis 1, the framework's convention), zeros at an
    episode's start. Nothing is rolled and no band applies: the block
    is handed the leaves and `done` [B, T], resets where an episode
    ends, and hands back the leaves the next unroll starts from."""

    leaves: Tuple[Tuple[int, ...], ...]


class _Block(nn.Module):
    d_model: int
    num_heads: int
    memory_len: int
    dtype: Any = jnp.float32
    mesh: Any = None  # set -> ring attention over mesh axis `seq_axis`
    seq_axis: str = "seq"
    ring_schedule: str = "contiguous"  # or "zigzag" (balanced causal work)
    sp_strategy: str = "ring"  # or "ulysses": all-to-all head sharding
    batch_axis: Any = None  # composite mesh: batch dim's data axis name
    num_experts: int = 0  # >0 -> MoE FFN (models/moe.py)
    moe_top_k: int = 2
    moe_mesh: Any = None  # mesh with an `expert` axis -> expert parallel

    @nn.compact
    def __call__(self, x, cache_state, cache_mask, seq_mask, seg=None):
        """The block contract of `TransformerNet`'s walk. x: [B, T, d];
        cache_state: (k, v), the layer's cache AS THE STATE HOLDS IT,
        [M, B, H, hd]; cache_mask [B, T, M] and seq_mask [B, T, T]
        (True = may attend): the masks of the two legs, the cache and
        the unroll, apart. seg [B, T] feeds the ring path (which
        rebuilds the in-unroll band/segment mask per block instead of
        materializing [T, T]). Returns (y, new_k, new_v) where
        new_k/new_v are this unroll's [B, T, H, hd].

        A family's block takes of these what it reads. The OLMoE and
        Ouro blocks read the state and the two masks as they come
        (ops/attention.cached_transformer_attend: two legs of one
        softmax, nothing over M + T keys built). This one wants the
        batch-first cache and, on its dense and Ulysses branches, one
        mask over [cache; unroll] with the relative distances its bias
        is indexed by: it builds them here."""
        cache = tuple(c.transpose(1, 0, 2, 3) for c in cache_state)
        B, T, _ = x.shape

        def over_cache_and_unroll():
            """(mask [B, T, M+T], offsets [T, M+T]: distances query_time
            - key_time clipped to [0, M]) for the two branches that
            attend over the concatenation."""
            _, offsets = band_relative_offsets(T, self.memory_len)
            return jnp.concatenate([cache_mask, seq_mask], axis=-1), offsets

        H = self.num_heads
        hd = self.d_model // H

        h = nn.LayerNorm()(x)
        q = nn.DenseGeneral((H, hd), name="q", dtype=self.dtype)(h)
        k = nn.DenseGeneral((H, hd), name="k", dtype=self.dtype)(h)
        v = nn.DenseGeneral((H, hd), name="v", dtype=self.dtype)(h)

        # Learned relative-position bias over offsets 0..M (cache-stable:
        # positions are relative, so batch and stepwise forwards agree).
        rel_bias = self.param(
            "rel_bias", nn.initializers.zeros, (H, self.memory_len + 1)
        )

        blocks = (
            self.mesh.shape[self.seq_axis] if self.mesh is not None else 0
        )
        if self.sp_strategy == "ulysses":
            # Heads are the sharded resource after the all-to-all; the
            # acting path (T=1) falls back to dense like the ring does.
            use_ulysses = (
                self.mesh is not None
                and T % blocks == 0
                and H % blocks == 0
            )
            use_ring = False
        elif self.sp_strategy == "ring":
            divisor = (
                2 * blocks if self.ring_schedule == "zigzag" else blocks
            )
            use_ulysses = False
            use_ring = self.mesh is not None and T % divisor == 0
        else:
            raise ValueError(
                f"Unknown sp_strategy {self.sp_strategy!r} "
                "(expected 'ring' or 'ulysses')"
            )
        if use_ulysses:
            attended = ulysses_transformer_attention(
                q, k, v,
                cache[0].astype(k.dtype),
                cache[1].astype(v.dtype),
                *over_cache_and_unroll(), rel_bias,
                self.mesh, self.seq_axis,
                batch_axis=self.batch_axis,
            ).astype(v.dtype)
        elif use_ring:
            # Softmax runs in f32 on both paths; ring also keeps the
            # einsums f32 (scores never materialize globally, so the
            # bf16-MXU win matters less than exact online-merge numerics).
            attended = ring_transformer_attention(
                q.astype(jnp.float32),
                k.astype(jnp.float32),
                v.astype(jnp.float32),
                cache[0].astype(jnp.float32),
                cache[1].astype(jnp.float32),
                cache_mask,
                rel_bias,
                self.memory_len,
                seg,
                self.mesh,
                self.seq_axis,
                schedule=self.ring_schedule,
                batch_axis=self.batch_axis,
            ).astype(v.dtype)
        else:
            k_all = jnp.concatenate([cache[0].astype(k.dtype), k], axis=1)
            v_all = jnp.concatenate([cache[1].astype(v.dtype), v], axis=1)
            # Shared body with the Ulysses path (ops/attention.py) so the
            # dense==ulysses parity invariant cannot drift.
            attended = dense_transformer_attend(
                q, k_all, v_all, *over_cache_and_unroll(), rel_bias
            )
        x = x + nn.DenseGeneral(
            self.d_model, axis=(-2, -1), name="out", dtype=self.dtype
        )(attended).astype(jnp.float32)

        h = nn.LayerNorm()(x)
        if self.num_experts > 0:
            from torchbeast_tpu.models.moe import MoEFFN

            Bq, Tq, d = h.shape
            y = MoEFFN(
                d_model=d,
                d_ff=4 * d,
                num_experts=self.num_experts,
                top_k=self.moe_top_k,
                mesh=self.moe_mesh,
                dtype=self.dtype,
                name="moe",
            )(h.reshape(Bq * Tq, d))
            x = x + y.reshape(Bq, Tq, d)
        else:
            h = nn.Dense(4 * self.d_model, dtype=self.dtype)(h)
            h = nn.gelu(h)
            x = x + nn.Dense(self.d_model, dtype=self.dtype)(h).astype(
                jnp.float32
            )
        return x, k.astype(jnp.float32), v.astype(jnp.float32)


class TransformerNet(nn.Module):
    # The family's memory is its KV cache: --use_lstm does not apply
    # (models/__init__.py `create_model`).
    memory_is_kv_cache = True
    # `--remat`'s lever here (runtime/remat_plan.py): `make_block` wraps
    # each block in nn.remat when the `remat` field is set. A family
    # whose `make_block` does not read the field says None.
    remat_lever = "blocks"

    num_actions: int
    use_lstm: bool = False  # accepted for registry uniformity; unused
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 4
    memory_len: int = 64
    dtype: Any = jnp.float32
    mesh: Optional[Any] = None  # sequence-parallel training mesh
    seq_axis: str = "seq"
    ring_schedule: str = "contiguous"  # "contiguous" | "zigzag"
    sp_strategy: str = "ring"  # "ring" | "ulysses" (all-to-all heads)
    batch_axis: Optional[str] = None  # composite (data x seq) mesh: the
    # name of the axis the batch dim shards over (usually "data")
    num_experts: int = 0  # >0 -> MoE FFN in every block
    moe_top_k: int = 2
    moe_mesh: Optional[Any] = None  # mesh with `expert` axis -> EP
    remat: bool = False  # rematerialize each block's backward (save the
    # block input only — trades recompute for activation memory, the
    # lever that fits deep towers / long unrolls in HBM; same policy as
    # models/resnet.py's per-stage remat)
    # Policy-head compute dtype (--precision bf16_train sets bfloat16:
    # the final-LayerNorm output and the policy/baseline projections
    # stay half-width; logits/baseline upcast at the head boundary,
    # models/cores.RecurrentPolicyHead). Closes the "transformer
    # families stay bf16-trunk-only" gap PR 8 logged.
    head_dtype: Any = jnp.float32
    # What the uint8 frame is scaled to for the projection (models/
    # olmoe.py centres it). A float frame is scaled before `Dense_0`; a
    # uint8 one on the chip goes in as its integers, and the range's
    # scale and shift are applied to the [B, T, d] result
    # (`frame_projection`).
    frame_range: Tuple[float, float] = (0.0, 1.0)
    # The projection of reward and last action starts at zero (models/
    # mellum2.py says why); a family's choice, not a flag.
    zero_init_extras: bool = False
    # What the encoder's output (the two projections' sum) is multiplied
    # by before the first block, the observation projection's init
    # divided by it (models/trinity.py: a config's `mup_enabled`); a
    # family's choice, not a flag. At 1 nothing is multiplied and
    # `Dense_0` is made as it always was.
    input_scale: float = 1.0
    # What the head's policy logits are multiplied by (models/cores.py
    # `RecurrentPolicyHead.logits_scale`; models/granite4.py: a config's
    # `logits_scaling`); a family's choice, not a flag.
    logits_scale: float = 1.0

    @nn.compact
    def __call__(self, inputs, core_state, *, sample_action: bool = True):
        frame = inputs["frame"]  # [T, B, ...]
        T, B = frame.shape[:2]

        with device_scope("obs_embed"):
            # Dense_0 [F, d]: the parent's module, so its parameters,
            # their initialisers and their RNG path are what they were.
            scaled_init = (
                {} if self.input_scale == 1.0
                else {"kernel_init": nn.initializers.variance_scaling(
                    self.input_scale ** -2.0, "fan_in", "truncated_normal"
                )}
            )
            project = nn.Dense(self.d_model, dtype=self.dtype, **scaled_init)
            # A uint8 frame enters the projection as the integers it is
            # (`frame_projection`), at the terms the traced precision
            # states; half-width compute reads the kernel in one. Off
            # the chip, for a float frame and at init (which makes the
            # parameters), the float expression stands.
            if (
                frame.dtype == jnp.uint8
                and jax.default_backend() == "tpu"
                and not self.is_initializing()
            ):
                terms = (
                    terms_traced_under() if self.dtype == jnp.float32 else 1
                )
                sow_stat(self, "obs_integer_applications", 1.0, "sum")
                sow_stat(self, "obs_weight_terms", terms, "same")
                weights = project.variables["params"]
                x = frame_projection(
                    frame, weights["kernel"], weights["bias"],
                    self.frame_range, terms,
                )
            else:
                x = scaled_frames(frame, self.frame_range, self.dtype)
                x = project(x).astype(jnp.float32)
                x = x.reshape(T, B, self.d_model).transpose(1, 0, 2)
            # [B, T, d] from here on: the side inputs follow into it.
            one_hot = jax.nn.one_hot(inputs["last_action"].T, self.num_actions)
            reward = jnp.clip(inputs["reward"].astype(jnp.float32), -1, 1).T
            side_init = (
                {"kernel_init": nn.initializers.zeros}
                if self.zero_init_extras else {}
            )
            x = x + nn.Dense(self.d_model, name="extras", **side_init)(
                jnp.concatenate([reward[..., None], one_hot], axis=-1)
            )
            if self.input_scale != 1.0:
                x = x * self.input_scale

        done = inputs["done"]  # [T, B]
        seg = segment_ids_from_done(done).T  # [B, T]

        # Times: in-unroll step j has time j; cache slot m (of M, ordered
        # oldest-first) has time m - M. The STEPWISE semantics (T=1 acting
        # with rolling eviction) are exactly "query t sees times in
        # [t - M, t]" — encoding that as a band mask makes the batch
        # (learner) forward identical to the actor's stepwise forward for
        # ANY T and cache fill level. (Shared with the pipelined family,
        # ops/attention.py.) M is a layer's own (`layer_caches`): one
        # band and one in-unroll mask is built for each M the layers
        # have, not for each layer.
        same = seg[:, :, None] == seg[:, None, :]
        # Cache mask: band + validity + no done up to the query (cache
        # precedes slot 0; any done invalidates it from there on).
        no_done_yet = jnp.cumsum(done.astype(jnp.int32), axis=0).T == 0
        geometry = {}
        for entry in self.layer_caches():
            if entry is None or isinstance(entry, Recurrent):
                continue
            M = entry[0]
            if M not in geometry:
                cache_band, seq_band = band_by_leg(T, M)
                # In-unroll mask: band-causal + same segment. [B, T, T]
                geometry[M] = cache_band, seq_band[None] & same

        # The walk: `block_passes` says which block's weights serve each
        # cache entry, in passes over the stack; the family's last norm
        # follows every pass. A block that serves several entries (a
        # looped family, models/ouro.py) is one module applied again.
        # `core_state` holds one item for every entry that is not None,
        # in order.
        final_norm = self.make_final_norm()
        walk = self.block_passes()
        # What the blocks hand each other: x itself, or the family's
        # residual streams made of it (`into_streams`).
        x = self.into_streams(x)
        entries, carried = iter(self.layer_caches()), iter(core_state)
        shares = iter(self.layer_shares())
        blocks, new_state, handed = {}, [], {}
        for blocks_of_pass in walk:
            with device_scope("loop_pass") if len(walk) > 1 else (
                contextlib.nullcontext()
            ):
                for layer in blocks_of_pass:
                    if layer not in blocks:
                        blocks[layer] = self.make_block(
                            f"block_{layer}", layer
                        )
                    entry = next(entries)
                    # What earlier blocks handed on for this one, by
                    # name (`layer_shares`): nothing in a family whose
                    # layers read x and their own state alone.
                    gives, takes = next(shares)
                    received = {name: handed[name] for name in takes}
                    if entry is None:
                        # A layer that carries nothing (a feed-forward
                        # part alone, models/nemotron3.py).
                        x = blocks[layer](x, **received)
                        continue
                    if isinstance(entry, Recurrent):
                        x, leaves, *given = blocks[layer](
                            x, next(carried), done=done.T, **received
                        )
                        new_state.append(tuple(leaves))
                        handed.update(zip(gives, given, strict=True))
                        continue
                    k_cache, v_cache, valid = next(carried)
                    cache_band, seq_mask = geometry[entry[0]]
                    valid_b = valid.T  # [B, M]
                    cache_mask = (
                        cache_band[None]
                        & valid_b[:, None, :].astype(bool)
                        & no_done_yet[:, :, None]
                    )  # [B, T, M]
                    # The cache goes in as the state has it, [M, B, ...]
                    # (the block contract, `_Block.__call__`): a block
                    # that is rematerialised keeps the state's own
                    # buffer for its backward pass, not a copy.
                    x, k_new, v_new = blocks[layer](
                        x, (k_cache, v_cache), cache_mask, seq_mask,
                        seg=seg, **received,
                    )
                    for name in gives:
                        # What the layer attended over: its cache
                        # BEFORE the roll, this unroll's keys and
                        # values, the two legs' masks.
                        handed[name] = (
                            (k_cache, v_cache), k_new, v_new, cache_mask,
                            seq_mask,
                        )

                    # Roll the cache where it lies: last M of [old
                    # cache; this unroll] on axis 0, validity restricted
                    # to the final segment (ops/attention.py).
                    new_state.append(roll_kv_cache(
                        k_cache, v_cache, valid,
                        k_new.transpose(1, 0, 2, 3),
                        v_new.transpose(1, 0, 2, 3),
                        seg.T, no_done_yet.T, axis=0,
                    ))
                x = final_norm(self.out_of_streams(x))

        core_output = x.transpose(1, 0, 2)  # [T, B, d], the head's layout

        out, _ = RecurrentPolicyHead(
            num_actions=self.num_actions,
            use_lstm=False,
            hidden_size=self.d_model,
            num_layers=1,
            dtype=self.head_dtype,
            logits_scale=self.logits_scale,
            name="head",
        )(core_output, done, (), sample_action)
        return out, tuple(new_state)

    # What a family built on this scaffolding replaces (models/olmoe.py,
    # models/mellum2.py, models/ouro.py, models/kanana2.py): its block,
    # its last norm, each cache entry's shape, which block serves which
    # entry, and (models/xing4.py) what the blocks hand each other:
    # `into_streams` makes it of the projections' [B, T, d] before the
    # first block, `out_of_streams` brings it back to [B, T, d] before
    # the last norm and the head. Everything else — observation and
    # extras projections, masks, cache roll, state convention, head — is
    # this class's.
    @nn.nowrap
    def make_block(self, name: str, layer: int):
        del layer  # every layer is the same block
        block_cls = nn.remat(_Block) if self.remat else _Block
        return block_cls(
            d_model=self.d_model, num_heads=self.num_heads,
            memory_len=self.memory_len, dtype=self.dtype,
            mesh=self.mesh, seq_axis=self.seq_axis,
            ring_schedule=self.ring_schedule,
            sp_strategy=self.sp_strategy,
            batch_axis=self.batch_axis,
            num_experts=self.num_experts,
            moe_top_k=self.moe_top_k,
            moe_mesh=self.moe_mesh,
            name=name,
        )

    @nn.nowrap
    def make_final_norm(self):
        return nn.LayerNorm()

    @nn.nowrap
    def into_streams(self, x):
        """What the first block is handed, of the projections' output x
        [B, T, d]. Here, and in every family with one residual stream,
        x itself; models/xing4.py hands its blocks `hc_mult` streams,
        [n, B, T, d], through `nn.remat` and a T=1 act step alike."""
        return x

    @nn.nowrap
    def out_of_streams(self, x):
        """[B, T, d] for the last norm and the head, of what the last
        block of a pass handed on. A family that overrides this and
        walks in several passes makes its streams again itself."""
        return x

    @nn.nowrap
    def layer_caches(self) -> Tuple[Tuple[int, int, Any], ...]:
        """Each cache entry's (memory_len, key/value heads, head size):
        the shape of the cache's two leaves, [memory_len, B, heads,
        size], and the band attended within. Where the leaves differ
        `size` is the pair of their sizes (models/kanana2.py: a latent
        and a RoPE key, not a key and a value); the walk rolls each
        leaf as it is, and what the two hold is the block's business.
        An entry a layer (`block_passes` says which block serves
        which); here every layer has the one.

        An entry may also be a `Recurrent` (leaves of stated shapes
        that are no window: not rolled, reset by the block where `done`
        says) or None (the layer carries nothing and its block is
        called with x alone): models/nemotron3.py has all three. The
        state holds an item for every entry that is not None."""
        return (
            (self.memory_len, self.num_heads, self.d_model // self.num_heads),
        ) * self.num_layers

    @nn.nowrap
    def block_passes(self) -> Tuple[Tuple[int, ...], ...]:
        """The walk over `layer_caches()`'s entries, as passes over the
        stack: each pass names, in order, the block whose weights serve
        the next entry, and `make_final_norm()`'s norm follows every
        pass. Here one pass, block i on entry i."""
        return (tuple(range(len(self.layer_caches()))),)

    @nn.nowrap
    def layer_shares(self) -> Tuple[Tuple[Tuple[str, ...], ...], ...]:
        """(gives, takes) a `layer_caches()` entry: the names of the
        values the entry's block hands on to the blocks after it, and of
        those it receives from blocks before it as keyword arguments
        (models/phi4flash.py: layers that read one earlier layer's
        state-space output, or attend over one earlier layer's keys and
        values). A `Recurrent` entry's block returns a value a name
        after its leaves; for a window entry that gives (one name) the
        walk hands on what the block attended over, `((k, v) of its
        cache as the state held it, this unroll's k, v, cache_mask,
        seq_mask)`. The values are of this unroll and are never carried:
        the T=1 act step hands them on as an unroll does. Handed through
        the blocks' arguments and results, they are inputs and outputs
        of a rematerialised block and their gradients sum over their
        readers. Here, and in every family but that one, no layer gives
        or takes."""
        return (((), ()),) * len(self.layer_caches())

    def initial_state(self, batch_size: int) -> Tuple:
        def window(M, heads, size):
            first, second = size if isinstance(size, tuple) else (size, size)
            return (
                jnp.zeros((M, batch_size, heads, first), jnp.float32),
                jnp.zeros((M, batch_size, heads, second), jnp.float32),
                jnp.zeros((M, batch_size), jnp.float32),
            )

        def recurrent(leaves):
            return tuple(
                jnp.zeros((shape[0], batch_size) + shape[1:], jnp.float32)
                for shape in leaves
            )

        return tuple(
            recurrent(entry.leaves) if isinstance(entry, Recurrent)
            else window(*entry)
            for entry in self.layer_caches() if entry is not None
        )
