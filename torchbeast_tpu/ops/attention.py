"""Attention ops: dense causal/segment attention and RING attention for
sequence parallelism.

The reference has no attention at all (conv+LSTM nets, SURVEY.md §5.7);
long-context support is a first-class goal of this framework, so the core
op comes with a sequence-parallel formulation from the start:

- `causal_attention`: dense softmax attention with a causal + segment mask
  (segments from episode-boundary `done` flags, so an agent never attends
  across episode resets).
- `ring_attention`: the same computation with the SEQUENCE axis sharded
  over a mesh axis. Each device holds a T/P block of Q/K/V; K/V blocks
  rotate around the ring via `lax.ppermute` while queries stay put, and
  softmax is accumulated online (flash-attention style running max/sum),
  so no device ever materializes the full [T, T] score matrix or the full
  K/V. Communication rides neighbor-to-neighbor ICI links.

Equivalence of the two is pinned by tests/test_attention.py on the 8-device
CPU mesh.

The transformer policies attend over a rolling KV cache and over the
unroll. Three bodies do that on one chip:

- `cached_transformer_attend` (the OLMoE and Ouro blocks): the cache and
  the unroll are TWO LEGS OF ONE SOFTMAX. The cache is read where the
  state holds it ([M, B, Hkv, D]), its scores and the unroll's are never
  joined on the key axis, and no `[cache; k]` / `[cache; v]` is built;
  the cache is an input of its own, so the backward pass does a query's
  work through it and none for its keys and values.
- `latent_cached_attend` (the Kanana-2 block): the same two legs for
  latent attention, whose cache is one compressed latent and one RoPE
  key a slot for all heads: the cache leg in absorbed form (the
  queries carried into the latent's space, the combine lifted out of
  it), the unroll leg on decompressed heads. One function in two
  regimes, chosen by `fused_latent_leg_applies` from the operands'
  shapes and the leg's precision: plain einsums with the leg's f32
  scores [B, H, T, M] in HBM where they are small; where they are
  128 MiB or more and the leg is at one bf16 pass (the Kanana-2
  learner step: 1.36 GB a layer) `ops/fused_attention.fused_latent_
  leg`, all heads of a group against ONE joined key a slot blockwise
  over the slots, forward and backward, whose scores never leave
  VMEM; the two legs are then joined by their log-sum-exps.
- `dense_transformer_attend` on the concatenated `[cache; unroll]`:
  kept for `models/transformer._Block` (learned relative bias), its
  parity with the Ulysses path, `models/transformer_pp.py`, and the
  Mellum2 block (tests/perfbench pins that name on it). One function
  in two regimes, chosen by `fused_pass_applies` from the operands'
  shapes and `rel_bias is None`: the dense body, which builds the f32
  scores [B, Hkv, G, T, M+T], where they are small; where they are
  128 MiB or more (the Mellum2 learner step: 1.385 GB a full layer)
  `ops/fused_attention.fused_attend`, a blockwise pass over the keys
  with a running maximum and denominator, forward and backward, whose
  scores never leave VMEM. Same mathematics and precision in both
  (bfloat16 matmul operands with float32 sums on the chip, float32
  softmax, every admitted key summed); a family with a learned bias
  keeps the dense body whatever its size.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchbeast_tpu.ops.bf16_terms import terms_traced_under
from torchbeast_tpu.ops.fused_attention import (
    BIG_NEG,
    fused_attend,
    fused_latent_leg,
    padded_steps,
)
from torchbeast_tpu.telemetry import device_scope

# f32 score bytes (B x H x T x K x 4) from which `dense_transformer_
# attend` takes the fused pass: see `fused_pass_applies`.
FUSED_SCORE_BYTES = 128 * 2 ** 20


def segment_ids_from_done(done):
    """[T, B] done flags -> [T, B] segment ids (segments start AT a done
    step, matching the models' convention that state resets where done is
    set)."""
    return jnp.cumsum(done.astype(jnp.int32), axis=0)


def causal_attention(q, k, v, segment_ids: Optional[jnp.ndarray] = None):
    """Dense reference implementation.

    q, k, v: [B, T, H, D]; segment_ids: [B, T] (attend only within the
    same segment). Returns [B, T, H, D].
    """
    T = q.shape[1]
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        mask = mask & same[:, None]
    scores = jnp.where(mask, scores, BIG_NEG)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _block_attend(q, k, v, mask, acc, row_max, row_sum, bias=None):
    """One online-softmax accumulation step over a K/V block.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]; mask: [B, Tq, Tk] (True=keep).
    acc: [B, Tq, H, D]; row_max/row_sum: [B, H, Tq].
    bias: optional additive [H, Tq, Tk] (e.g. relative-position bias),
    applied after scaling, before masking — matching the dense order.
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        scores = scores + bias[None]
    scores = jnp.where(mask[:, None], scores, BIG_NEG)

    block_max = scores.max(axis=-1)
    new_max = jnp.maximum(row_max, block_max)
    correction = jnp.exp(row_max - new_max)
    weights = jnp.exp(scores - new_max[..., None])

    acc = acc * correction.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", weights, v
    )
    row_sum = row_sum * correction + weights.sum(axis=-1)
    return acc, new_max, row_sum


def _online_softmax_init(q_blk):
    """(acc, row_max, row_sum) carries for online-softmax accumulation.

    The running max starts WELL ABOVE the mask value: if it started at
    BIG_NEG, a fully-masked first block would give scores==row_max and
    exp(0)=1 weights for masked entries. It is derived from q_blk (not
    jnp.full) so the fori_loop carry is device-varying under shard_map.
    """
    acc = jnp.zeros_like(q_blk)
    zeros_bht = q_blk[..., 0].transpose(0, 2, 1) * 0  # [B, H, Tb]
    return acc, zeros_bht - 1e9, zeros_bht


def _ring_pass(axis, num_blocks, my_idx, q_blk, k_blk, v_blk, seg_blk,
               carry, mask_bias_fn):
    """Rotate K/V (+ their segment ids) around the ring, accumulating the
    online softmax into `carry` = (acc, row_max, row_sum).

    mask_bias_fn(q_pos, k_pos, seg_cur) -> (mask [B?, Tq, Tk], bias or
    None) builds the per-block mask/bias from GLOBAL positions — the only
    part that differs between the ring attention variants.

    NOTE: under the contiguous schedule every device runs all P steps,
    including the ~P/2 blocks its causal mask fully rejects (their
    weights are exact zeros). schedule="zigzag" fixes this for BOTH ring
    ops (measured ~1.8x wall-clock at T=4096 on the 8-way CPU mesh for
    the plain causal op).
    """
    Tb = q_blk.shape[1]
    q_pos = my_idx * Tb + jnp.arange(Tb)

    def body(step, c):
        acc, row_max, row_sum, k_cur, v_cur, seg_cur = c
        kv_idx = (my_idx - step) % num_blocks
        k_pos = kv_idx * Tb + jnp.arange(Tb)
        mask, bias = mask_bias_fn(q_pos, k_pos, seg_cur)
        acc, row_max, row_sum = _block_attend(
            q_blk, k_cur, v_cur, mask, acc, row_max, row_sum, bias=bias
        )
        perm = [(i, (i + 1) % num_blocks) for i in range(num_blocks)]
        return (
            acc, row_max, row_sum,
            jax.lax.ppermute(k_cur, axis, perm),
            jax.lax.ppermute(v_cur, axis, perm),
            jax.lax.ppermute(seg_cur, axis, perm),
        )

    acc, row_max, row_sum, _, _, _ = jax.lax.fori_loop(
        0, num_blocks, body, (*carry, k_blk, v_blk, seg_blk)
    )
    return acc / row_sum.transpose(0, 2, 1)[..., None]


def _zigzag_pass(axis, num_blocks, c, my_idx, q_blk, k_blk, v_blk, seg_blk,
                 accs_e, accs_l, mask_bias_fn):
    """Shared zig-zag scaffold: the step-0 interactions, the
    rotate-then-cond ring loop, and the finalize — used by both the plain
    causal and the transformer variants.

    mask_bias_fn(q_pos, k_pos, seg_q, seg_k) -> (mask [B?, Tq, Tk],
    bias-or-None) builds every computed interaction's mask/bias from
    GLOBAL positions (full-visibility pairs simply get an all-true causal
    term). accs_e/accs_l seed the online softmax for the early/late query
    chunks — e.g. with a cache leg already accumulated.
    """
    e_pos = my_idx * c + jnp.arange(c)
    l_pos = (2 * num_blocks - 1 - my_idx) * c + jnp.arange(c)
    q_e, q_l = q_blk[:, :c], q_blk[:, c:]
    seg_e_q, seg_l_q = seg_blk[:, :c], seg_blk[:, c:]

    def attend_at(accs, q_chunk, q_pos, seg_q, k_chunk, v_chunk, k_pos,
                  seg_k):
        mask, bias = mask_bias_fn(q_pos, k_pos, seg_q, seg_k)
        return _block_attend(q_chunk, k_chunk, v_chunk, mask, *accs,
                             bias=bias)

    # Step 0 (j == i): both diagonal interactions + the always-visible
    # late x early one.
    accs_e = attend_at(accs_e, q_e, e_pos, seg_e_q,
                       k_blk[:, :c], v_blk[:, :c], e_pos, seg_e_q)
    accs_l = attend_at(accs_l, q_l, l_pos, seg_l_q,
                       k_blk[:, c:], v_blk[:, c:], l_pos, seg_l_q)
    accs_l = attend_at(accs_l, q_l, l_pos, seg_l_q,
                       k_blk[:, :c], v_blk[:, :c], e_pos, seg_e_q)

    def body(step, carry):
        accs_e, accs_l, k_cur, v_cur, seg_cur = carry
        # Rotate FIRST: after s rotations we hold device (i-s)'s pair.
        perm_ring = [(a, (a + 1) % num_blocks) for a in range(num_blocks)]
        k_cur = jax.lax.ppermute(k_cur, axis, perm_ring)
        v_cur = jax.lax.ppermute(v_cur, axis, perm_ring)
        seg_cur = jax.lax.ppermute(seg_cur, axis, perm_ring)
        j = (my_idx - step) % num_blocks
        ke_pos = j * c + jnp.arange(c)
        kl_pos = (2 * num_blocks - 1 - j) * c + jnp.arange(c)
        k_e, k_l = k_cur[:, :c], k_cur[:, c:]
        v_e, v_l = v_cur[:, :c], v_cur[:, c:]
        seg_e_k, seg_l_k = seg_cur[:, :c], seg_cur[:, c:]

        # Always: q_late x k_early (early chunks are always before).
        accs_l2 = attend_at(accs_l, q_l, l_pos, seg_l_q,
                            k_e, v_e, ke_pos, seg_e_k)

        # One of the two same-half interactions, chosen by j vs i — the
        # other is structurally invisible and skipped entirely.
        def early_branch(operands):
            accs_e, accs_l, k_e, v_e, k_l, v_l, seg_e_k, seg_l_k = operands
            return (
                attend_at(accs_e, q_e, e_pos, seg_e_q,
                          k_e, v_e, ke_pos, seg_e_k),
                accs_l,
            )

        def late_branch(operands):
            accs_e, accs_l, k_e, v_e, k_l, v_l, seg_e_k, seg_l_k = operands
            return (
                accs_e,
                attend_at(accs_l, q_l, l_pos, seg_l_q,
                          k_l, v_l, kl_pos, seg_l_k),
            )

        accs_e, accs_l2 = jax.lax.cond(
            j < my_idx, early_branch, late_branch,
            (accs_e, accs_l2, k_e, v_e, k_l, v_l, seg_e_k, seg_l_k),
        )
        return accs_e, accs_l2, k_cur, v_cur, seg_cur

    accs_e, accs_l, _, _, _ = jax.lax.fori_loop(
        1, num_blocks, body, (accs_e, accs_l, k_blk, v_blk, seg_blk)
    )

    def finalize(accs):
        acc, _, row_sum = accs
        return acc / row_sum.transpose(0, 2, 1)[..., None]

    return jnp.concatenate([finalize(accs_e), finalize(accs_l)], axis=1)


def zigzag_permutation(t: int, num_blocks: int) -> np.ndarray:
    """Row permutation mapping the contiguous sequence into the zig-zag
    layout: device i holds chunks (i, 2P-1-i) of the 2P chunks. Balances
    causal work: a device owning an early chunk (few visible keys) also
    owns the mirror-image late chunk (many visible keys), so every ring
    step does the same amount of unmasked block work on every device."""
    assert t % (2 * num_blocks) == 0, (t, num_blocks)
    c = t // (2 * num_blocks)
    chunks = np.arange(t).reshape(2 * num_blocks, c)
    order = []
    for i in range(num_blocks):
        order.extend([i, 2 * num_blocks - 1 - i])
    return chunks[order].reshape(-1)


def ring_attention(
    q, k, v, mesh: Mesh, axis: str = "data",
    segment_ids: Optional[jnp.ndarray] = None,
    schedule: str = "contiguous",
):
    """Sequence-parallel causal(+segment) attention.

    q, k, v: [B, T, H, D] GLOBAL arrays sharded along T over `axis` of
    `mesh` (callers place them; see tests). segment_ids: [B, T] sharded
    the same way. Returns [B, T, H, D] with the same sharding.

    schedule:
    - "contiguous": device i holds rows [i*T/P, (i+1)*T/P). Simple, but
      causal masking means device 0 rejects ~all rotated-in K/V blocks
      while device P-1 uses every one — per-step wall-clock is gated by
      the busiest device, so ~2x the necessary block FLOPs are spent.
    - "zigzag": rows are permuted (inside this op — callers still pass
      contiguous-layout arrays) so device i holds chunks (i, 2P-1-i) of
      2P half-sized chunks. Every ring step then computes exactly two
      unmasked chunk interactions per device: the busiest-device FLOPs —
      and so the wall-clock — halve. Requires T % 2P == 0.
    """
    num_blocks = mesh.shape[axis]
    if schedule == "zigzag":
        return _zigzag_ring_attention(q, k, v, mesh, axis, segment_ids)
    if schedule != "contiguous":
        raise ValueError(f"Unknown ring schedule {schedule!r}")

    def local_fn(q_blk, k_blk, v_blk, seg_blk):
        # q_blk: [B, T/P, H, D]; this device holds query block `my_idx`.
        my_idx = jax.lax.axis_index(axis)
        B, Tb = q_blk.shape[0], q_blk.shape[1]

        def mask_bias(q_pos, k_pos, seg_cur):
            causal = q_pos[:, None] >= k_pos[None, :]  # [Tq, Tk] global
            mask = jnp.broadcast_to(causal[None], (B, Tb, Tb))
            if segment_ids is not None:
                # seg_cur: [B, Tk] (travels with k/v); seg_blk: [B, Tq].
                mask = mask & (seg_blk[:, :, None] == seg_cur[:, None, :])
            return mask, None

        return _ring_pass(
            axis, num_blocks, my_idx, q_blk, k_blk, v_blk, seg_blk,
            _online_softmax_init(q_blk), mask_bias,
        )

    from jax import shard_map

    seq = P(None, axis, None, None)
    seg_spec = P(None, axis)
    if segment_ids is None:
        fn = shard_map(
            # Dummy seg ids, unread by mask_bias; derived from q (not
            # jnp.zeros) so they are device-VARYING — ppermute in the ring
            # body outputs varying arrays and the loop carry types must
            # match.
            lambda q_, k_, v_: local_fn(
                q_, k_, v_, (q_[..., 0, 0] * 0).astype(jnp.int32)
            ),
            mesh=mesh,
            in_specs=(seq, seq, seq),
            out_specs=seq,
        )
        return fn(q, k, v)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(seq, seq, seq, seg_spec),
        out_specs=seq,
    )
    return fn(q, k, v, segment_ids)


def _zigzag_ring_attention(q, k, v, mesh, axis, segment_ids):
    """Zig-zag-scheduled causal(+segment) ring attention.

    Layout (handled in here — callers pass contiguous-layout arrays): the
    T axis is split into 2P chunks of c rows; device i holds the pair
    (chunk i, chunk 2P-1-i). Chunk-level causal visibility is then fully
    determined by chunk indices:

      q_early(i) x k_early(j):  visible iff j <= i  (diagonal at j == i)
      q_early(i) x k_late(j):   never (late chunks are always after)
      q_late(i)  x k_early(j):  always (early chunks are always before)
      q_late(i)  x k_late(j):   visible iff j >= i  (diagonal at j == i)

    so every ring step runs exactly TWO unmasked c x c chunk interactions
    per device (one of them chosen by lax.cond on j vs i), instead of the
    contiguous schedule's worst-case four — halving the busiest-device
    FLOPs that gate each synchronized ring step. Step 0 (j == i) runs the
    two diagonal interactions plus the always-visible late x early one.

    Segment (episode-boundary) masks still apply inside every computed
    interaction; "never visible" pairs are skipped structurally.
    """
    num_blocks = mesh.shape[axis]
    B, T, H, D = q.shape
    if T % (2 * num_blocks) != 0:
        raise ValueError(
            f"zigzag schedule needs T ({T}) divisible by 2P "
            f"({2 * num_blocks})"
        )
    c = T // (2 * num_blocks)
    perm = zigzag_permutation(T, num_blocks)
    inv_perm = np.argsort(perm)

    if segment_ids is None:
        segment_ids = jnp.zeros((B, T), jnp.int32)
    # Keep the permuted arrays T-sharded: without the constraints GSPMD
    # implements the gather by all-gathering the full sequence onto every
    # device — exactly the memory blowup ring attention exists to avoid.
    # Each device's zigzag block draws from two source devices, so the
    # constrained gather lowers to neighbor exchanges instead.
    seq_sh = NamedSharding(mesh, P(None, axis, None, None))
    seg_sh = NamedSharding(mesh, P(None, axis))
    constrain = jax.lax.with_sharding_constraint
    qz = constrain(jnp.take(q, perm, axis=1), seq_sh)
    kz = constrain(jnp.take(k, perm, axis=1), seq_sh)
    vz = constrain(jnp.take(v, perm, axis=1), seq_sh)
    segz = constrain(jnp.take(segment_ids, perm, axis=1), seg_sh)

    def local_fn(q_blk, k_blk, v_blk, seg_blk):
        my_idx = jax.lax.axis_index(axis)
        q_e, q_l = q_blk[:, :c], q_blk[:, c:]

        def mask_bias(q_pos, k_pos, seg_q, seg_k):
            causal = q_pos[:, None] >= k_pos[None, :]
            mask = causal[None] & (seg_q[:, :, None] == seg_k[:, None, :])
            return mask, None

        return _zigzag_pass(
            axis, num_blocks, c, my_idx, q_blk, k_blk, v_blk, seg_blk,
            _online_softmax_init(q_e), _online_softmax_init(q_l),
            mask_bias,
        )

    from jax import shard_map

    seq = P(None, axis, None, None)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(seq, seq, seq, P(None, axis)),
        out_specs=seq,
    )
    out_z = fn(qz, kz, vz, segz)
    return constrain(jnp.take(out_z, inv_perm, axis=1), seq_sh)


def ring_transformer_attention(
    q, k, v, cache_k, cache_v, cache_mask, rel_bias, memory_len: int,
    segment_ids, mesh: Mesh, axis: str = "seq",
    schedule: str = "contiguous", batch_axis: Optional[str] = None,
):
    """Sequence-parallel version of the transformer policy's in-unroll
    attention (models/transformer.py _Block): band-causal windowing to the
    last `memory_len` steps, segment masking, learned relative-position
    bias, AND attention into the rolling KV cache — softmax-merged online
    so the numerics match the dense path exactly (pinned by
    tests/test_transformer.py::test_ring_path_matches_dense_* ).

    The unroll axis T is sharded over `axis`; each device's query block
    first attends the (replicated, M-entry) cache locally, then in-unroll
    K/V blocks rotate around the ring via ppermute. The cache leg needs no
    communication because M << T and every query may need any slot.

    q, k, v:      [B, T, H, D] global, sharded along T.
    cache_k/v:    [B, M, H, D] replicated.
    cache_mask:   [B, T, M] bool — band+validity+no-done, exactly the
                  dense model's cache mask (sharded along T).
    rel_bias:     [H, M+1] learned bias over offsets 0..M.
    segment_ids:  [B, T] int, sharded along T.
    Returns [B, T, H, D], sharded along T.

    schedule: "contiguous" or "zigzag" (see ring_attention — same ~2x
    busiest-device FLOP saving, with the band/bias/cache semantics kept).
    """
    num_blocks = mesh.shape[axis]
    M = memory_len
    if schedule == "zigzag":
        return _zigzag_transformer_ring(
            q, k, v, cache_k, cache_v, cache_mask, rel_bias, M,
            segment_ids, mesh, axis, batch_axis,
        )
    if schedule != "contiguous":
        raise ValueError(f"Unknown ring schedule {schedule!r}")

    def local_fn(q_blk, k_blk, v_blk, seg_blk, c_k, c_v, c_mask, bias_tbl):
        my_idx = jax.lax.axis_index(axis)
        Tb = q_blk.shape[1]
        q_pos = my_idx * Tb + jnp.arange(Tb)

        # Cache leg (local): slot m has global time m - M, so the offset
        # of query t to slot m is t + M - m; the band/validity are already
        # folded into c_mask by the caller.
        cache_offsets = q_pos[:, None] + M - jnp.arange(M)[None, :]
        cache_bias = bias_tbl[:, jnp.clip(cache_offsets, 0, M)]
        carry = _block_attend(
            q_blk, c_k, c_v, c_mask, *_online_softmax_init(q_blk),
            bias=cache_bias,
        )

        def mask_bias(q_pos, k_pos, seg_cur):
            offsets = q_pos[:, None] - k_pos[None, :]  # [Tq, Tk] global
            band = (offsets >= 0) & (offsets <= M)
            same = seg_blk[:, :, None] == seg_cur[:, None, :]
            return band[None] & same, bias_tbl[:, jnp.clip(offsets, 0, M)]

        return _ring_pass(
            axis, num_blocks, my_idx, q_blk, k_blk, v_blk, seg_blk,
            carry, mask_bias,
        )

    from jax import shard_map

    # batch_axis: on a composite (data x seq) mesh, the batch dim shards
    # over `data` — each data row runs its own independent seq ring (the
    # per-device math only indexes the seq axis, so it is unchanged).
    ba = batch_axis
    seq = P(ba, axis, None, None)
    cache4 = P(ba, None, None, None)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            seq, seq, seq, P(ba, axis), cache4, cache4,
            P(ba, axis, None), P(None, None),
        ),
        out_specs=seq,
    )
    return fn(q, k, v, segment_ids, cache_k, cache_v, cache_mask, rel_bias)


def _zigzag_transformer_ring(q, k, v, cache_k, cache_v, cache_mask,
                             rel_bias, memory_len, segment_ids, mesh, axis,
                             batch_axis=None):
    """Zig-zag-scheduled transformer ring attention.

    Same chunk-pair layout and structural skipping as
    _zigzag_ring_attention (device i holds chunks (i, 2P-1-i); two
    computed interactions per ring step chosen by lax.cond), with the
    transformer semantics layered on: every computed interaction applies
    the band + segment mask and the relative-position bias from GLOBAL
    positions, and each device's two query chunks attend the replicated
    cache locally first. The band can mask additional distant pairs
    beyond causality; those are where'd out rather than skipped
    structurally (at RL scale the band spans most of the unroll).
    """
    num_blocks = mesh.shape[axis]
    M = memory_len
    B, T, H, D = q.shape
    if T % (2 * num_blocks) != 0:
        raise ValueError(
            f"zigzag schedule needs T ({T}) divisible by 2P "
            f"({2 * num_blocks})"
        )
    c = T // (2 * num_blocks)
    perm = zigzag_permutation(T, num_blocks)
    inv_perm = np.argsort(perm)

    ba = batch_axis
    seq_sh = NamedSharding(mesh, P(ba, axis, None, None))
    seg_sh = NamedSharding(mesh, P(ba, axis))
    cm_sh = NamedSharding(mesh, P(ba, axis, None))
    constrain = jax.lax.with_sharding_constraint
    qz = constrain(jnp.take(q, perm, axis=1), seq_sh)
    kz = constrain(jnp.take(k, perm, axis=1), seq_sh)
    vz = constrain(jnp.take(v, perm, axis=1), seq_sh)
    segz = constrain(jnp.take(segment_ids, perm, axis=1), seg_sh)
    cmz = constrain(jnp.take(cache_mask, perm, axis=1), cm_sh)

    def local_fn(q_blk, k_blk, v_blk, seg_blk, cm_blk, c_k, c_v, bias_tbl):
        my_idx = jax.lax.axis_index(axis)
        e_pos = my_idx * c + jnp.arange(c)
        l_pos = (2 * num_blocks - 1 - my_idx) * c + jnp.arange(c)
        q_e, q_l = q_blk[:, :c], q_blk[:, c:]

        def band_seg_bias(q_pos, k_pos, seg_q, seg_k):
            offsets = q_pos[:, None] - k_pos[None, :]
            band = (offsets >= 0) & (offsets <= M)
            mask = band[None] & (seg_q[:, :, None] == seg_k[:, None, :])
            return mask, bias_tbl[:, jnp.clip(offsets, 0, M)]

        def cache_leg(q_chunk, q_pos, cm_chunk):
            offs = q_pos[:, None] + M - jnp.arange(M)[None, :]
            bias = bias_tbl[:, jnp.clip(offs, 0, M)]
            return _block_attend(
                q_chunk, c_k, c_v, cm_chunk,
                *_online_softmax_init(q_chunk), bias=bias,
            )

        return _zigzag_pass(
            axis, num_blocks, c, my_idx, q_blk, k_blk, v_blk, seg_blk,
            cache_leg(q_e, e_pos, cm_blk[:, :c]),
            cache_leg(q_l, l_pos, cm_blk[:, c:]),
            band_seg_bias,
        )

    from jax import shard_map

    seq = P(ba, axis, None, None)
    cache4 = P(ba, None, None, None)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            seq, seq, seq, P(ba, axis), P(ba, axis, None),
            cache4, cache4, P(None, None),
        ),
        out_specs=seq,
    )
    out_z = fn(qz, kz, vz, segz, cmz, cache_k, cache_v, rel_bias)
    return constrain(jnp.take(out_z, inv_perm, axis=1), seq_sh)


def band_by_leg(T: int, M: int):
    """The transformer families' windowed-causal time geometry, a leg
    at a time: (cache_band [T, M], seq_band [T, T]) bool.

    Cache slot m (of M, oldest-first) has time m - M; in-unroll step j
    has time j; query t may attend to times in [t - M, t]. Slot m lies
    t + M - m >= 1 steps back, so it is in the band iff m >= t.
    """
    q_time = jnp.arange(T)
    cache_band = jnp.arange(M)[None, :] >= q_time[:, None]
    back = q_time[:, None] - q_time[None, :]
    return cache_band, (back >= 0) & (back <= M)


def band_relative_offsets(T: int, M: int):
    """(band, offsets) over the combined [cache; unroll] key axis —
    `band_by_leg`'s two bands side by side, for the bodies that attend
    over the concatenation (models/transformer.py `_Block`,
    models/transformer_pp.py), and the offsets their learned relative
    bias is indexed by. Returns band [T, M+T] bool and offsets
    [T, M+T] int clipped to [0, M].
    """
    key_time = jnp.concatenate([jnp.arange(M) - M, jnp.arange(T)])
    offsets = jnp.arange(T)[:, None] - key_time[None, :]  # [T, M+T]
    band = jnp.concatenate(band_by_leg(T, M), axis=-1)
    return band, jnp.clip(offsets, 0, M)


def roll_kv_cache(k_cache, v_cache, valid, k_new, v_new, seg, no_done,
                  axis: int = 1):
    """Roll a per-layer KV cache across an unroll: keep the last M of
    [old cache; this unroll], with validity restricted to the FINAL
    segment (an episode boundary inside the unroll evicts everything
    before it). Shared by both transformer families — see
    band_relative_offsets.

    `axis` is where every argument has its time (slot or step), the
    batch on the other of the first two: 1 for the batch-first layout
    (k_cache/v_cache [B, M, H, hd]; valid [B, M]; k_new/v_new
    [B, T, H, hd]; seg/no_done [B, T]: models/transformer_pp.py's
    carry), 0 for the state's own ([M, B, H, hd], [M, B], [T, B, H, hd],
    [T, B]: models/transformer.py rolls the state where it lies, a slice
    and a concatenation, no transposed copy of the cache in or out).
    Returns (k, v, valid_f32) in the layout given.
    """
    M, T = k_cache.shape[axis], k_new.shape[axis]

    def last(x, n):
        size = x.shape[axis]
        return jax.lax.slice_in_dim(x, size - n, size, axis=axis)

    def rolled(old, new):
        # The last M of [old; new], with nothing of M + T built: what
        # the unroll leaves of the old slots, then the unroll's last.
        kept = max(M - T, 0)
        return jnp.concatenate(
            [last(old, kept), last(new, M - kept)], axis=axis
        )

    seq_valid = seg == last(seg, 1)
    old_valid = valid.astype(bool) & last(no_done, 1)
    return (
        rolled(k_cache, k_new),
        rolled(v_cache, v_new),
        rolled(old_valid, seq_valid).astype(jnp.float32),
    )


@jax.custom_jvp
def _queried_first(q, slot_times):
    """The identity, with the cache slots' times tied to the queries.
    The cache is there from the program's start, and so is whatever is
    computed from it alone: left to itself the compiler rotates the
    cached keys of many layers at once, ahead of their use, and keeps
    the results (0.73 GiB more at the Ouro cell's sizes). With the
    times behind this barrier a layer's keys are rotated when the
    layer's queries are there. The times, not the cache: a barrier on
    an argument of the program costs a copy of it."""
    return jax.lax.optimization_barrier((q, slot_times))


# The barrier's own rule makes a tangent of zeros for what has none;
# the identity's is the identity.
_queried_first.defjvp(
    lambda primals, tangents: (_queried_first(*primals), tangents),
    symbolic_zeros=True,
)


def cached_transformer_attend(q, k, v, cache_k, cache_v, cache_mask,
                              seq_mask, place_cache_keys=None):
    """Attention over a rolling cache and over the unroll as two legs
    of one softmax, the cache read where the state holds it.

    q: [B, T, H, D]; k, v: [B, T, Hkv, D], this unroll's; cache_k,
    cache_v: [M, B, Hkv, D], THE STATE'S LAYOUT; cache_mask [B, T, M] and
    seq_mask [B, T, T] bool, as models/transformer.py builds them apart.
    q and k come with their positions applied; `place_cache_keys`, if
    given, applies the cache's: called with cache_k and the slots'
    times [M] (slot m is at m - M), it returns cache_k placed, where it
    lies (RoPE: the state holds un-rotated keys). Returns [B, T, H, D].

    What `dense_transformer_attend` computes on `[cache; k]`, `[cache;
    v]` and `[cache_mask, seq_mask]`, in another order of summation:
    scores of each leg in f32, masked with BIG_NEG; one maximum and one
    denominator over both legs; the two combines added and divided by
    the denominator on the [B, T, H, D] output. Every query sees its own
    step, so the denominator is positive whatever the cache's validity,
    and the program is the same for a full cache and an empty one.
    Nothing of M + T keys is built, forward or backward, and the cache
    takes part as plain inputs of autodiff: asked for, its gradient is
    the dense path's; not asked for (the learner's case: the cache is
    data), only dq is computed through the cache leg.

    Hkv may be a divisor of H: query head j reads key/value head
    j // (H // Hkv), contracted by group as in `dense_transformer_attend`.
    """
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(
            f"{H} query heads do not divide over {Hkv} key/value heads"
        )
    scale = D ** -0.5
    q = q.reshape(B, T, Hkv, H // Hkv, D)
    if place_cache_keys is not None:
        # Slot m of M is at time m - M (`band_by_leg`).
        M = cache_k.shape[0]
        q, slot_times = _queried_first(q, jnp.arange(M) - M)
        cache_k = place_cache_keys(cache_k, slot_times)

    def scores(spec, keys, mask):
        s = jnp.einsum(spec, q, keys).astype(jnp.float32) * scale
        return jnp.where(mask[:, None, None], s, BIG_NEG)

    with device_scope("cache_leg"):
        s_c = scores("bqhgd,mbhd->bhgqm", cache_k, cache_mask)
    with device_scope("unroll_leg"):
        s_u = scores("bqhgd,bkhd->bhgqk", k, seq_mask)
    # As jax.nn.softmax: no gradient through the maximum.
    top = jax.lax.stop_gradient(
        jnp.maximum(s_c.max(axis=-1), s_u.max(axis=-1))
    )[..., None]
    with device_scope("cache_leg"):
        p_c = jnp.exp(s_c - top)
        out_c = jnp.einsum(
            "bhgqm,mbhd->bqhgd", p_c.astype(cache_v.dtype), cache_v
        )
    with device_scope("unroll_leg"):
        p_u = jnp.exp(s_u - top)
        out_u = jnp.einsum("bhgqk,bkhd->bqhgd", p_u.astype(v.dtype), v)
    den = p_c.sum(axis=-1) + p_u.sum(axis=-1)  # [B, Hkv, G, T]
    out = (out_c + out_u) / den.transpose(0, 3, 1, 2)[..., None].astype(
        out_u.dtype
    )
    return out.reshape(B, T, H, D)


def latent_cached_attend(q_nope, q_rope, k_nope, k_rope, v, cache_latent,
                         cache_rope, w_uk, w_uv, cache_mask, seq_mask,
                         place_cache_keys=None, cache_precision=None):
    """`cached_transformer_attend` for latent attention (MLA, models/
    kanana2.py): the cache holds, for all heads together, a compressed
    latent and one un-rotated RoPE key a slot, and its leg is computed
    in ABSORBED form, with nothing decompressed.

    q_nope, k_nope: [B, T, H, Dn]; q_rope [B, T, H, Dr] and k_rope
    [B, T, 1, Dr] (one key for every head), both with their positions
    applied; v [B, T, H, Dv]: this unroll's, decompressed. cache_latent
    [M, B, 1, C] and cache_rope [M, B, 1, Dr], THE STATE'S LAYOUT;
    w_uk [C, H, Dn] and w_uv [C, H, Dv], the two halves of the
    decompression (a cached key's content part is latent @ w_uk[:, h],
    its value latent @ w_uv[:, h]); the masks and `place_cache_keys`
    (applied to cache_rope) as in `cached_transformer_attend`. Returns
    [B, T, H, Dv].

    The cache leg: q_nope . (latent @ w_uk) = (q_nope @ w_uk^T) .
    latent, so the H query heads, carried into the latent's space once
    (`latent_absorb`), all score against ONE key of C + Dr a slot; the
    weights combine the latents themselves, and what comes out is
    lifted to Dv a head by w_uv (`latent_lift`, linear, so before the
    division by the denominator). The unroll leg is the plain one on
    the T decompressed keys and values. One maximum and one denominator
    over both legs, scores in f32 and scaled by (Dn + Dr)^-0.5: what
    dense attention over `[cache; unroll]` decompressed computes, in
    another order (tests/test_kanana2.py). Nothing of [M, H, Dn + Dv]
    is built, forward or backward. THE CACHE IS DATA: cache_latent and
    cache_rope take no gradient (zeros, in both regimes below; no
    caller asks, and the fused leg makes none); w_uk and w_uv take
    theirs through both legs.

    `cache_precision`, if given, is the matmul precision of the cache
    leg's two products over the M slots (and of their gradients'),
    whatever `jax.default_matmul_precision` the caller traces under:
    they are most of the step's operations at a long cache, and their
    sums run over keys, where rounding averages out.

    One function, two regimes, chosen by `fused_latent_leg_applies` (no
    flag). Below 128 MiB of f32 scores in the leg (acting, toy widths),
    or with the leg above one bf16 pass: the einsums below, the scores
    [B, H, T, M] left to XLA. At or over it (the learner's step at the
    published widths): `ops/fused_attention.fused_latent_leg`, which
    returns the leg's output normalised within the leg and the rows'
    log-sum-exp, and the two legs are joined by `top = max(lse_c, max
    s_u)`, `den = exp(lse_c - top) + sum exp(s_u - top)`, `out =
    (exp(lse_c - top) lift(out_c) + p_u v) / den`: the same sums in
    another order, the same precision (bf16 operands, f32 sums and
    softmax on the chip), every admitted slot summed and no block
    skipped on the mask. There the queries are made head-major with
    their steps padded to whole sublane tiles BEFORE the absorb, so
    that the absorb writes what the kernel reads and the lift reads
    what it writes. A row that admits no slot weighs the leg by
    exp(BIG_NEG - top) = 0: the unroll leg alone, finite gradients.
    """
    M = cache_latent.shape[0]
    scale = (q_nope.shape[-1] + q_rope.shape[-1]) ** -0.5
    # The cache is data, in both regimes.
    cache_latent = jax.lax.stop_gradient(cache_latent)
    cache_rope = jax.lax.stop_gradient(cache_rope)
    fused = fused_latent_leg_applies(
        q_rope.shape, M, cache_latent.shape[-1], cache_precision
    )

    def placed(queries):
        # As in `cached_transformer_attend`: the joined key of a layer
        # is built when the layer's queries are there, not for every
        # layer at the program's start.
        # The cache follows, one key a slot for all heads: [M, B, .].
        rope = cache_rope
        if place_cache_keys is not None:
            queries, slot_times = _queried_first(queries, jnp.arange(M) - M)
            rope = place_cache_keys(rope, slot_times)
        return queries, cache_latent[:, :, 0], rope[:, :, 0]

    def masked(s, mask):
        return jnp.where(mask[:, None], s.astype(jnp.float32) * scale, BIG_NEG)

    def unroll_scores():
        with device_scope("unroll_leg"):
            return masked(
                jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
                + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0]),
                seq_mask,
            )

    if fused:
        # The queries head-major ([H, B, Tp, .]: where a matmul
        # batched over heads writes them), their steps padded to whole
        # sublane tiles where they are still 128 and 64 wide: the
        # absorb's output is then what the kernel reads, its output
        # what the lift reads, and nothing latent-sized is relaid.
        T = q_nope.shape[1]
        steps = ((0, 0), (0, padded_steps(T) - T), (0, 0), (0, 0))
        q_nope, latent, cache_rope = placed(q_nope)
        with device_scope("latent_absorb"):
            q_latent = jnp.einsum(
                "bqhd,chd->hbqc", jnp.pad(q_nope, steps), w_uk
            )
        with device_scope("cache_leg"):
            out_c, lse_c = fused_latent_leg(
                q_latent, jnp.pad(q_rope, steps).transpose(2, 0, 1, 3),
                latent, cache_rope, cache_mask, scale,
            )
            lse_c = lse_c[:, :, :T].transpose(1, 0, 2)  # [B, H, T]
        s_u = unroll_scores()
        top = jax.lax.stop_gradient(jnp.maximum(lse_c, s_u.max(axis=-1)))
        # The leg's share of the one denominator: its own, rescaled.
        den_c = jnp.exp(lse_c - top)
        with device_scope("latent_lift"):
            out_c = jnp.einsum(
                "hbqc,chd->hbqd", out_c.astype(w_uv.dtype), w_uv
            )[:, :, :T].transpose(1, 2, 0, 3)
        out_c = out_c * den_c.transpose(0, 2, 1)[..., None].astype(
            out_c.dtype
        )
        top = top[..., None]
    else:
        with device_scope("latent_absorb"):
            q_cache = jnp.concatenate(
                [jnp.einsum("bqhd,chd->bqhc", q_nope, w_uk), q_rope],
                axis=-1,
            )
        q_cache, latent, cache_rope = placed(q_cache)
        with device_scope("cache_leg"):
            s_c = masked(
                jnp.einsum(
                    "bqhc,mbc->bhqm", q_cache,
                    jnp.concatenate([latent, cache_rope], axis=-1),
                    precision=cache_precision,
                ),
                cache_mask,
            )
        s_u = unroll_scores()
        top = jax.lax.stop_gradient(
            jnp.maximum(s_c.max(axis=-1), s_u.max(axis=-1))
        )[..., None]
        with device_scope("cache_leg"):
            p_c = jnp.exp(s_c - top)
            out_c = jnp.einsum(
                "bhqm,mbc->bqhc", p_c.astype(latent.dtype), latent,
                precision=cache_precision,
            )
        with device_scope("latent_lift"):
            out_c = jnp.einsum("bqhc,chd->bqhd", out_c, w_uv)
    with device_scope("unroll_leg"):
        p_u = jnp.exp(s_u - top)
        out_u = jnp.einsum("bhqk,bkhd->bqhd", p_u.astype(v.dtype), v)
    # [B, H, T]; the XLA body's sum where it always stood in the program.
    den = (den_c if fused else p_c.sum(axis=-1)) + p_u.sum(axis=-1)
    return (out_c + out_u) / den.transpose(0, 2, 1)[..., None].astype(
        out_u.dtype
    )


def _one_bf16_pass(precision) -> bool:
    """Whether a float32 matmul traced now at `precision` (None: as the
    caller traces) is one bfloat16 pass on the chip."""
    if precision is None:
        precision = jax.config.jax_default_matmul_precision
    try:
        return jax.lax.Precision(precision) == jax.lax.Precision.DEFAULT
    except ValueError:  # an algorithm's name, a pair: not read here
        return False


def fused_latent_leg_applies(q_shape, num_slots, latent_rank,
                             cache_precision) -> bool:
    """Whether `latent_cached_attend` computes its cache leg by `ops/
    fused_attention.fused_latent_leg` for absorbed queries [B, T, H, *]
    against `num_slots` cached slots: f32 scores of the leg of `FUSED_
    SCORE_BYTES` (128 MiB) or more, a latent whose width is whole lane
    tiles (the combine reads the joined key's first `latent_rank`
    columns), and the leg's two products at one bfloat16 pass, which is
    what the kernels compute: a `cache_precision` of `high` / `highest`,
    or None under a caller that traces so, keeps the XLA body. The same
    kind of rule as `fused_pass_applies`, and for its reasons: the body
    asks it, and a block asks it to count what it compiled in
    (`attention_latent_fused_applications`). Kanana-2's learner step is
    above it (32 x 32 x 81 x 4,095 x 4 = 1,359 MB a layer); acting at
    T=1 (16.8 MB), tier-1's toy widths and tests/perfbench's tiny cell
    are below."""
    B, T, H = q_shape[:3]
    return (
        latent_rank % 128 == 0
        and B * H * T * num_slots * 4 >= FUSED_SCORE_BYTES
        and _one_bf16_pass(cache_precision)
    )


def fused_pass_applies(q_shape, k_shape, rel_bias) -> bool:
    """Whether `dense_transformer_attend` takes the fused pass for q
    [B, T, H, D] and k_all [B, K, Hkv, D]: no learned bias, a head size
    that fills the 128 lanes (the kernels read a head's keys as a
    column block of [K, B * Hkv * D]) or is half of them (models/
    lfm2.py: 64, which `fused_attend` pads with zero columns), and f32
    scores of `FUSED_SCORE_
    BYTES` (128 MiB) or more. A function of the shapes and of `rel_bias
    is None` alone: the body asks it, and a block asks it to count what
    it compiled in (`attention_fused_applications`, models/
    transformer.py `count_fused_application`).

    Kanana-2's heads are not these (192-wide unroll keys, one 576-wide
    cache key for all heads): its cache leg has a rule and a pass of
    its own, `fused_latent_leg_applies`.

    Why 128 MiB: it lies between what was measured to gain and what
    nothing measures. At the Mellum2 cell's sizes (366 MB a window
    layer, 1,385 MB the full one) XLA's passes over the scores cost
    10-45 ms a step and the fused pass a quarter of that (PERF.md
    section 6, PR 37). Below it are acting at T=1 (one query row padded
    to a tile of 8, 34 MB against a full cache), the toy families
    (which on the CPU would run through the Pallas interpreter) and
    OLMoE- and Ouro-sized problems (35 and 56 MB; equal heads, so 81
    rows a matmul where Mellum2's groups give 648; they attend through
    `cached_transformer_attend` anyway): none has been timed on the
    chip through the fused pass in its own program."""
    B, T, H, D = q_shape
    return (
        rel_bias is None
        and (D % 128 == 0 or D == 64)
        and B * H * T * k_shape[1] * 4 >= FUSED_SCORE_BYTES
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _no_gradient_before(keys, count):
    """keys [B, K, ...] as they are, with zeros for a gradient in the
    first `count`."""
    return keys


def _no_gradient_before_bwd(count, _, grad):
    takes = jnp.arange(grad.shape[1]) >= count
    return (jnp.where(takes[None, :, None, None], grad, 0),)


_no_gradient_before.defvjp(
    lambda keys, count: (keys, None), _no_gradient_before_bwd
)


def dense_transformer_attend(q, k_all, v_all, mask, offsets, rel_bias,
                             no_grad_keys=0, scale=None):
    """The transformer policy's attention body over `[cache; unroll]` —
    ONE implementation shared by the model's dense branch
    (models/transformer.py _Block) and the Ulysses path below (which is
    exactly this on a head slice), so the two can never drift apart
    numerically; models/transformer_pp.py and models/mellum2.py call it
    too. The OLMoE and Ouro blocks do not: `cached_transformer_attend`
    computes the same over the two legs apart.

    q: [B, T, H, D]; k_all/v_all: [B, M+T, Hkv, D] (cache prepended);
    mask: [B, T, M+T] bool; offsets: [T, M+T] int in [0, M];
    rel_bias: [H, M+1], or None for a family whose positions enter
    elsewhere (RoPE, models/olmoe.py). Scores and softmax run in f32;
    the combine runs in v's dtype: softmax(mask(q k^T * scale + bias))
    v, where `scale` (a Python float, static) is D^-0.5 when None, the
    default, and otherwise what the family's config states (models/
    granite4.py: `attention_multiplier`), in both regimes below.

    Hkv may be a divisor of H (grouped-query heads, models/mellum2.py):
    query head j reads key/value head j // (H // Hkv). The queries are
    then contracted by group, [B, T, Hkv, G, D], and K and V are never
    repeated.

    One function, two regimes, chosen by `fused_pass_applies` (no
    flag): the dense body below, which builds the f32 scores
    [B, Hkv, G, T, M+T] and leaves the rest to XLA, or `ops/
    fused_attention.fused_attend`, the same blockwise over the keys
    with the scores in VMEM, forward and backward. The precision is the
    same in both: matmul operands bfloat16 with float32 sums on the
    chip (XLA's default for a float32 einsum), scores, mask, maximum,
    exponent and denominator float32, the weights cast to v's type for
    the combine; only the order of summation over keys differs. Under
    a caller that traces at `high` or `highest` the dense body's
    einsums follow, three or six passes over bfloat16 terms of the
    float32 operands, and so does the fused pass, which is handed the
    number of terms the trace states (`bf16_terms.terms_traced_under`:
    2, 3) and makes the same passes from float32 tiles cut in VMEM
    (models/nemotron3.py, models/qwen3next.py, models/lfm2.py). Every
    query must admit a key.

    `no_grad_keys` (a Python int; the Mellum2 block passes its cache's
    length) says that the first so many keys of k_all and v_all are
    data: their part of the two gradients is zeros in both regimes, and
    the fused pass does no work for it (most of its backward pass's
    `dk`, `dv` when the cache is 4,095 of 4,176 keys).
    """
    B, T, H, D = q.shape
    Hkv = k_all.shape[2]
    if H % Hkv:
        raise ValueError(
            f"{H} query heads do not divide over {Hkv} key/value heads"
        )
    if fused_pass_applies(q.shape, k_all.shape, rel_bias):
        return fused_attend(
            q, k_all, v_all, mask, no_grad_keys, terms=terms_traced_under(),
            scale=scale,
        )
    if no_grad_keys:
        k_all = _no_gradient_before(k_all, no_grad_keys)
        v_all = _no_gradient_before(v_all, no_grad_keys)
    scale = D ** -0.5 if scale is None else float(scale)
    if Hkv == H:
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", q, k_all).astype(jnp.float32)
            * scale
        )
        if rel_bias is not None:
            scores = scores + rel_bias[:, offsets][None]
        scores = jnp.where(mask[:, None], scores, BIG_NEG)
        weights = jax.nn.softmax(scores, axis=-1).astype(v_all.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v_all)
    G = H // Hkv
    scores = (
        jnp.einsum(
            "bqhgd,bkhd->bhgqk", q.reshape(B, T, Hkv, G, D), k_all
        ).astype(jnp.float32)
        * scale
    )
    if rel_bias is not None:
        scores = scores + rel_bias[:, offsets].reshape(
            (1, Hkv, G) + offsets.shape
        )
    scores = jnp.where(mask[:, None, None], scores, BIG_NEG)
    weights = jax.nn.softmax(scores, axis=-1).astype(v_all.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", weights, v_all).reshape(
        B, T, H, D
    )


def ulysses_attention(
    q, k, v, mesh: Mesh, axis: str = "seq", segment_ids=None
):
    """All-to-all (DeepSpeed-Ulysses style) sequence-parallel causal
    attention — the second canonical long-context strategy next to
    `ring_attention`, with a different communication shape: instead of
    rotating K/V blocks P times around the ring, TWO all-to-alls per call
    re-shard the tensors from sequence-sharded to HEAD-sharded and back.
    Each device then holds the FULL sequence for H/P heads and runs plain
    dense attention locally — exact numerics, no online-softmax merging.

    Trade-off vs ring: all-to-all moves the same O(T·H·D/P) bytes but in
    one collective (latency-bound on small T, bandwidth-friendly on large
    T), and peak memory holds the full [T, T] score matrix for H/P heads
    — so ring wins when T is huge, Ulysses when H is plentiful and T
    moderate. Requires H divisible by the axis size (heads are the
    sharded resource); T divisible by it as well (the input layout).

    q, k, v: [B, T, H, D] global, sharded along T. segment_ids: [B, T].
    Returns [B, T, H, D], sharded along T.
    """
    from jax import shard_map

    num_blocks = mesh.shape[axis]
    B, T, H, D = q.shape
    if T % num_blocks != 0:
        raise ValueError(
            f"ulysses needs T ({T}) divisible by the axis size "
            f"({num_blocks})"
        )
    if H % num_blocks != 0:
        raise ValueError(
            f"ulysses needs H ({H}) divisible by the axis size "
            f"({num_blocks}) — heads are the sharded resource"
        )

    def local_fn(q_blk, k_blk, v_blk, seg):
        # [B, T/P, H, D] -> [B, T, H/P, D]: split heads, gather sequence.
        a2a = functools.partial(
            jax.lax.all_to_all, axis_name=axis, split_axis=2,
            concat_axis=1, tiled=True,
        )
        qh, kh, vh = a2a(q_blk), a2a(k_blk), a2a(v_blk)
        out = causal_attention(qh, kh, vh, seg)
        # [B, T, H/P, D] -> [B, T/P, H, D]: split sequence, gather heads.
        return jax.lax.all_to_all(
            out, axis_name=axis, split_axis=1, concat_axis=2, tiled=True
        )

    seq = P(None, axis, None, None)
    if segment_ids is None:
        fn = shard_map(
            lambda q_, k_, v_: local_fn(q_, k_, v_, None),
            mesh=mesh,
            in_specs=(seq, seq, seq),
            out_specs=seq,
        )
        return fn(q, k, v)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(seq, seq, seq, P(None, None)),
        out_specs=seq,
    )
    return fn(q, k, v, segment_ids)


def ulysses_transformer_attention(
    q, k, v, cache_k, cache_v, mask, offsets, rel_bias,
    mesh: Mesh, axis: str = "seq", batch_axis: Optional[str] = None,
):
    """Ulysses-style sequence parallelism for the transformer policy's
    in-unroll attention: all-to-all to head sharding, then EXACTLY the
    dense path's computation (band mask, segment mask, relative bias,
    KV-cache leg) on the full sequence for H/P local heads, then
    all-to-all back. Numerics match the dense branch by construction —
    it IS the dense branch on a head slice.

    q, k, v:   [B, T, H, D] global, sharded along T.
    cache_k/v: [B, M, H, D] replicated (every head set needs its slice).
    mask:      [B, T, M+T] bool — the dense path's combined cache+unroll
               mask, replicated.
    offsets:   [T, M+T] int relative distances (dense path's table).
    rel_bias:  [H, M+1] learned bias.
    Returns [B, T, H, D], sharded along T.
    """
    from jax import shard_map

    num_blocks = mesh.shape[axis]
    B, T, H, D = q.shape
    if H % num_blocks != 0:
        raise ValueError(
            f"ulysses needs H ({H}) divisible by the axis size "
            f"({num_blocks})"
        )
    hs = H // num_blocks

    def local_fn(q_blk, k_blk, v_blk, c_k, c_v, mask_f, off, bias_tbl):
        i = jax.lax.axis_index(axis)
        a2a = functools.partial(
            jax.lax.all_to_all, axis_name=axis, split_axis=2,
            concat_axis=1, tiled=True,
        )
        qh, kh, vh = a2a(q_blk), a2a(k_blk), a2a(v_blk)  # [B, T, hs, D]
        c_k_h = jax.lax.dynamic_slice_in_dim(c_k, i * hs, hs, axis=2)
        c_v_h = jax.lax.dynamic_slice_in_dim(c_v, i * hs, hs, axis=2)
        bias_h = jax.lax.dynamic_slice_in_dim(bias_tbl, i * hs, hs, axis=0)

        k_all = jnp.concatenate([c_k_h, kh], axis=1)  # [B, M+T, hs, D]
        v_all = jnp.concatenate([c_v_h, vh], axis=1)
        out = dense_transformer_attend(
            qh, k_all, v_all, mask_f, off, bias_h
        )
        return jax.lax.all_to_all(
            out, axis_name=axis, split_axis=1, concat_axis=2, tiled=True
        )

    ba = batch_axis
    seq = P(ba, axis, None, None)
    cache4 = P(ba, None, None, None)
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(seq, seq, seq, cache4, cache4, P(ba, None, None),
                  P(None, None), P(None, None)),
        out_specs=seq,
    )
    return fn(q, k, v, cache_k, cache_v, mask, offsets, rel_bias)
