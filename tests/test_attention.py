"""Ring attention == dense attention, on the 8-device CPU mesh: causal,
with and without segment (episode-boundary) masking, odd head dims, and
gradient equivalence."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from torchbeast_tpu.ops.attention import (
    causal_attention,
    ring_attention,
    segment_ids_from_done,
)
from torchbeast_tpu.parallel import create_mesh
from tests import family_scaffold as scaffold

B, T, H, D = 2, 16, 4, 8  # T divisible by the 8-way ring

# The functions under test through `jax.jit`, traced once a shape (run
# op by op they are an XLA compile an op: tests/family_scaffold.py).
_causal = jax.jit(causal_attention)
_ring = jax.jit(ring_attention, static_argnames=("mesh", "axis", "schedule"))


def make_qkv(seed=0, t=T):
    rng = np.random.default_rng(seed)
    shape = (B, t, H, D)
    return tuple(
        jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        for _ in range(3)
    )


def seq_sharded(mesh, x):
    return jax.device_put(
        x, NamedSharding(mesh, P(None, "data") + P(*(None,) * (x.ndim - 2)))
    )


def test_causal_attention_is_causal():
    q, k, v = make_qkv()
    out1 = _causal(q, k, v)
    # Changing the future must not change the past.
    v2 = v.at[:, -1].set(123.0)
    out2 = _causal(q, k, v2)
    np.testing.assert_allclose(out1[:, :-1], out2[:, :-1], rtol=1e-6)
    assert not np.allclose(out1[:, -1], out2[:, -1])


def test_segment_mask_blocks_cross_episode():
    q, k, v = make_qkv()
    done = np.zeros((T, B), bool)
    done[T // 2] = True  # episode boundary mid-sequence
    seg = segment_ids_from_done(jnp.asarray(done)).T  # [B, T]
    out = _causal(q, k, v, segment_ids=seg)
    # Changing pre-boundary values must not affect post-boundary outputs.
    v2 = v.at[:, 0].set(55.0)
    out2 = _causal(q, k, v2, segment_ids=seg)
    np.testing.assert_allclose(
        out[:, T // 2 :], out2[:, T // 2 :], rtol=1e-6
    )


@pytest.mark.parametrize("with_segments", [False, True])
def test_ring_matches_dense(with_segments):
    mesh = create_mesh(8)
    q, k, v = make_qkv()
    seg = None
    if with_segments:
        done = np.zeros((T, B), bool)
        done[5] = True
        done[11, 0] = True
        seg = segment_ids_from_done(jnp.asarray(done)).T

    dense = _causal(q, k, v, segment_ids=seg)

    qs, ks, vs = (seq_sharded(mesh, x) for x in (q, k, v))
    segs = None
    if seg is not None:
        segs = jax.device_put(seg, NamedSharding(mesh, P(None, "data")))
    ring = _ring(qs, ks, vs, mesh=mesh, axis="data", segment_ids=segs)

    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=2e-4, atol=2e-5
    )


@pytest.mark.slow
def test_ring_gradients_match_dense():
    mesh = create_mesh(8)
    q, k, v = make_qkv(seed=3)

    def dense_loss(q, k, v):
        return jnp.sum(_causal(q, k, v) ** 2)

    def ring_loss(q, k, v):
        return jnp.sum(_ring(q, k, v, mesh=mesh, axis="data") ** 2)

    g_dense_fn = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))
    g_dense = g_dense_fn(q, k, v)
    qs, ks, vs = (seq_sharded(mesh, x) for x in (q, k, v))
    g_ring_fn = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))
    g_ring = g_ring_fn(qs, ks, vs)
    for gd, gr in zip(g_dense, g_ring):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gd), rtol=2e-3, atol=2e-4
        )


def test_ring_long_sequence():
    # 512 tokens over the 8-way ring: 64-token blocks, no full [T, T]
    # materialization per device.
    mesh = create_mesh(8)
    q, k, v = make_qkv(seed=4, t=512)
    dense = _causal(q, k, v)
    qs, ks, vs = (seq_sharded(mesh, x) for x in (q, k, v))
    ring = _ring(qs, ks, vs, mesh=mesh, axis="data")
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("with_segments", [False, True])
def test_zigzag_ring_matches_dense(with_segments):
    mesh = create_mesh(8)
    q, k, v = make_qkv(seed=5)
    seg = None
    if with_segments:
        done = np.zeros((T, B), bool)
        done[5] = True
        done[11, 0] = True
        seg = segment_ids_from_done(jnp.asarray(done)).T

    dense = _causal(q, k, v, segment_ids=seg)
    qs, ks, vs = (seq_sharded(mesh, x) for x in (q, k, v))
    segs = None
    if seg is not None:
        segs = jax.device_put(seg, NamedSharding(mesh, P(None, "data")))
    zig = _ring(
        qs, ks, vs, mesh=mesh, axis="data", segment_ids=segs,
        schedule="zigzag",
    )
    np.testing.assert_allclose(
        np.asarray(zig), np.asarray(dense), rtol=2e-4, atol=2e-5
    )


@pytest.mark.slow
def test_zigzag_ring_gradients_match_dense():
    mesh = create_mesh(8)
    q, k, v = make_qkv(seed=6)

    def dense_loss(q, k, v):
        return jnp.sum(_causal(q, k, v) ** 2)

    def zig_loss(q, k, v):
        return jnp.sum(
            _ring(q, k, v, mesh=mesh, axis="data", schedule="zigzag")
            ** 2
        )

    g_dense_fn = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))
    g_dense = g_dense_fn(q, k, v)
    qs, ks, vs = (seq_sharded(mesh, x) for x in (q, k, v))
    g_zig_fn = jax.jit(jax.grad(zig_loss, argnums=(0, 1, 2)))
    g_zig = g_zig_fn(qs, ks, vs)
    for gd, gz in zip(g_dense, g_zig):
        np.testing.assert_allclose(
            np.asarray(gz), np.asarray(gd), rtol=2e-3, atol=2e-4
        )


@pytest.mark.parametrize("with_segments", [False, True])
@pytest.mark.slow
def test_zigzag_ring_long_sequence(with_segments):
    # T=512 on the 8-way mesh -> chunk size 32: exercises the intra-chunk
    # tril-and-segment interaction at c > 1 (T=16 degenerates to c=1).
    t = 512
    mesh = create_mesh(8)
    q, k, v = make_qkv(seed=7, t=t)
    seg = None
    if with_segments:
        done = np.zeros((t, B), bool)
        done[50] = True
        done[200, 0] = True
        done[470] = True
        seg = segment_ids_from_done(jnp.asarray(done)).T
    dense = _causal(q, k, v, segment_ids=seg)
    qs, ks, vs = (seq_sharded(mesh, x) for x in (q, k, v))
    segs = None
    if seg is not None:
        segs = jax.device_put(seg, NamedSharding(mesh, P(None, "data")))
    zig = _ring(
        qs, ks, vs, mesh=mesh, axis="data", segment_ids=segs,
        schedule="zigzag",
    )
    np.testing.assert_allclose(
        np.asarray(zig), np.asarray(dense), rtol=2e-4, atol=2e-5
    )
    # Contract: output keeps the input's T-sharding (a replicated output
    # would mean the in-op permutation all-gathered the sequence).
    assert zig.sharding.is_equivalent_to(qs.sharding, zig.ndim)


def test_zigzag_rejects_indivisible_t():
    mesh = create_mesh(8)
    q, k, v = make_qkv(seed=8, t=24)  # 24 % 16 != 0
    with pytest.raises(ValueError, match="divisible"):
        _ring(q, k, v, mesh=mesh, axis="data", schedule="zigzag")


def test_unknown_schedule_rejected():
    mesh = create_mesh(8)
    q, k, v = make_qkv(seed=9)
    with pytest.raises(ValueError, match="schedule"):
        _ring(q, k, v, mesh=mesh, axis="data", schedule="spiral")


# --- dense_transformer_attend: equal and grouped heads -------------------


def _old_dense_transformer_attend(q, k_all, v_all, mask, offsets, rel_bias):
    """The body as it was before grouped-query heads (PR 32), kept here
    as the oracle for `Hkv == H`."""
    scale = q.shape[-1] ** -0.5
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k_all).astype(jnp.float32) * scale
    )
    if rel_bias is not None:
        scores = scores + rel_bias[:, offsets][None]
    scores = jnp.where(mask[:, None], scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1).astype(v_all.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v_all)


@pytest.mark.parametrize("kv_heads", [4, 2, 1], ids=["mha", "gqa-2", "mqa"])
@pytest.mark.parametrize("with_bias", [False, True])
def test_dense_transformer_attend_by_group(kv_heads, with_bias):
    """With as many key/value heads as query heads the body is the
    program it was, bit for bit (jitted, as the models run it); with
    fewer it equals the old body on K and V repeated per group, values
    and gradients."""
    from torchbeast_tpu.ops.attention import dense_transformer_attend

    rng = np.random.default_rng(kv_heads)
    M = 5
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k, v = (
        jnp.asarray(rng.standard_normal((B, M + T, kv_heads, D)), jnp.float32)
        for _ in range(2)
    )
    mask = jnp.asarray(rng.random((B, T, M + T)) < 0.6).at[:, :, M].set(True)
    offsets = jnp.asarray(rng.integers(0, M + 1, (T, M + T)))
    bias = (
        jnp.asarray(rng.standard_normal((H, M + 1)), jnp.float32)
        if with_bias else None
    )
    group = H // kv_heads
    repeat = lambda x: jnp.repeat(x, group, axis=2)
    attend = jax.jit(dense_transformer_attend)
    attend_as_it_was = jax.jit(_old_dense_transformer_attend)
    new = attend(q, k, v, mask, offsets, bias)
    old = attend_as_it_was(q, repeat(k), repeat(v), mask, offsets, bias)
    if kv_heads == H:
        np.testing.assert_array_equal(new, old)
    else:
        np.testing.assert_allclose(new, old, rtol=1e-5, atol=1e-6)

    def total(fn, rep):
        return lambda q, k, v: jnp.sum(
            jnp.sin(fn(q, rep(k), rep(v), mask, offsets, bias))
        )

    got_fn = jax.jit(jax.grad(
        total(dense_transformer_attend, lambda x: x), (0, 1, 2)
    ))
    got = got_fn(q, k, v)
    want_fn = jax.jit(jax.grad(
        total(_old_dense_transformer_attend, repeat), (0, 1, 2)
    ))
    want = want_fn(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# --- cached_transformer_attend: the cache and the unroll as two legs -----


@functools.partial(jax.jit, static_argnames="M")
def _masks_by_leg(done, valid, M):
    from torchbeast_tpu.ops.attention import band_by_leg

    seg = segment_ids_from_done(done).T
    no_done_yet = jnp.cumsum(done, axis=0).T == 0
    cache_band, seq_band = band_by_leg(T, M)
    cache_mask = cache_band[None] & valid[:, None, :] & no_done_yet[:, :, None]
    seq_mask = seq_band[None] & (seg[:, :, None] == seg[:, None, :])
    return cache_mask, seq_mask


def _two_leg_case(kv_heads, cache, seed=0, M=5, heads=H, head_size=D):
    """A seeded case as models/transformer.py would hand it over: a
    `done` inside the unroll (row 0, step 7: later queries see neither
    the cache nor the steps before it), the band, and a cache that is
    wholly invalid, valid in its newest slots, or full."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, heads, head_size)), jnp.float32)
    k, v = (
        jnp.asarray(rng.standard_normal((B, T, kv_heads, head_size)), jnp.float32)
        for _ in range(2)
    )
    cache_k, cache_v = (
        jnp.asarray(rng.standard_normal((M, B, kv_heads, head_size)), jnp.float32)
        for _ in range(2)
    )
    done = np.zeros((T, B), bool)
    done[7, 0] = True
    valid = {
        "invalid": np.zeros((B, M), bool),
        "partly": np.arange(M)[None, :] >= np.array([2, 4])[:, None],
        "full": np.ones((B, M), bool),
    }[cache]
    cache_mask, seq_mask = _masks_by_leg(done, valid, M=M)
    return q, k, v, cache_k, cache_v, cache_mask, seq_mask


def _dense_on_the_concatenation(q, k, v, cache_k, cache_v, cache_mask,
                                seq_mask):
    from torchbeast_tpu.ops.attention import dense_transformer_attend

    return dense_transformer_attend(
        q,
        jnp.concatenate([cache_k.transpose(1, 0, 2, 3), k], axis=1),
        jnp.concatenate([cache_v.transpose(1, 0, 2, 3), v], axis=1),
        jnp.concatenate([cache_mask, seq_mask], axis=-1), None, None,
    )


@pytest.mark.parametrize("cache", ["invalid", "partly", "full"])
@pytest.mark.parametrize(
    "heads, kv_heads", [(H, H), (8, 2)], ids=["mha", "gqa-8-on-2"]
)
def test_cached_transformer_attend_is_the_dense_body(heads, kv_heads, cache):
    """Two legs of one softmax against the dense body on `[cache; k]`,
    `[cache; v]`: outputs, and the gradients with respect to q, k, v
    AND the cache (asked for explicitly: the function is plain autodiff,
    no rule of its own drops it)."""
    from torchbeast_tpu.ops.attention import cached_transformer_attend

    case = _two_leg_case(kv_heads, cache, seed=kv_heads, heads=heads)
    two_legs = jax.jit(cached_transformer_attend)
    dense = jax.jit(_dense_on_the_concatenation)
    got, want = two_legs(*case), dense(*case)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # A query after the `done` sees no slot and nothing before step 7.
    assert not bool(case[5][0, 7:].any()) and not bool(case[6][0, 7:, :7].any())

    def total(fn):
        return lambda *operands: jnp.sum(jnp.sin(fn(*operands, *case[5:])))

    operands = case[:5]
    got_fn = jax.jit(
        jax.grad(total(cached_transformer_attend), range(5))
    )
    got = got_fn(*operands)
    want_fn = jax.jit(
        jax.grad(total(_dense_on_the_concatenation), range(5))
    )
    want = want_fn(*operands)
    for name, a, b in zip(("q", "k", "v", "cache_k", "cache_v"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)
    if cache != "invalid":
        assert float(jnp.abs(got[3]).max()) > 0 < float(jnp.abs(got[4]).max())


def _two_legs_as_at_pr_35(q, k, v, cache_k, cache_v, cache_mask, seq_mask):
    """`cached_transformer_attend`'s body as PR 35 wrote it, kept here
    word for word: PR 38 put `latent_cached_attend` beside it and may
    not have moved it."""
    from torchbeast_tpu.ops.attention import BIG_NEG

    B, T, H, D = q.shape
    Hkv = k.shape[2]
    scale = D ** -0.5
    q = q.reshape(B, T, Hkv, H // Hkv, D)

    def scores(spec, keys, mask):
        s = jnp.einsum(spec, q, keys).astype(jnp.float32) * scale
        return jnp.where(mask[:, None, None], s, BIG_NEG)

    s_c = scores("bqhgd,mbhd->bhgqm", cache_k, cache_mask)
    s_u = scores("bqhgd,bkhd->bhgqk", k, seq_mask)
    top = jax.lax.stop_gradient(
        jnp.maximum(s_c.max(axis=-1), s_u.max(axis=-1))
    )[..., None]
    p_c = jnp.exp(s_c - top)
    out_c = jnp.einsum(
        "bhgqm,mbhd->bqhgd", p_c.astype(cache_v.dtype), cache_v
    )
    p_u = jnp.exp(s_u - top)
    out_u = jnp.einsum("bhgqk,bkhd->bqhgd", p_u.astype(v.dtype), v)
    den = p_c.sum(axis=-1) + p_u.sum(axis=-1)
    out = (out_c + out_u) / den.transpose(0, 3, 1, 2)[..., None].astype(
        out_u.dtype
    )
    return out.reshape(B, T, H, D)


@pytest.mark.parametrize("cache", ["partly", "full"])
@pytest.mark.parametrize(
    "heads, kv_heads", [(H, H), (8, 2)], ids=["mha", "gqa-8-on-2"]
)
def test_cached_transformer_attend_is_what_it_was(heads, kv_heads, cache):
    """The old call, bit for bit: outputs and all five gradients of the
    function OLMoE and Ouro attend through equal its PR 35 body's."""
    from torchbeast_tpu.ops.attention import cached_transformer_attend

    case = _two_leg_case(kv_heads, cache, seed=3 + kv_heads, heads=heads)
    now, then = jax.jit(cached_transformer_attend), jax.jit(_two_legs_as_at_pr_35)
    np.testing.assert_array_equal(now(*case), then(*case))

    def total(fn):
        return jax.jit(jax.grad(
            lambda *operands: jnp.sum(jnp.sin(fn(*operands, *case[5:]))),
            range(5),
        ))

    for a, b in zip(
        total(cached_transformer_attend)(*case[:5]),
        total(_two_legs_as_at_pr_35)(*case[:5]),
    ):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("cache", ["invalid", "partly", "full"])
def test_latent_cached_attend_with_nothing_compressed_is_the_two_legs(cache):
    """`latent_cached_attend` where the latent IS a head's key and
    value (one head, the decompression matrices the identity, no rope
    part worth the name): the absorbed cache leg is then the plain one,
    and the function computes what `cached_transformer_attend` does."""
    from torchbeast_tpu.ops.attention import (
        cached_transformer_attend,
        latent_cached_attend,
    )

    q, k, _, cache_k, _, cache_mask, seq_mask = _two_leg_case(
        1, cache, seed=5, heads=1
    )
    size = q.shape[-1]
    rope = jnp.zeros(q.shape[:-1] + (2,), jnp.float32)
    got_fn = jax.jit(latent_cached_attend)
    got = got_fn(
        q, rope, k, rope[:, :, :1], k, cache_k,
        jnp.zeros(cache_k.shape[:-1] + (2,), jnp.float32),
        jnp.eye(size)[:, None, :], jnp.eye(size)[:, None, :],
        cache_mask, seq_mask,
    )
    # The two-leg body scales by D^-0.5, the latent one by (D + 2)^-0.5.
    want_fn = jax.jit(cached_transformer_attend)
    want = want_fn(
        q * (size / (size + 2)) ** 0.5, k, k, cache_k, cache_k,
        cache_mask, seq_mask,
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_cached_transformer_attend_refuses_heads_that_do_not_divide():
    from torchbeast_tpu.ops.attention import cached_transformer_attend

    q, k, v, cache_k, cache_v, cache_mask, seq_mask = _two_leg_case(3, "full")
    with pytest.raises(ValueError, match="do not divide"):
        cached_transformer_attend(
            q, k, v, cache_k, cache_v, cache_mask, seq_mask
        )


@pytest.mark.parametrize("t", [1, 3, 9], ids=["act", "short", "evicts-all"])
def test_roll_kv_cache_in_the_states_layout(t):
    """Rolled where the state lies (axis 0) = rolled batch-first and
    transposed back, for an act step, an unroll shorter than the cache
    and one that evicts all of it, a `done` inside."""
    from torchbeast_tpu.ops.attention import roll_kv_cache

    M = 5
    rng = np.random.default_rng(t)
    k_cache, v_cache = (
        jnp.asarray(rng.standard_normal((B, M, H, D)), jnp.float32)
        for _ in range(2)
    )
    k_new, v_new = (
        jnp.asarray(rng.standard_normal((B, t, H, D)), jnp.float32)
        for _ in range(2)
    )
    valid = jnp.asarray(rng.random((B, M)) < 0.7, jnp.float32)
    done = np.zeros((t, B), bool)
    done[t // 2, 1] = True
    seg = segment_ids_from_done(jnp.asarray(done)).T
    no_done = jnp.cumsum(jnp.asarray(done), axis=0).T == 0
    roll = jax.jit(roll_kv_cache, static_argnames="axis")
    want = roll(k_cache, v_cache, valid, k_new, v_new, seg, no_done)
    to_state = lambda x: jnp.swapaxes(x, 0, 1)
    got = roll(
        *map(to_state, (k_cache, v_cache, valid, k_new, v_new, seg, no_done)),
        axis=0,
    )
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, to_state(b))


def _arrays_of(jaxpr):
    """(primitive, input shapes, output shapes) of every equation of a
    jaxpr and of the jaxprs it holds (remat, pjit, custom rules)."""
    for eqn in jaxpr.eqns:
        yield (
            eqn.primitive.name,
            [getattr(x.aval, "shape", ()) for x in eqn.invars],
            [x.aval.shape for x in eqn.outvars],
            eqn.params,
        )
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _arrays_of(sub)


def test_ouro_update_gradient_builds_nothing_over_cache_and_unroll():
    """The gradient of a tiny Ouro update with respect to its
    parameters, as a jaxpr: no array has a key axis of M + T (no
    `[cache; k]`, no joined scores or mask, forward or backward), and
    no `[M, B, H, hd]` operand is transposed to `[B, M, H, hd]`; the
    cache leg's backward pass is a query's alone: no dot_general puts
    out an array of the cache's shape."""
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.models import OuroNet

    t, rows, slots, heads, size, actions = 6, 2, 5, 4, 16, 4
    model = OuroNet(
        num_actions=actions, memory_len=slots, d_model=64, num_heads=heads,
        head_dim=size, mlp_width=96, num_layers=2, passes=3, remat=True,
    )
    rng = np.random.default_rng(0)
    lead = (t, rows)
    batch = {
        "frame": jnp.asarray(rng.integers(0, 256, lead + (8, 8, 1)), jnp.uint8),
        "reward": jnp.asarray(rng.standard_normal(lead), jnp.float32),
        "done": jnp.zeros(lead, bool).at[3, 0].set(True),
        "last_action": jnp.asarray(rng.integers(0, actions, lead)),
        "episode_return": jnp.zeros(lead, jnp.float32),
        "episode_step": jnp.zeros(lead, jnp.int32),
        "action": jnp.asarray(rng.integers(0, actions, lead)),
        "policy_logits": jnp.asarray(
            rng.standard_normal(lead + (actions,)), jnp.float32
        ),
        "baseline": jnp.asarray(rng.standard_normal(lead), jnp.float32),
    }
    state = model.initial_state(rows)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        {k: batch[k] for k in ("frame", "reward", "done", "last_action")},
        state,
    )
    hp = monobeast.hparams_from_flags(monobeast.make_parser().parse_args([]))

    def loss(params):
        return learner_lib.compute_loss(model, params, batch, state, hp)[0]

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    cache, cache_first = (slots, rows, heads, size), (rows, slots, heads, size)
    joined, equations = slots + t, 0
    assert joined not in (t, rows, slots, heads, size, actions, 64, 96)
    for name, ins, outs, eqn_params in _arrays_of(jaxpr):
        equations += 1
        for shape in ins + outs:
            assert joined not in shape, (name, ins, outs)
        if name == "transpose":
            assert not (ins[0] == cache and outs[0] == cache_first), (
                name, eqn_params,
            )
        if name == "dot_general":
            assert cache not in outs and cache_first not in outs, (ins, outs)
    # The walk went inside the rematerialised blocks: 3 passes x 2
    # layers, forward and again in the backward pass.
    assert equations > 1000


# --- fused_attend: the dense body with its scores in VMEM -----------------


@jax.jit
def _side_by_side(q, k, v, cache_k, cache_v, cache_mask, seq_mask):
    return (
        q,
        jnp.concatenate([cache_k.transpose(1, 0, 2, 3), k], axis=1),
        jnp.concatenate([cache_v.transpose(1, 0, 2, 3), v], axis=1),
        jnp.concatenate([cache_mask, seq_mask], axis=-1),
    )


def _fused_case(heads, kv_heads, cache, M, head_size=D):
    """`_two_leg_case` as the Mellum2 block hands it over: `[cache; k]`,
    `[cache; v]` and the two masks side by side."""
    return _side_by_side(*_two_leg_case(
        kv_heads, cache, seed=heads + M, M=M, heads=heads,
        head_size=head_size,
    ))


def _dense_body(q, k_all, v_all, mask):
    from torchbeast_tpu.ops.attention import dense_transformer_attend

    return dense_transformer_attend(q, k_all, v_all, mask, None, None)


def _gradients_of(fn, mask):
    """Jitted gradients of a scalar of fn(q, k_all, v_all, mask) with
    respect to its three operands."""
    return jax.jit(jax.grad(
        lambda *operands: jnp.sum(jnp.sin(fn(*operands, mask))), (0, 1, 2)
    ))


@pytest.mark.parametrize(
    "heads, kv_heads, cache, M, blocks",
    [
        (4, 4, "full", 1776, (896, 256)),
        (4, 4, "invalid", 1684, (896, 256)),
        (8, 2, "partly", 684, (768, 384)),
        (8, 2, "full", 5, (128, 128)),
        (8, 1, "invalid", 1776, (896, 256)),
        (8, 1, "partly", 1684, (896, 256)),
    ],
    ids=[
        "mha-full-whole-blocks", "mha-empty-ragged", "gqa4-partly-ragged",
        "gqa4-full-1-block", "gqa8-empty-whole-blocks", "gqa8-partly-ragged",
    ],
)
def test_fused_attend_is_the_dense_body(heads, kv_heads, cache, M, blocks):
    """The blockwise pass (interpreted here) against the dense body on
    the same `[cache; unroll]`: the output and the gradients of q, k_all
    and v_all, for equal and grouped heads (1, 4, 8 to a key/value
    head), M + T = 1,792 keys (two whole blocks of 896 forward, seven of
    256 backward), 1,700 (the last of each ragged), 700 (one ragged
    block forward, two backward) and 21 (one block, mostly padding),
    T = 16 steps (two sublane tiles) — and in every case an episode end
    inside the unroll, whose first query (row 0, step 7) admits its own
    step alone when the cache is empty."""
    from torchbeast_tpu.ops import fused_attention
    from torchbeast_tpu.ops.fused_attention import fused_attend, key_block

    q, k_all, v_all, mask = _fused_case(heads, kv_heads, cache, M)
    assert blocks == (
        key_block(M + T, fused_attention._FORWARD_KEYS),
        key_block(M + T, fused_attention._BACKWARD_KEYS),
    )
    if cache == "invalid":
        assert int(mask[0, 7].sum()) == 1 and bool(mask[0, 7, M + 7])
    fused, dense = jax.jit(fused_attend), jax.jit(_dense_body)
    got, want = fused(q, k_all, v_all, mask), dense(q, k_all, v_all, mask)
    assert got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    fused_grads = _gradients_of(fused_attend, mask)
    dense_grads = _gradients_of(_dense_body, mask)
    got, want = fused_grads(q, k_all, v_all), dense_grads(q, k_all, v_all)
    for name, a, b in zip(("q", "k_all", "v_all"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)
    if cache != "invalid":
        # The cached keys take their gradient too: k_all is one operand.
        assert float(jnp.abs(got[1][:, :M]).max()) > 0


@pytest.mark.parametrize(
    "M, no_grad_keys, regime",
    [(1684, 1684, "fused"), (1684, 1536, "fused"), (1684, 100, "fused"),
     (5, 5, "fused"), (684, 684, "dense"), (5, 5, "dense")],
    ids=[
        "fused-from-the-last-ragged-block", "fused-from-a-block's-edge",
        "fused-from-inside-the-first-block", "fused-one-block",
        "dense-700-keys", "dense-21-keys",
    ],
)
def test_keys_told_to_take_no_gradient_take_zeros(
    monkeypatch, M, no_grad_keys, regime
):
    """`no_grad_keys` (the Mellum2 block passes its cache's length M):
    the output and dq are unchanged, dk_all and dv_all are zeros over
    the first so many keys and unchanged over the rest, in both regimes
    of `dense_transformer_attend`. In the fused one the backward kernel
    writes dk, dv from the block that holds the first key that takes
    them (of seven blocks of 256: the last and ragged one, the seventh
    from its first key, the first)."""
    from torchbeast_tpu.ops import attention

    if regime == "fused":
        monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    q, k_all, v_all, mask = _fused_case(8, 2, "partly", M, head_size=128)

    def grads(no_grad_keys):
        def total(q, k_all, v_all):
            return jnp.sum(jnp.sin(attention.dense_transformer_attend(
                q, k_all, v_all, mask, None, None, no_grad_keys
            )))

        traced = jax.jit(jax.value_and_grad(total, (0, 1, 2)))
        return traced(q, k_all, v_all)

    n = no_grad_keys
    (value, (dq, dk, dv)), (value_0, (dq_0, dk_0, dv_0)) = grads(n), grads(0)
    assert float(value) == float(value_0)
    np.testing.assert_array_equal(dq, dq_0)
    for got, whole in ((dk, dk_0), (dv, dv_0)):
        assert float(jnp.abs(whole[:, :n]).max()) > 0
        np.testing.assert_array_equal(got[:, :n], 0.0)
        np.testing.assert_array_equal(got[:, n:], whole[:, n:])


# What two and three bfloat16 terms an operand leave of a product, of
# its largest entry: 2^-16 and 2^-24 a product, a few products deep.
_CUT_TOLERANCE = {2: 1e-4, 3: 5e-6}


@pytest.mark.parametrize("terms", [2, 3], ids=["high", "highest"])
@pytest.mark.parametrize(
    "heads, kv_heads, cache, M, blocks, no_grad_keys",
    [(8, 2, "partly", 684, (384, 256), 684),
     (8, 1, "invalid", 1776, (384, 256), 300)],
    ids=["gqa4-partly-ragged", "gqa8-empty-whole-blocks"],
)
def test_fused_attend_at_its_callers_terms_is_the_dense_body(
    heads, kv_heads, cache, M, blocks, no_grad_keys, terms
):
    """At two and at three terms an operand (what a caller at `high`
    and at `highest` hands over) the kernels read float32 tiles, cut
    them into bfloat16 terms and make the product's three or six passes
    themselves; interpreted here, against the dense body traced at that
    precision (float32 on this backend): the output and dq, dk, dv
    with a cache that takes no gradient, within what the terms leave
    out, and three terms closer than two."""
    import functools

    from torchbeast_tpu.ops import attention, fused_attention
    from torchbeast_tpu.ops.fused_attention import fused_attend

    q, k_all, v_all, mask = _fused_case(heads, kv_heads, cache, M)
    # 700 keys: two ragged blocks of 384 forward (512 would pad them to
    # 1,024), three of 256 backward; 1,792: five 384s forward (to 1,920
    # where 512s pad to 2,048), seven whole 256s backward, dk and dv
    # from the second on. The cells' 4,351 keys: 512 and 256.
    assert fused_attention._key_blocks(4351, terms) == (512, 256)
    assert blocks == fused_attention._key_blocks(M + T, terms)
    cut = functools.partial(
        fused_attend, no_grad_keys=no_grad_keys, terms=terms
    )

    def dense(q, k_all, v_all, mask):
        with jax.default_matmul_precision(
            {2: "high", 3: "highest"}[terms]
        ):
            return attention.dense_transformer_attend(
                q, k_all, v_all, mask, None, None, no_grad_keys
            )

    def worst(got, want):
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    fused, plain = jax.jit(cut), jax.jit(dense)
    errors = [worst(fused(q, k_all, v_all, mask), plain(q, k_all, v_all, mask))]
    got = _gradients_of(cut, mask)(q, k_all, v_all)
    want = _gradients_of(dense, mask)(q, k_all, v_all)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(a[:, :no_grad_keys], 0.0)
        assert float(jnp.abs(b[:, no_grad_keys:]).max()) > 0
    errors += [worst(a, b) for a, b in zip(got, want)]
    assert max(errors) < _CUT_TOLERANCE[terms], errors
    if terms == 2:
        # The tail is cut and multiplied, not folded away: two terms
        # leave more than float32's rounding, and far less than one
        # term's 2^-9.
        assert max(errors) > 1e-6, errors


def test_two_terms_are_not_one():
    """Values whose bfloat16 head is the same 1.0 at every key and
    whose tail alone tells them apart: through one term they are all
    1.0, every output exactly the softmax's sum and dq zeros; through
    two terms the tail is cut in the kernel and multiplied, and the
    output and dq are the dense body's."""
    import functools

    from torchbeast_tpu.ops.fused_attention import fused_attend

    q, k_all, v_all, mask = _fused_case(8, 2, "partly", 684)
    tail = 2.0 ** -10 * jnp.tanh(v_all)
    values = 1.0 + tail
    head = values.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(head, 1.0)

    two = functools.partial(fused_attend, terms=2)
    dense, at_two, at_one = map(jax.jit, (_dense_body, two, fused_attend))
    want = dense(q, k_all, values, mask)
    signal = float(jnp.max(jnp.abs(want - 1.0)))
    assert signal > 1e-5
    got = at_two(q, k_all, values, mask)
    np.testing.assert_allclose(got - 1.0, want - 1.0, atol=0.02 * signal)
    one = at_one(q, k_all, head, mask)
    assert float(jnp.max(jnp.abs(one - 1.0))) < 0.02 * signal

    dq = _gradients_of(two, mask)(q, k_all, values)[0]
    want_dq = _gradients_of(_dense_body, mask)(q, k_all, values)[0]
    signal = float(jnp.max(jnp.abs(want_dq)))
    np.testing.assert_allclose(dq, want_dq, atol=0.02 * signal)
    dq_one = _gradients_of(fused_attend, mask)(q, k_all, head)[0]
    assert float(jnp.max(jnp.abs(dq_one))) < 0.02 * signal


@pytest.mark.parametrize(
    "traced_at, terms",
    [(None, 1), ("default", 1), ("high", 2), ("highest", 3)],
)
def test_the_fused_pass_follows_the_precision_its_caller_traces_at(
    monkeypatch, traced_at, terms
):
    """`dense_transformer_attend` hands `fused_attend` the number of
    bfloat16 terms its caller's traced precision states: two at `high`
    (models/nemotron3.py, models/qwen3next.py, models/lfm2.py), three
    at `highest`, one at the default (Mellum2's program is what it
    was)."""
    import contextlib

    from torchbeast_tpu.ops import attention

    seen = []
    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    monkeypatch.setattr(
        attention, "fused_attend",
        lambda q, k, v, mask, no_grad_keys, terms, scale: (
            seen.append(terms) or q
        ),
    )
    q, k_all, v_all, mask = _fused_case(8, 2, "partly", 5, head_size=128)
    # Traced on shapes alone: what `fused_attend` is handed is a
    # Python-side effect of the trace, and nothing is computed.
    with (
        jax.default_matmul_precision(traced_at) if traced_at
        else contextlib.nullcontext()
    ):
        jax.eval_shape(
            lambda *operands: attention.dense_transformer_attend(
                *operands, None, None
            ),
            q, k_all, v_all, mask,
        )
    assert seen == [terms]


def test_fused_attend_under_remat():
    """`--remat all` wraps the block: the rematerialised forward runs
    the kernel again and the gradients are those without it."""
    from torchbeast_tpu.ops.fused_attention import fused_attend

    q, k_all, v_all, mask = _fused_case(8, 2, "partly", 1684)

    plain_grads = _gradients_of(fused_attend, mask)
    remat_grads = _gradients_of(jax.checkpoint(fused_attend), mask)
    plain, remat = plain_grads(q, k_all, v_all), remat_grads(q, k_all, v_all)
    for a, b in zip(remat, plain):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize(
    "q_shape, keys, bias, fused",
    [
        ((32, 81, 32, 128), 4176, False, True),
        ((32, 81, 32, 128), 1104, False, True),
        ((32, 81, 32, 128), 4176, True, False),
        ((32, 81, 16, 128), 209, False, False),
        ((32, 81, 16, 128), 336, False, False),
        ((64, 1, 32, 128), 4096, False, False),
        ((2, 16, 4, 8), 21, False, False),
        ((32, 81, 64, 64), 4176, False, True),
        ((32, 81, 64, 96), 4176, False, False),
        ((32, 81, 64, 32), 4176, False, False),
    ],
    ids=[
        "mellum2-full", "mellum2-sliding", "a-learned-bias", "olmoe-sized",
        "ouro-sized", "acting", "tier-1-toy", "heads-of-64", "heads-of-96",
        "heads-of-32",
    ],
)
def test_fused_pass_applies_by_shapes_and_bias_alone(
    q_shape, keys, bias, fused
):
    """The rule: 128 MiB of f32 scores or more, heads that fill the 128
    lanes or are half of them (64, since PR 53: `fused_attend` pads such
    a head with zero columns; no other width under a tile) and no
    learned bias. The Mellum2 cell's two kinds of layer
    are above it (1,385 and 366 MB); OLMoE- and Ouro-sized problems,
    acting at T=1 against a full cache and everything tier-1 builds are
    below it."""
    from torchbeast_tpu.ops.attention import (
        FUSED_SCORE_BYTES,
        fused_pass_applies,
    )

    assert FUSED_SCORE_BYTES == 128 * 2 ** 20
    k_shape = (q_shape[0], keys, 4, q_shape[3])
    rel_bias = jnp.zeros((q_shape[2], keys)) if bias else None
    assert fused_pass_applies(q_shape, k_shape, rel_bias) is fused


@pytest.mark.parametrize(
    "threshold, bias, fused",
    [(None, False, False), (1, False, True), (1, True, False)],
    ids=["small-problem", "above-the-threshold", "above-it-with-a-bias"],
)
def test_dense_transformer_attend_chooses_by_the_rule(
    monkeypatch, threshold, bias, fused
):
    """A side of the rule each: the same call compiles the dense body
    for a small problem, the kernel once the scores are over the
    threshold (lowered here to reach it), the dense body again whenever
    a learned bias comes with it — and gives the same values."""
    from torchbeast_tpu.ops import attention

    q, k_all, v_all, mask = _fused_case(8, 2, "partly", 193, head_size=128)
    rng = np.random.default_rng(0)
    offsets = jnp.asarray(rng.integers(0, 194, (T, 193 + T)))
    rel_bias = (
        jnp.asarray(rng.standard_normal((8, 194)), jnp.float32)
        if bias else None
    )
    # A function of its own each time: traces are cached by the function
    # traced, and the rule is read at trace time.
    operands = (q, k_all, v_all, mask, offsets, rel_bias)
    want_fn = jax.jit(
        lambda *operands: attention.dense_transformer_attend(*operands)
    )
    want = want_fn(*operands)
    if threshold is not None:
        monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", threshold)
    program = str(
        jax.make_jaxpr(
            lambda *operands: attention.dense_transformer_attend(*operands)
        )(*operands)
    )
    assert ("pallas_call" in program) is fused
    got_fn = jax.jit(
        lambda *operands: attention.dense_transformer_attend(*operands)
    )
    got = got_fn(*operands)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# --- fused_latent_leg: the absorbed cache leg with its scores in VMEM -----

LATENT_NAMES = ("q_nope", "q_rope", "k_nope", "k_rope", "v", "w_uk", "w_uv")


def _latent_cache_mask(cache, rng, shape):
    """[B, T, M] bool: a cache that is full, a third masked (at random:
    a row may admit slots in one block and none in the next) or WHOLLY
    masked."""
    return jnp.asarray({
        "full": np.ones(shape, bool),
        "third-masked": rng.random(shape) < 0.67,
        "wholly-masked": np.zeros(shape, bool),
    }[cache])


def _latent_case(cache, steps, slots, seed=0, heads=4, latent=128):
    """Operands of `latent_cached_attend` as the Kanana-2 block hands
    them over, at toy head sizes (16 + 8, values of 12) over a latent
    of whole lane tiles: the seven that take a gradient, and the cache,
    the masks and a cotangent."""
    rng = np.random.default_rng(seed)
    rows, nope, rope, value = 2, 16, 8, 12

    def normal(*shape, scale=1.0):
        return jnp.asarray(
            np.float32(scale) * rng.standard_normal(shape).astype(np.float32)
        )

    operands = dict(
        q_nope=normal(rows, steps, heads, nope),
        q_rope=normal(rows, steps, heads, rope),
        k_nope=normal(rows, steps, heads, nope),
        k_rope=normal(rows, steps, 1, rope),
        v=normal(rows, steps, heads, value),
        w_uk=normal(latent, heads, nope, scale=0.1),
        w_uv=normal(latent, heads, value, scale=0.1),
    )
    fixed = dict(
        cache_latent=normal(slots, rows, 1, latent),
        cache_rope=normal(slots, rows, 1, rope),
        cache_mask=_latent_cache_mask(cache, rng, (rows, steps, slots)),
        seq_mask=jnp.asarray(np.broadcast_to(
            np.tril(np.ones((steps, steps), bool)), (rows, steps, steps)
        )),
        dout=normal(rows, steps, heads, value),
    )
    return operands, fixed


def _latent_attend(operands, fixed, cache_precision="default"):
    from torchbeast_tpu.models import kanana2
    from torchbeast_tpu.ops.attention import latent_cached_attend

    return latent_cached_attend(
        *(operands[name] for name in LATENT_NAMES[:5]),
        fixed["cache_latent"], fixed["cache_rope"],
        operands["w_uk"], operands["w_uv"],
        fixed["cache_mask"], fixed["seq_mask"],
        place_cache_keys=lambda keys, times: kanana2.rope_pairs(
            keys, times, 1e6, time_axis=0
        ),
        cache_precision=cache_precision,
    )


def _latent_value_and_grads(operands, fixed):
    """A fresh jitted function (traces are cached by the function
    traced): the output and the gradients of the seven operands."""
    def run(operands):
        out, pull = jax.vjp(lambda o: _latent_attend(o, fixed), operands)
        return out, pull(fixed["dout"])[0]

    fresh = jax.jit(run)
    return fresh(operands)


@pytest.mark.parametrize(
    "cache, steps, slots, blocks",
    [
        ("full", 16, 2048, (1024, 1024)),
        ("full", 5, 1300, (768, 768)),
        ("third-masked", 16, 1300, (768, 768)),
        ("third-masked", 3, 300, (384, 384)),
        ("wholly-masked", 5, 1300, (768, 768)),
        ("wholly-masked", 8, 100, (128, 128)),
    ],
    ids=[
        "full-whole-blocks", "full-ragged-5-steps", "third-masked-ragged",
        "third-masked-1-block-3-steps", "wholly-masked-ragged",
        "wholly-masked-1-block",
    ],
)
def test_fused_latent_leg_is_the_xla_body(
    monkeypatch, cache, steps, slots, blocks
):
    """`latent_cached_attend` with its cache leg as the blockwise pass
    (interpreted here, f32) against its XLA body on the same operands:
    the output and the gradients of q_nope, q_rope, w_uk, w_uv and the
    unroll's k_nope, k_rope, v. 2,048 slots fill their two blocks of
    1,024; 1,300 do not fill the last of two of 768; 300 and 100 are
    one ragged block; 5 and 3 steps are no multiple of the sublane
    tile. A
    WHOLLY masked cache weighs nothing in the join: the result is the
    unroll leg alone, and every gradient is finite (w_uk and w_uv,
    which only the cache leg reads, take exact zeros)."""
    from torchbeast_tpu.ops import attention, fused_attention
    from torchbeast_tpu.ops.fused_attention import key_block

    assert blocks == (
        key_block(slots, fused_attention._LATENT_FORWARD_KEYS),
        key_block(slots, fused_attention._LATENT_BACKWARD_KEYS),
    )
    operands, fixed = _latent_case(cache, steps, slots, seed=steps + slots)
    want, want_grads = _latent_value_and_grads(operands, fixed)
    assert "pallas_call" not in str(
        jax.make_jaxpr(lambda o: _latent_attend(o, fixed))(operands)
    )
    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    assert "pallas_call" in str(
        jax.make_jaxpr(lambda o: _latent_attend(o, fixed))(operands)
    )
    got, got_grads = _latent_value_and_grads(operands, fixed)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name in LATENT_NAMES:
        a, b = got_grads[name], want_grads[name]
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-5 * max(1.0, float(jnp.abs(b).max())),
            err_msg=name,
        )
    if cache == "wholly-masked":
        for name in ("w_uk", "w_uv"):
            np.testing.assert_array_equal(got_grads[name], 0.0)
    else:
        assert float(jnp.abs(got_grads["w_uk"]).max()) > 0


@pytest.mark.parametrize("cache", ["full", "third-masked", "wholly-masked"])
def test_fused_latent_leg_takes_a_cotangent_on_its_log_sum_exp(cache):
    """The leg alone against the same in plain jnp: its output, the
    rows' log-sum-exp, and the gradients of the queries' two parts when
    BOTH results carry a cotangent (ds = p (dP - delta + dlse)); 8 heads
    in two cells of 4 (`_LATENT_HEADS` lowered), 11 steps padded to 16
    as the caller pads them. A row that admits no slot (the padded
    steps always) reads a finite output (an average of the latents,
    over the last block's padding too: the join weighs it by 0),
    BIG_NEG for a log-sum-exp and a gradient of zeros, whatever its
    cotangents."""
    from torchbeast_tpu.ops import fused_attention
    from torchbeast_tpu.ops.fused_attention import (
        BIG_NEG,
        fused_latent_leg,
        padded_steps,
    )

    rng = np.random.default_rng(11)
    rows, steps, heads, slots, latent, rope = 2, 11, 8, 700, 128, 8
    tp = padded_steps(steps)
    assert tp == 16
    scale = 24 ** -0.5

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, tp - steps), (0, 0)))

    q_latent = padded(normal(heads, rows, steps, latent))
    q_rope = padded(normal(heads, rows, steps, rope))
    cache_latent, cache_rope = normal(slots, rows, latent), normal(slots, rows, rope)
    mask = _latent_cache_mask(cache, rng, (rows, steps, slots))
    dout, dlse = normal(heads, rows, tp, latent), normal(heads, rows, tp)

    def plain(q_latent, q_rope):
        s = scale * (
            jnp.einsum("hbqc,mbc->hbqm", q_latent, cache_latent)
            + jnp.einsum("hbqd,mbd->hbqm", q_rope, cache_rope)
        )
        admitted = jnp.pad(mask, ((0, 0), (0, tp - steps), (0, 0)))
        s = jnp.where(admitted[None], s, BIG_NEG)
        lse = jax.nn.logsumexp(s, axis=-1)
        out = jnp.einsum(
            "hbqm,mbc->hbqc", jnp.exp(s - lse[..., None]), cache_latent
        )
        return out, lse

    def fused(q_latent, q_rope):
        return fused_latent_leg(
            q_latent, q_rope, cache_latent, cache_rope, mask, scale
        )

    def results_and_grads(leg):
        (out, lse), pull = jax.vjp(leg, q_latent, q_rope)
        return (out, lse) + pull((dout, dlse))

    of_fused = jax.jit(lambda: results_and_grads(fused))
    of_plain = jax.jit(lambda: results_and_grads(plain))
    saved = fused_attention._LATENT_HEADS
    fused_attention._LATENT_HEADS = 4
    try:
        got = of_fused()
    finally:
        fused_attention._LATENT_HEADS = saved
        # The calls are jitted of their own: no later trace at these
        # shapes may find this one's cells.
        fused_attention._latent_forward_call.clear_cache()
        fused_attention._latent_backward_call.clear_cache()
    want = of_plain()
    dead = np.ones((heads, rows, tp), bool)
    if cache != "wholly-masked":
        dead[:, :, :steps] = ~np.asarray(mask).any(axis=-1)[None]
    assert dead[:, :, steps:].all() and (
        dead[:, :, :steps].all() == (cache == "wholly-masked")
    )
    for name, a, b in zip(("out", "lse", "dq_latent", "dq_rope"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a[~dead], b[~dead], rtol=2e-5, atol=2e-5, err_msg=name
        )
    # BIG_NEG + log(slots) is BIG_NEG in f32.
    np.testing.assert_array_equal(got[1][dead], np.float32(BIG_NEG))
    for grad in got[2:]:
        np.testing.assert_array_equal(grad[dead], 0.0)


def test_a_latent_cache_is_data_in_both_regimes(monkeypatch):
    """The cache takes no gradient from `latent_cached_attend`, fused
    leg or XLA body: the contract is one (the fused backward makes dq
    alone, and must not differ from the other regime in silence)."""
    from torchbeast_tpu.ops import attention

    operands, fixed = _latent_case("third-masked", 5, 300, seed=2)

    def cache_grads():
        # Jitted anew a regime: the rule is read at trace time.
        traced = jax.jit(jax.grad(
            lambda latent, rope: jnp.sum(jnp.sin(_latent_attend(
                operands, dict(fixed, cache_latent=latent, cache_rope=rope)
            ))),
            (0, 1),
        ))
        return traced(fixed["cache_latent"], fixed["cache_rope"])

    for threshold in (attention.FUSED_SCORE_BYTES, 1):
        monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", threshold)
        for grad in cache_grads():
            np.testing.assert_array_equal(grad, 0.0)


def test_fused_latent_leg_under_remat(monkeypatch):
    """`--remat all` wraps the block: the rematerialised forward runs
    the kernel again and the gradients are those without it."""
    from torchbeast_tpu.ops import attention

    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    operands, fixed = _latent_case("third-masked", 5, 300, seed=4)

    def grads(attend):
        of = jax.jit(jax.grad(lambda o: jnp.sum(jnp.sin(attend(o)))))
        return of(operands)

    plain = grads(lambda o: _latent_attend(o, fixed))
    remat = grads(jax.checkpoint(lambda o: _latent_attend(o, fixed)))
    for name in LATENT_NAMES:
        np.testing.assert_array_equal(remat[name], plain[name], err_msg=name)


@pytest.mark.parametrize(
    "q_shape, slots, latent, precision, ambient, fused",
    [
        ((32, 81, 32, 576), 4095, 512, "default", "high", True),
        ((32, 81, 32, 576), 4095, 512, None, None, True),
        ((32, 81, 32, 576), 4095, 512, "high", None, False),
        ((32, 81, 32, 576), 4095, 512, "highest", "high", False),
        ((32, 81, 32, 576), 4095, 512, None, "high", False),
        ((32, 1, 32, 576), 4095, 512, "default", "high", False),
        ((64, 1, 32, 576), 4095, 512, "default", "high", False),
        ((2, 6, 4, 32), 9, 24, "default", "high", False),
        ((32, 81, 32, 576), 4095, 576, "default", "high", False),
        ((4, 81, 32, 576), 4095, 512, "default", "high", True),
        ((4, 81, 32, 576), 255, 512, "default", "high", False),
    ],
    ids=[
        "kanana2-published", "one-pass-by-the-caller's-trace",
        "leg-at-high", "leg-at-highest", "high-by-the-caller's-trace",
        "acting-32-rows", "acting-64-rows", "tier-1-toy",
        "a-latent-of-4.5-lane-tiles", "a-chip's-4-rows", "a-short-cache",
    ],
)
def test_fused_latent_leg_applies_by_shapes_and_precision_alone(
    q_shape, slots, latent, precision, ambient, fused
):
    """The rule: 128 MiB of f32 scores or more in the leg, a latent of
    whole lane tiles, the leg's products at one bf16 pass (what the
    kernels compute). Kanana-2's learner step at the published widths is
    above it (1,359 MB a layer; 170 MB at a chip's 4 rows); acting at
    T=1, everything tier-1 builds and a leg asked for (or traced) at
    `high` are not."""
    from torchbeast_tpu.ops.attention import fused_latent_leg_applies

    with jax.default_matmul_precision(ambient or "default"):
        if ambient is None:
            jax.config.update("jax_default_matmul_precision", None)
        assert fused_latent_leg_applies(
            q_shape, slots, latent, precision
        ) is fused


@pytest.mark.parametrize(
    "steps, precision, fused",
    [(81, "default", True), (1, "default", False), (81, "high", False)],
    ids=["learner-step", "act-step", "leg-at-high"],
)
def test_latent_cached_attend_chooses_by_the_rule(steps, precision, fused):
    """At Kanana-2's published widths (traced on shapes alone, nothing
    computed): the learner's [81, 32] step compiles the kernels in, a
    T=1 act step and a leg asked for at `high` keep the einsums."""
    from torchbeast_tpu.ops.attention import latent_cached_attend

    B_, H_, C_, Dn, Dr, Dv, M_ = 32, 32, 512, 128, 64, 128, 4095

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def mask(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bool_)

    program = str(jax.make_jaxpr(
        lambda *operands: latent_cached_attend(
            *operands, cache_precision=precision
        )
    )(
        f32(B_, steps, H_, Dn), f32(B_, steps, H_, Dr),
        f32(B_, steps, H_, Dn), f32(B_, steps, 1, Dr),
        f32(B_, steps, H_, Dv), f32(M_, B_, 1, C_), f32(M_, B_, 1, Dr),
        f32(C_, H_, Dn), f32(C_, H_, Dv),
        mask(B_, steps, M_), mask(B_, steps, steps),
    ))
    assert ("pallas_call" in program) is fused
    # The leg's f32 scores are an array of the program in one regime only.
    assert (f"f32[{B_},{H_},{steps},{M_}]" in program) is not fused
