"""The `kanana2` family's latent attention on its own (models/
kanana2.py; ops/attention.latent_cached_attend): absorbed against
decompressed, the interleaved RoPE, and the whole family with the cache
leg as the blockwise pass. Apart from tests/test_kanana2.py, which
holds the family against its reference: under `--dist loadfile` a file
is one worker's chain (ISSUE 49)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench.reference import kanana2_policy as reference
from tests import family_scaffold as scaffold
from tests.test_kanana2 import ATOL, B, RTOL, T
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import kanana2
from torchbeast_tpu.ops import attention


def test_absorbed_equals_decompressed():
    """`latent_cached_attend` against dense attention over `[cache;
    unroll]` with `kv_b` applied to every cached latent: the same
    values, and the same gradient for the decompression matrix, which
    the absorbed leg reads in two halves and never multiplies a cached
    latent by. A third of the cache is masked out."""
    rng = np.random.default_rng(3)
    rows, steps, slots, H, C, Dn, Dr, Dv = 2, 5, 7, 4, 24, 16, 8, 12

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q_nope, q_rope = normal(rows, steps, H, Dn), normal(rows, steps, H, Dr)
    c, k_r = normal(rows, steps, C), normal(rows, steps, 1, Dr)
    cache_c, cache_r = normal(slots, rows, 1, C), normal(slots, rows, 1, Dr)
    w_kvb = 0.3 * normal(C, H, Dn + Dv)
    cache_mask = jnp.asarray(rng.random((rows, steps, slots)) < 0.67)
    seq_mask = jnp.broadcast_to(
        jnp.tril(jnp.ones((steps, steps), bool)), (rows, steps, steps)
    )
    theta = 1e6

    @jax.jit
    def absorbed(w):
        kv = jnp.einsum("btc,chd->bthd", c, w)
        return attention.latent_cached_attend(
            q_nope, kanana2.rope_pairs(q_rope, jnp.arange(steps), theta),
            kv[..., :Dn], kanana2.rope_pairs(k_r, jnp.arange(steps), theta),
            kv[..., Dn:], cache_c, cache_r, w[..., :Dn], w[..., Dn:],
            cache_mask, seq_mask,
            place_cache_keys=lambda keys, times: kanana2.rope_pairs(
                keys, times, theta, time_axis=0
            ),
        )

    @jax.jit
    def decompressed(w):
        latents = jnp.concatenate(
            [cache_c[:, :, 0].transpose(1, 0, 2), c], axis=1
        )
        rope_keys = jnp.concatenate(
            [cache_r.transpose(1, 0, 2, 3), k_r], axis=1
        )
        times = jnp.concatenate([jnp.arange(slots) - slots, jnp.arange(steps)])
        kv = jnp.einsum("bkc,chd->bkhd", latents, w)
        keys = jnp.concatenate([
            kv[..., :Dn],
            jnp.repeat(kanana2.rope_pairs(rope_keys, times, theta), H, axis=2),
        ], axis=-1)
        queries = jnp.concatenate([
            q_nope, kanana2.rope_pairs(q_rope, jnp.arange(steps), theta),
        ], axis=-1)
        # Values as wide as the keys for the dense body, then cut back.
        values = jnp.pad(kv[..., Dn:], ((0, 0),) * 3 + ((0, Dn + Dr - Dv),))
        return attention.dense_transformer_attend(
            queries, keys, values,
            jnp.concatenate([cache_mask, seq_mask], axis=-1), None, None,
        )[..., :Dv]

    np.testing.assert_allclose(
        absorbed(w_kvb), decompressed(w_kvb), RTOL, ATOL
    )
    weight = normal(rows, steps, H, Dv)
    grad = jax.jit(
        lambda w, f: jax.grad(lambda w: jnp.sum(weight * f(w)))(w),
        static_argnums=1,
    )
    grads = [grad(w_kvb, f) for f in (absorbed, decompressed)]
    assert float(jnp.max(jnp.abs(grads[1]))) > 0.1
    np.testing.assert_allclose(grads[0], grads[1], rtol=1e-4, atol=1e-5)
    # The cache is data: it takes no gradient from the absorbed form,
    # in either regime of its cache leg (tests/test_attention.py).
    assert absorbed(w_kvb).shape == (rows, steps, H, Dv)


def test_interleaved_rope_turns_neighbouring_pairs():
    """`rope_interleave`: (x[2i], x[2i+1]) is the pair, not (x[i],
    x[i + D/2]); the program's and the reference's agree, in both
    layouts."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 1, 8)),
                    jnp.float32)
    times = jnp.asarray([-2, 0, 5])
    got = kanana2.rope_pairs(x, times, 1e6)
    for row in range(2):
        np.testing.assert_allclose(
            got[row], reference._rope_pairs(x[row], times, 1e6), RTOL, ATOL
        )
    np.testing.assert_allclose(
        kanana2.rope_pairs(x.transpose(1, 0, 2, 3), times, 1e6, time_axis=0),
        got.transpose(1, 0, 2, 3), RTOL, ATOL,
    )
    # Position 0 leaves x alone; the first pair turns by the position.
    np.testing.assert_allclose(got[:, 1], x[:, 1], RTOL, ATOL)
    np.testing.assert_allclose(
        got[0, 2, 0, :2],
        [x[0, 2, 0, 0] * np.cos(5) - x[0, 2, 0, 1] * np.sin(5),
         x[0, 2, 0, 1] * np.cos(5) + x[0, 2, 0, 0] * np.sin(5)],
        RTOL, ATOL,
    )


def test_family_with_the_fused_leg_agrees_with_the_xla_body(monkeypatch):
    """Five layers over a latent of whole lane tiles (128), caches an
    actor warmed: with the threshold of `fused_latent_leg_applies`
    lowered every layer's cache leg is the blockwise pass (interpreted
    here) and is counted,
    `attention_latent_fused_applications` 5 beside `attention_latent_
    applications` 5; the loss and the gradients are those of the XLA
    body; a leg at `high` keeps the XLA body and the key is absent."""
    layers = 5
    model, params = scaffold.build(
        "kanana2", num_layers=layers, latent_rank=128
    )
    state = scaffold.warm_state(model, params, seed=5, unrolls=2)
    batch = scaffold.learner_batch(9, done_steps=[(1, 1)])
    loss, stats, grads = scaffold.loss_and_grads(model)(params, batch, state)
    assert float(stats["attention_latent_applications"]) == layers
    assert "attention_latent_fused_applications" not in stats

    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    # A trace of its own (`__wrapped__`: not the scaffold's memoised
    # one): the rule is read at the trace.
    loss_f, stats_f, grads_f = scaffold.loss_and_grads.__wrapped__(model)(
        params, batch, state
    )
    assert float(stats_f["attention_latent_applications"]) == layers
    assert float(stats_f["attention_latent_fused_applications"]) == layers
    assert float(loss_f) == pytest.approx(float(loss), rel=1e-4)
    flat, flat_f = scaffold.flat(grads), scaffold.flat(grads_f)
    np.testing.assert_allclose(
        flat_f, flat, rtol=0, atol=RTOL * float(jnp.max(jnp.abs(flat)))
    )
    # The stats' keys alone, nothing computed.
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    stats_h = jax.eval_shape(
        lambda p: learner_lib.compute_loss(
            model.clone(cache_leg_precision="high"), p, batch, state, hp
        )[1],
        params,
    )
    assert "attention_latent_applications" in stats_h
    assert "attention_latent_fused_applications" not in stats_h
