"""The scores' multiplier is the caller's (ISSUE 64): `ops/attention.
dense_transformer_attend(..., scale=)` and `ops/fused_attention.
fused_attend(..., scale=)` take what a family's config states
(models/granite4.py: `attention_multiplier` 1/64 on heads of 64, where
head_dim^-0.5 is 1/8); absent, both compute head_dim^-0.5 as they did,
and the programs of the families that never name it are the parent's.
"""

import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.ops import attention, fused_attention

B, T, K, H, HKV = 2, 16, 40, 4, 2


def _operands(d, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (B, T, H, d))
    k = jax.random.normal(keys[1], (B, K, HKV, d))
    v = jax.random.normal(keys[2], (B, K, HKV, d))
    # Causal over the last T keys, the K - T before them a cache of
    # which row 1 holds half.
    mask = jnp.arange(K)[None, :] <= jnp.arange(T)[:, None] + K - T
    mask = jnp.broadcast_to(mask, (B, T, K))
    mask = mask.at[1, :, : (K - T) // 2].set(False)
    return q, k, v, mask, jax.random.normal(keys[3], (B, T, H, d))


def _plain(q, k, v, mask, scale):
    """softmax(mask(q k^T * scale)) v, every query head with its
    key/value head repeated, float32 at the highest precision."""
    k, v = (jnp.repeat(x, H // HKV, axis=2) for x in (k, v))
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        scores = jnp.where(mask[:, None], scores, -1e30)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v
        )


def _value_and_grads(f):
    def scalar(q, k, v, mask, dout):
        return jnp.sum(f(q, k, v, mask) * dout)

    return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2)))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("scale", [1 / 64, None])
def test_both_regimes_are_a_plain_softmax_at_the_callers_scale(d, scale):
    """The dense body and the fused pass (interpreted; heads of 64
    padded to the lanes inside it, heads of 128 as they are) against a
    plain softmax at the config's 1/64 and, the argument absent, at
    head_dim^-0.5: value and the gradients of q, k and v. A scale taken
    from the padded width, or left at head_dim^-0.5, is another
    function."""
    operands = _operands(d)
    want_scale = d ** -0.5 if scale is None else scale
    named = {} if scale is None else {"scale": scale}

    def dense(q, k, v, mask):
        with jax.default_matmul_precision("highest"):
            return attention.dense_transformer_attend(
                q, k, v, mask, None, None, **named
            )

    def fused(q, k, v, mask):
        return fused_attention.fused_attend(q, k, v, mask, terms=3, **named)

    assert not attention.fused_pass_applies(
        operands[0].shape, operands[1].shape, None
    )  # `dense` is the dense body at these sizes
    want, want_grads = _value_and_grads(
        lambda q, k, v, mask: _plain(q, k, v, mask, want_scale)
    )(*operands)
    for regime in (dense, fused):
        got, grads = _value_and_grads(regime)(*operands)
        assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-4)
        for g, w in zip(grads, want_grads):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    other, _ = _value_and_grads(
        lambda q, k, v, mask: _plain(q, k, v, mask, 128 ** -0.5 * 0.9)
    )(*operands)
    assert abs(float(other) - float(want)) > 1e-2


def test_the_fused_pass_under_the_body_takes_the_callers_scale(monkeypatch):
    """Where `fused_pass_applies`, `dense_transformer_attend` hands its
    `scale` on: the same result as the fused pass called with it."""
    q, k, v, mask, _ = _operands(64)
    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    assert attention.fused_pass_applies(q.shape, k.shape, None)
    through = jax.jit(lambda q, k, v, mask: (
        attention.dense_transformer_attend(
            q, k, v, mask, None, None, scale=1 / 64
        )
    ))
    direct = jax.jit(lambda q, k, v, mask: fused_attention.fused_attend(
        q, k, v, mask, scale=1 / 64
    ))
    np.testing.assert_array_equal(
        through(q, k, v, mask), direct(q, k, v, mask)
    )


# sha256 (first 16 hex digits) of the toy update's lowered text at
# commit a1c1765, PR 63's, the parent of the PR that gave the two
# functions their `scale` and cut `mamba_mixer` / `attention_mixer` out
# of models/nemotron3.py's blocks: made by `_lowered_update` below in a
# `git archive` of that commit. A later PR that changes one of these
# programs on purpose computes its own: Nemotron-3's two are PR 65's,
# whose mixers sow one more stat (`ssm_kernel_applications`) and norm
# the gate's groups as slices (`gated_group_norm`). All three are PR
# 67's, whose layers that call `conv_over_episodes` sow one more stat
# (`conv_kernel_applications`) and nothing else: with those four sows
# taken out the three programs hash to PR 65's (087c85731cee531b,
# 4c5c6bbe544f47dc, 58ee18004b2effdd).
PARENTS = {
    # Nemotron-3's blocks call the two mixers; the dense body.
    ("nemotron3", False): "83b176739ada4716",
    # Heads of 64 through the fused pass, rematerialised: LFM2's.
    ("lfm2", True): "f9b65d9d7f691c19",
    # Heads of 128 through the fused pass, the mixers' caller.
    ("nemotron3", True): "3c43e1f27612f5bd",
}
_FUSED = {"lfm2": dict(head_dim=64), "nemotron3": dict(head_dim=128)}


def _lowered_update(family, fused):
    """The toy family's update step as `learner.make_update_step`
    lowers it, inner functions' counters stripped (two lowerings in one
    process differ in them); `fused`: through the fused pass,
    rematerialised."""
    model, params = scaffold.build(family, **(_FUSED[family] if fused else {}))
    if fused:
        model = model.clone(remat=True)
    t = scaffold.FAMILIES[family].t
    hp = learner_lib.HParams(batch_size=scaffold.B, unroll_length=t - 1)
    optimizer = learner_lib.make_optimizer(hp)
    text = learner_lib.make_update_step(model, optimizer, hp).lower(
        params, optimizer.init(params),
        scaffold.learner_batch(9, [(1, 1)], t=t),
        model.initial_state(scaffold.B),
    ).as_text()
    return re.sub(r"(@[A-Za-z_][A-Za-z_0-9.]*?)_\d+\b", r"\1", text)


@pytest.mark.parametrize(
    "family,fused", list(PARENTS), ids=lambda v: str(v)
)
def test_lowered_updates_are_the_parents(family, fused, monkeypatch):
    """The families that name no `scale` lower to the parent's text,
    byte for byte: Nemotron-3 through the mixers cut out of its blocks
    (its parameter tree is then the parent's too: the text lists every
    leaf's shape in the tree's order), and a fused-pass family with the
    argument absent (the three programs as PRs 65 and 67 left them:
    `PARENTS`)."""
    if fused:
        monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    text = _lowered_update(family, fused)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS[
        (family, fused)
    ]
