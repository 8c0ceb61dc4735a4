"""A rematerialised block runs the fused attention pass's forward kernel
ONCE (ISSUE 63): `ops/fused_attention.py` names the kernel's two results
and `models/transformer.py` `rematerialised` keeps the names across
`nn.remat`. A case a family of ONE parametrised test, beside tests/
test_families_remat.py, whose toy cases take the dense body and see none
of this: here the fused regime is forced (heads of 128, LFM2's of 64;
the threshold at one byte; the kernels interpreted).
"""

import collections
import re

import flax.linen as nn
import jax
import numpy as np
import optax
import pytest
from jax._src import core

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.ops import attention, fused_attention

_ENDS = [(1, 1)]
# family: the overrides that bring its attention to the fused pass's
# widths, and the block applications that then take it.
KEPT = {
    "trinity": (dict(expert_share=(0, 8), head_dim=128), 3),
    "mellum2": (dict(expert_share=(1, 4), head_dim=128), 4),
    "lfm2": (dict(head_dim=64), 1),
    # A key pair side by side is the key: pairs of 64. Sliding, full and
    # the cross layer that reads the full layer's keys.
    "phi4flash": (dict(d_model=256, num_heads=4, num_key_value_heads=2), 3),
    "nemotron3": (dict(head_dim=128), 1),
    "qwen3next": (dict(head_dim=128), 1),
    # Heads of 64 padded to the lanes, the scores times the config's
    # constant and not 64^-0.5.
    "granite4": (dict(head_dim=64), 1),
}


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in core.jaxprs_in_params(eqn.params):
            yield from _walk(inner)


def _kernel_calls(jaxpr):
    """Pallas calls by the kernel's name, through every inner jaxpr."""
    return collections.Counter(
        eqn.params["name"] for eqn in _walk(jaxpr)
        if eqn.primitive.name == "pallas_call"
    )


def _kept_across_remat(jaxpr):
    """The shapes a backward pass's rematerialised blocks read: what the
    first forward kept for them (a block's inputs, and whatever the
    policy saved) beside the cotangents."""
    return collections.Counter(
        tuple(var.aval.shape)
        for eqn in _walk(jaxpr)
        if eqn.params.get("differentiated") and "policy" in eqn.params
        for var in eqn.invars
    )


def _update(family, monkeypatch, named):
    """(jaxpr, loss, stats, gradients) of the family's rematerialised toy
    update through the fused pass; `named` False: the same with the
    names taken away, which is the program `nn.remat` under no policy
    makes."""
    overrides, _ = KEPT[family]
    model, params = scaffold.build(family, **overrides)
    model = model.clone(remat=True)
    toy = scaffold.FAMILIES[family]
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(9, _ENDS, t=toy.t)
    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    if not named:
        monkeypatch.setattr(
            fused_attention, "checkpoint_name", lambda x, name: x
        )
    # A trace of its own: both rules are read at the trace.
    traced = scaffold.loss_and_grads.__wrapped__(model).trace(
        params, batch, state
    )
    loss, stats, grads = traced.lower().compile()(params, batch, state)
    return traced.jaxpr.jaxpr, loss, stats, grads


@pytest.mark.parametrize("family", list(KEPT))
def test_a_rematerialised_block_calls_the_forward_kernel_once(
    family, monkeypatch
):
    """With the names kept the update's gradient holds ONE `fused_
    attend_forward` an attention application where it holds two without
    them, the same `fused_attend_backward`s; what the backward pass's
    blocks read beside that is the kernel's two results an application
    and nothing else (no `[cache; k]`, no mask); the loss and every
    gradient leaf are the same to the bit; the stats count every fused
    application as one whose results were kept."""
    _, applications = KEPT[family]
    jaxpr, loss, stats, grads = _update(family, monkeypatch, named=True)
    with monkeypatch.context() as without:
        jaxpr_p, loss_p, stats_p, grads_p = _update(
            family, without, named=False
        )
    calls, calls_p = _kernel_calls(jaxpr), _kernel_calls(jaxpr_p)
    assert calls["fused_attend_forward"] == applications
    assert calls_p["fused_attend_forward"] == 2 * applications
    assert (
        calls["fused_attend_backward"] == calls_p["fused_attend_backward"]
        == applications
    )
    # Every other kernel (the experts', the scans') as often as before.
    del calls["fused_attend_forward"], calls_p["fused_attend_forward"]
    assert calls == calls_p

    kept = _kept_across_remat(jaxpr) - _kept_across_remat(jaxpr_p)
    assert not _kept_across_remat(jaxpr_p) - _kept_across_remat(jaxpr)
    assert sum(kept.values()) == 2 * applications
    # `out` [B, Hkv, G * Tp, D] and a float a row [B, Hkv, G * Tp].
    outs = {shape for shape in kept if len(shape) == 4}
    assert {shape[:3] for shape in outs} == {
        shape for shape in kept if len(shape) == 3
    }
    assert all(shape[-1] == 128 for shape in outs)

    assert float(loss) == float(loss_p)
    jax.tree_util.tree_map(np.testing.assert_array_equal, grads, grads_p)
    assert float(stats["attention_fused_applications"]) == applications
    assert float(stats["attention_forward_results_kept"]) == applications
    # Without `--remat` nothing is kept and nothing says so.
    assert "attention_forward_results_kept" not in _stats_without_remat(
        family
    )


def _stats_without_remat(family):
    overrides, _ = KEPT[family]
    model, params = scaffold.build(family, **overrides)
    toy = scaffold.FAMILIES[family]
    stats = jax.eval_shape(
        lambda p: learner_lib.compute_loss(
            model, p, scaffold.learner_batch(9, _ENDS, t=toy.t),
            model.initial_state(scaffold.B),
            learner_lib.HParams(
                batch_size=scaffold.B, unroll_length=toy.t - 1
            ),
        )[1],
        params,
    )
    assert "attention_fused_applications" in stats
    return stats


def _lowered_update(family, overrides):
    model, params = scaffold.build(family, **overrides)
    model = model.clone(remat=True)
    toy = scaffold.FAMILIES[family]
    hp = learner_lib.HParams(batch_size=scaffold.B, unroll_length=toy.t - 1)
    optimizer = optax.sgd(1e-3)
    text = learner_lib.make_update_step(model, optimizer, hp).lower(
        params, optimizer.init(params),
        scaffold.learner_batch(9, _ENDS, t=toy.t),
        model.initial_state(scaffold.B),
    ).as_text()
    # An inner function's symbol ends in a count of the process's
    # lowerings so far (`@along_axis_367`).
    return re.sub(r"(@\w+?)_\d+\b", r"\1", text)


@pytest.mark.parametrize("family", list(KEPT))
def test_a_block_that_takes_the_dense_body_is_rematerialised_whole(
    family, monkeypatch
):
    """At the toy widths every block takes the dense body, which names
    nothing: the rematerialised update under the policy lowers to the
    text that plain `nn.remat` gives (Qwen3-Next's under the solves'
    name alone), which is the parent's program."""
    overrides = {
        k: v for k, v in KEPT[family][0].items() if k == "expert_share"
    }
    kept = _lowered_update(family, overrides)

    def plain(block_cls, *also_kept):
        policy = jax.checkpoint_policies.save_only_these_names(*also_kept)
        return nn.remat(block_cls, policy=policy if also_kept else None)

    monkeypatch.setattr(
        scaffold.FAMILIES[family].module, "rematerialised", plain
    )
    assert _lowered_update(family, overrides) == kept


def test_a_family_outside_the_rule_is_rematerialised_under_no_policy(
    monkeypatch
):
    """Kanana-2's attention is the latent leg, a `custom_vjp` of its
    own (ROADMAP S8 (a)): with that leg fused its rematerialised update
    reaches no name and hands `nn.remat` no policy, as before this
    rule."""
    def unreached(x, name):
        raise AssertionError(f"{name} named outside the fused pass")

    monkeypatch.setattr(fused_attention, "checkpoint_name", unreached)
    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    model, params = scaffold.build(
        "kanana2", expert_share=(1, 8), latent_rank=128
    )
    model = model.clone(remat=True)
    jaxpr = jax.make_jaxpr(
        scaffold.loss_and_grads.__wrapped__(model, jit=False)
    )(
        params, scaffold.learner_batch(9, _ENDS),
        model.initial_state(scaffold.B),
    )
    calls = _kernel_calls(jaxpr.jaxpr)
    assert calls["fused_latent_leg_forward"] == 2 * model.num_layers
    policies = [
        eqn.params["policy"] for eqn in _walk(jaxpr.jaxpr)
        if eqn.params.get("differentiated") and "policy" in eqn.params
    ]
    assert policies == [None] * model.num_layers
