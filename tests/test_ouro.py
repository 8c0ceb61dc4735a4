"""The `ouro` family (models/ouro.py; the pass walk in models/
transformer.py): against the plain reference on seeded weights, the loop
against an untied stack of copies, one pass against the plain stack,
the learner's batch forward against stepwise acting through the passes'
rolling caches (directly and through `DeviceStateTable`), and
rematerialised shared blocks."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import OLMoENet, OuroNet
from torchbeast_tpu.runtime import remat_plan

T, B, A = 6, scaffold.B, scaffold.A
# The shrunken `PUBLISHED` (tests/family_scaffold.py): 2 layers run 3
# times over caches of `M` slots.
SMALL = scaffold.FAMILIES["ouro"].small
M = SMALL["memory_len"]
# As tests/test_olmoe.py: on the CPU both sides compute in float32 at
# full precision and differ by the order of their sums (keys and logits
# reach 4-5 under the perturbed norm scales, hence the absolute part).
RTOL, ATOL = 1e-5, 3e-5


def test_family_agrees_with_the_reference():
    """Outputs, the passes' new caches, the loss and its gradients, and
    the exit gates' distribution, on seeded weights from warm caches
    with an episode end inside the unroll. RTOL: f32 on both sides, the
    sums in another order."""
    model, params = scaffold.build("ouro")
    state = scaffold.warm_state(model, params, seed=5)
    assert len(state) == 3 * 2
    batch = scaffold.learner_batch(7, done_steps=[(3, 0)])
    stats, grads, ref_grads, gates = (
        scaffold.assert_agrees_with_the_reference(
            model, params, state, batch, RTOL, ATOL
        )
    )
    # No gradient reaches the gate, in the program or in the reference.
    for tree in (grads, ref_grads):
        assert not np.any(scaffold.flat(tree["params"]["exit_gate"]))
    assert float(stats["aux_loss"]) == 0.0

    # The statistics the learner logs, against the reference's gates.
    reference = scaffold.FAMILIES["ouro"].reference
    p_exit = reference.exit_distribution(gates)  # [3, B, T]
    np.testing.assert_allclose(p_exit.sum(axis=0), 1.0, 1e-6)
    assert float(stats["loop_passes"]) == 3
    assert float(stats["loop_block_applications"]) == 6
    assert float(stats["attention_two_leg_applications"]) == 6
    assert "attention_fused_applications" not in stats
    assert float(stats["loop_cache_bytes_per_row"]) == 6 * 4 * M * (
        2 * 4 * 16 + 1
    )
    np.testing.assert_allclose(
        stats["loop_expected_exit_pass"],
        jnp.mean(jnp.sum(p_exit * jnp.arange(1, 4)[:, None, None], axis=0)),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        stats["loop_exit_p_last"], jnp.mean(p_exit[-1]), rtol=1e-5
    )
    assert 1.0 < float(stats["loop_expected_exit_pass"]) < 3.0


class _UntiedOuroNet(OuroNet):
    """The loop written out as a stack of `passes x num_layers` distinct
    blocks: entry i is served by block i, and the last norm still
    follows every `num_layers` of them."""

    def block_passes(self):
        L = self.num_layers
        return tuple(
            tuple(range(u * L, (u + 1) * L)) for u in range(self.passes)
        )


def test_looped_equals_untied_and_its_gradient_is_the_copies_sum():
    """Weight tying is the only difference between the loop and a stack
    of passes x L distinct blocks: given copies of the looped weights
    the untied stack gives the same outputs and caches, and its
    gradients summed over a block's copies are the looped gradient."""
    model, params = scaffold.build("ouro")
    L, P = SMALL["num_layers"], SMALL["passes"]
    untied = _UntiedOuroNet(num_actions=A, **SMALL)
    inner = dict(params["params"])
    copies = {
        f"block_{u * L + layer}": jax.tree_util.tree_map(
            jnp.copy, inner[f"block_{layer}"]
        )
        for u in range(P) for layer in range(L)
    }
    untied_params = {"params": dict(inner, **copies)}
    # The untied net really builds P x L blocks of its own.
    shapes = jax.eval_shape(
        lambda: untied.init(
            {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
            scaffold.inputs(0), untied.initial_state(B),
        )
    )
    assert sorted(shapes["params"]) == sorted(untied_params["params"])

    state = scaffold.warm_state(model, params, seed=3)
    batch = scaffold.learner_batch(11, done_steps=[(2, 1)])
    out, new_state = scaffold.forward(model)(params, batch, state)
    out_u, new_state_u = scaffold.forward(untied)(
        untied_params, batch, state
    )
    np.testing.assert_allclose(out.policy_logits, out_u.policy_logits, RTOL, ATOL)
    for got, want in zip(
        jax.tree_util.tree_leaves(new_state),
        jax.tree_util.tree_leaves(new_state_u),
    ):
        np.testing.assert_allclose(got, want, RTOL, ATOL)

    loss, _, grads = scaffold.loss_and_grads(model)(params, batch, state)
    loss_u, _, grads_u = scaffold.loss_and_grads(untied)(
        untied_params, batch, state
    )
    assert float(loss) == pytest.approx(float(loss_u), rel=1e-6)
    g, gu = grads["params"], grads_u["params"]
    for layer in range(L):
        summed = jax.tree_util.tree_map(
            lambda *parts: sum(parts),
            *[gu[f"block_{u * L + layer}"] for u in range(P)],
        )
        flat, want = (
            scaffold.flat(t) for t in (summed, g[f"block_{layer}"])
        )
        # A sum of three gradients against one accumulated in the
        # backward pass: f32, another order.
        np.testing.assert_allclose(
            flat, want, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(want)))
        )
        # And no copy's gradient is the whole.
        assert float(jnp.max(jnp.abs(
            scaffold.flat(gu[f"block_{layer}"]) - want
        ))) > 1e-4 * float(jnp.max(jnp.abs(want)))
    for name in ("Dense_0", "extras", "final_norm", "head"):
        np.testing.assert_allclose(
            scaffold.flat(g[name]), scaffold.flat(gu[name]),
            rtol=1e-4, atol=1e-6,
        )


def test_one_pass_is_the_plain_stack():
    """passes = 1: L caches, block i on entry i, the norm once at the
    end — the walk every other family on the scaffolding takes — and the
    reference's single pass agrees."""
    model, params = scaffold.build("ouro", passes=1)
    assert model.block_passes() == ((0, 1),)
    assert model.block_passes() == OLMoENet(
        num_actions=A, num_layers=2
    ).block_passes()
    assert len(model.initial_state(B)) == 2
    state = scaffold.warm_state(model, params, seed=1)
    batch = scaffold.learner_batch(2, done_steps=[(1, 0)])
    out, _ = scaffold.forward(model)(params, batch, state)
    logits, baseline, _, gates = scaffold.reference_forward(model)(
        params, batch, state
    )
    np.testing.assert_allclose(out.policy_logits, logits, RTOL, ATOL)
    np.testing.assert_allclose(out.baseline, baseline, RTOL, ATOL)
    _, stats, _ = scaffold.loss_and_grads(model)(params, batch, state)
    assert float(stats["loop_expected_exit_pass"]) == 1.0
    assert float(stats["loop_exit_p_last"]) == 1.0
    # More passes over the same weights give another policy.
    looped, _ = scaffold.build("ouro")
    out3, _ = scaffold.forward(looped)(
        params, batch, looped.initial_state(B)
    )
    out1, _ = scaffold.forward(model)(params, batch, model.initial_state(B))
    assert float(jnp.max(jnp.abs(out3.policy_logits - out1.policy_logits))) > 1e-3


@pytest.mark.parametrize("unrolls", [0, 1], ids=["empty", "full"])
def test_batch_forward_equals_stepwise_acting_through_the_passes_caches(
    unrolls,
):
    """The learner's [T, B] forward and the actor's T=1 forwards, each
    of 3 passes through its 3 x 2 rolling caches (5 slots; T=6 evicts),
    give the same logits and leave the same caches, across an episode
    end. Tolerance: a softmax over another number of masked keys, f32."""
    model, params = scaffold.build("ouro")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    full_state = scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, done_steps=[(3, 1)])
    )
    # A pass's cache is its own: pass 0's and pass 1's keys of layer 0
    # differ.
    assert float(jnp.max(jnp.abs(full_state[0][0] - full_state[2][0]))) > 1e-3


def test_stepwise_acting_through_the_state_table_equals_the_batch_forward():
    """Three actors' slots in a `DeviceStateTable` whose rows hold the
    3 x 2 caches (a pytree of 18 leaves the table knows nothing of);
    the rows arrive in another order every step and one episode ends on
    the way. Every step's logits equal the learner's batch forward from
    empty caches over the same inputs, and what the table holds at the
    end is what that forward leaves. Tolerance as above."""
    model, params = scaffold.build("ouro")
    table = scaffold.assert_state_table_acting_equals_the_batch_forward(
        model, params, scaffold.inputs(4, done_steps=[(3, 2)], rows=3)
    )
    assert len(table.read_slot(0)) == 3 * 2
    # Resetting a slot empties all 6 of its caches and no other's.
    table.reset([1])
    assert not any(np.any(leaf) for e in table.read_slot(1) for leaf in e)
    assert all(np.any(e[2]) for e in table.read_slot(0))


@pytest.mark.parametrize("family,lever", [
    ("transformer", True), ("pipelined_transformer", True),
    ("mellum2", True), ("ouro", True), ("olmoe", False), ("deep", False),
    ("mlp", False), ("shallow", False), ("pipelined_mlp", False),
])
def test_a_familys_class_says_whether_remat_reaches_its_blocks(family, lever):
    """runtime/remat_plan.py asks the class (`remat_lever`), not a list
    of names: the membership PR 32 left, plus ouro; olmoe stays out."""
    names = [s.name for s in remat_plan.stages_for(family, use_lstm=False)]
    assert ("blocks" in names) == lever
    kwargs = remat_plan.model_kwargs(
        family, {name: True for name in names}
    )
    assert (kwargs.get("remat") is True) == lever


def test_every_matmul_is_traced_at_the_familys_precision():
    """Three bf16 passes (`high`) on every dot of the update, the
    gradient's and the rematerialised blocks' among them: what keeps the
    chip's loss within 2e-5 of the float32 reference's scale, not 2.5e-3
    (PERF.md, PR 34). The other families' dots say nothing (DEFAULT)."""
    import re

    def dots(model, params, state):
        batch = scaffold.learner_batch(1, done_steps=[])
        hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
        text = jax.jit(jax.grad(
            lambda p: learner_lib.compute_loss(model, p, batch, state, hp)[0]
        )).lower(params).as_text()
        return re.findall(r"dot_general.*", text)

    model, params = scaffold.build("ouro")
    ours = dots(model.clone(remat=True), params, model.initial_state(B))
    assert len(ours) > 100 and all("HIGH" in d for d in ours)
    assert not any("HIGHEST" in d for d in ours)
    plain = OLMoENet(
        num_actions=A, num_layers=1, d_model=64, num_heads=4, memory_len=M,
        num_experts=4, experts_per_token=2, expert_width=32,
    )
    plain_params = scaffold.init_params(plain, scaffold.inputs(0))
    theirs = dots(plain, plain_params, plain.initial_state(B))
    assert theirs and not any("HIGH" in d for d in theirs if "HIGHEST" not in d)
