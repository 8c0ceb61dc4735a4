"""The `granite4` family (models/granite4.py: a layer is a mixer AND a
SwiGLU, each branch times a residual multiplier; the Mamba-2 mixer and
the position-free attention are models/nemotron3.py's `mamba_mixer` and
`attention_mixer`, the one copy of each): against the plain reference
on seeded weights (loss, gradients, new states), each of the config's
four multipliers seen by that comparison, the scan at ONE B/C group
against a per-head loop, chunks longer than the unroll and unrolls that
are no whole chunks, batch forward against stepwise acting through the
carried states and through the state table."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import Granite4Net, granite4, nemotron3
from torchbeast_tpu.models.transformer import Recurrent

T, B, A = scaffold.FAMILIES["granite4"].t, scaffold.B, scaffold.A
# The shrunken `PUBLISHED` (tests/family_scaffold.py): two Mamba-2
# layers around one attention layer, scanned in chunks of 4 steps: the
# 11 steps of an unroll are two whole chunks and one padded.
SMALL = scaffold.FAMILIES["granite4"].small
M = SMALL["memory_len"]
# On the CPU both sides compute in float32 at full precision and differ
# by the order of their sums.
RTOL = ATOL = 2e-5

# Episode ends at a chunk's first step (4), at its last (7), and twice in
# one chunk (8 and 10), in one row; the other row ends one on step 0,
# where the state the unroll starts from is dropped whole.
ENDS = [(4, 0), (7, 0), (8, 0), (10, 0), (0, 1), (5, 1)]


@pytest.mark.parametrize("unrolls", [0, 2], ids=["empty", "warm"])
def test_family_agrees_with_the_reference(unrolls):
    """Outputs, the states every layer leaves (Mamba state, conv tail,
    the rolled cache), the loss and its gradients against perfbench/
    reference/granite4_policy.py (the recurrence a step at a time, one
    softmax over all keys), from empty states and from what two unrolls
    left, across episode ends inside a chunk and at a chunk's edge; the
    update's stats say what the layers are."""
    model, params = scaffold.build("granite4")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    stats, grads, _, _ = scaffold.assert_agrees_with_the_reference(
        model, params, state, scaffold.learner_batch(1, ENDS, t=T),
        RTOL, ATOL,
    )
    assert float(stats["ssm_applications"]) == 2
    assert float(stats["ssm_chunks"]) == 3
    assert float(stats["ssm_resets_per_row"]) == len(ENDS) / B
    # A state [8, 8, 6] and a tail [3, 8 * 8 + 2 * 6], float32, a layer.
    assert float(stats["ssm_state_bytes_per_row"]) == 2 * 4 * (384 + 3 * 76)
    assert float(stats["mlp_applications"]) == 3
    assert float(stats["attention_unrotated_applications"]) == 1
    # The toy's scores are far under the fused pass's threshold.
    assert "attention_fused_applications" not in stats
    # Every leaf learns: none is cut off by a multiplier or a reset.
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert np.any(leaf), path


# What each multiplier is at the toy's size, and another value for it:
# the program run with the other value is no longer the reference.
MULTIPLIERS = {
    "embedding_multiplier": dict(input_scale=1.0),
    "attention_multiplier": dict(attention_multiplier=8 ** -0.5),
    "residual_multiplier": dict(residual_multiplier=1.0),
    "logits_scaling": dict(logits_scale=1.0),
}


@pytest.mark.parametrize("multiplier", list(MULTIPLIERS))
def test_the_reference_comparison_sees_each_multiplier(multiplier):
    """A program that leaves one of the config's four multipliers out
    (the embedding's 12, the scores' 1/64 taken for head_dim^-0.5, the
    residual branches' 0.22, the logits' 1/8; here the toy's 3, 1/16,
    0.3 and 1/4) fails the comparison that the family passes, by the
    harness's own measure: the loss against the reference's over its
    scale, past the benchmark's 5e-3."""
    model, params = scaffold.build("granite4")
    state = scaffold.warm_state(model, params, seed=2)
    batch = scaffold.learner_batch(1, ENDS, t=T)
    want, scale, _ = scaffold.reference_loss_and_grads(model)(
        params, batch, state
    )
    good, _, _ = scaffold.loss_and_grads(model)(params, batch, state)
    assert abs(float(good) - float(want)) <= RTOL * float(scale)
    # The same weights and the reference unchanged: `input_scale` also
    # divides `Dense_0`'s init, which the memoised weights leave alone.
    faulty = model.clone(**MULTIPLIERS[multiplier])
    got, _, _ = scaffold.loss_and_grads(faulty)(params, batch, state)
    assert abs(float(got) - float(want)) > 5e-3 * float(scale), multiplier


def _per_head_recurrence(x, dt, A, B_in, C_in, state, done):
    """The recurrence a step and a HEAD at a time: every head reads the
    one group's B_t and C_t."""
    rows, steps, H, P = x.shape
    N = B_in.shape[-1]
    y = np.zeros((rows, steps, H, P))
    h = np.array(state, np.float64)
    for b in range(rows):
        for t in range(steps):
            if done[b, t]:
                h[b] = 0.0
            for head in range(H):
                h[b, head] = np.exp(dt[b, t, head] * A[head]) * h[
                    b, head
                ] + dt[b, t, head] * np.outer(x[b, t, head], B_in[b, t, 0])
                y[b, t, head] = h[b, head] @ C_in[b, t, 0]
    assert h.shape == (rows, H, P, N)
    return y, h


@pytest.mark.parametrize(
    "steps,chunk", [(12, 4), (11, 4), (1, 256), (7, 256), (9, 8)]
)
def test_one_group_scan_equals_a_per_head_loop(steps, chunk):
    """`ssd_scan` at G = 1 (every head on the one B/C group: Granite's
    64 on one, where Nemotron-3's heads read 8 groups of 16) against a
    loop over steps and heads in float64, with `done` at a chunk's first
    step, at its last, twice in one chunk and at step 0; a chunk longer
    than the unroll (the published 256 over 7 steps, and over the one
    step of acting) and an unroll that is no whole chunks (11 of 4, 9
    of 8)."""
    rows, H, P, N = 2, 6, 3, 5
    keys = jax.random.split(jax.random.PRNGKey(steps), 6)
    x = jax.random.normal(keys[0], (rows, steps, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (rows, steps, H)))
    A_ = -jnp.exp(jax.random.normal(keys[2], (H,)))
    B_in = jax.random.normal(keys[3], (rows, steps, 1, N))
    C_in = jax.random.normal(keys[4], (rows, steps, 1, N))
    state = jax.random.normal(keys[5], (rows, H, P, N))
    done = np.zeros((rows, steps), bool)
    for step, row in ENDS:
        if step < steps:
            done[row, step] = True
    scan = jax.jit(nemotron3.ssd_scan, static_argnums=7)
    y, last = scan(x, dt, A_, B_in, C_in, state, jnp.asarray(done), chunk)
    want_y, want_last = _per_head_recurrence(
        *(np.asarray(a, np.float64) for a in (x, dt, A_, B_in, C_in, state)),
        done,
    )
    np.testing.assert_allclose(y, want_y, 1e-4, 1e-4)
    np.testing.assert_allclose(last, want_last, 1e-4, 1e-4)


@pytest.mark.parametrize("unrolls", [0, 2], ids=["empty", "warm"])
def test_batch_forward_equals_stepwise_acting_through_the_carried_states(
    unrolls
):
    """The learner's [T, B] forward (the scan in chunks of 4, the
    convolution as shifted adds over the unroll, attention over [cache;
    unroll]) and the actor's T=1 forwards through two Mamba states,
    their conv tails and the rolling cache give the same logits and
    leave the same states, across episode ends inside a chunk."""
    model, params = scaffold.build("granite4")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, ENDS, t=T)
    )


def test_the_published_chunk_is_longer_than_a_short_unroll():
    """At the published chunk of 256 the toy's 11 steps are ONE chunk
    of 11 (`chunk_plan`): the same function as in chunks of 4."""
    model, params = scaffold.build("granite4")
    long_chunks, _ = scaffold.build("granite4", chunk_size=256)
    batch = scaffold.inputs(3, ENDS, t=T)
    state = scaffold.warm_state(model, params, seed=2)
    out, new_state = scaffold.forward(model)(params, batch, state)
    out_long, new_state_long = scaffold.forward(long_chunks)(
        params, batch, state
    )
    np.testing.assert_allclose(
        out.policy_logits, out_long.policy_logits, RTOL, ATOL
    )
    for got, want in zip(
        jax.tree_util.tree_leaves(new_state_long),
        jax.tree_util.tree_leaves(new_state),
    ):
        np.testing.assert_allclose(got, want, RTOL, ATOL)


def test_stepwise_acting_through_the_state_table_equals_the_batch_forward():
    """Three actors' slots in a `DeviceStateTable` whose rows hold three
    items of two kinds: two Mamba layers' (h [8, 1, 8, 6], tail [3, 1,
    76]) around the attention layer's window (k, v [M, 1, 2, 8], valid
    [M, 1]). The rows arrive in another order every step and episodes
    end on the way; every step's logits equal the batch forward's and
    the table ends with what that forward leaves; a reset brings back
    zeros of every shape."""
    model, params = scaffold.build("granite4")
    carried = [(8, 1, 8, 6), (3, 1, 76)]
    shapes = [carried, [(M, 1, 2, 8), (M, 1, 2, 8), (M, 1)], carried]
    table = scaffold.assert_state_table_acting_equals_the_batch_forward(
        model, params,
        scaffold.inputs(4, [(3, 2), (4, 2), (1, 0)], t=6, rows=3),
        shapes=shapes,
    )
    table.reset([1])
    assert all(np.any(leaf) for item in table.read_slot(0) for leaf in item)
    held = table.read_slot(1)
    assert [[np.shape(leaf) for leaf in item] for item in held] == shapes
    assert not any(np.any(leaf) for item in held for leaf in item)


def test_layers_follow_the_published_order_and_the_state_holds_what_they_carry():
    model, params = scaffold.build("granite4")
    assert model.pattern() == ("mamba", "attention", "mamba")
    carried = Recurrent(((8, 8, 6), (3, 8 * 8 + 2 * 6)))
    assert model.layer_caches() == (carried, (M, 2, 8), carried)
    blocks = params["params"]
    mlp = ["input_linear", "mlp_norm", "output_linear"]
    assert sorted(blocks["block_0"]) == sorted(mlp + [
        "A_log", "D", "conv_bias", "conv_kernel", "dt_bias", "gate_norm",
        "in_proj", "norm", "out_proj",
    ])
    assert sorted(blocks["block_1"]) == sorted(
        mlp + ["k", "norm", "o", "q", "v"]
    )
    # z | x B C | dt, ONE group; the SwiGLU's two halves in one matrix.
    assert blocks["block_0"]["in_proj"]["kernel"].shape == (
        32, 64 + (64 + 2 * 6) + 8
    )
    assert blocks["block_0"]["input_linear"]["kernel"].shape == (32, 96)
    # The published table: 40 layers, attention at 5, 15, 25, 35; a cut
    # is whole periods of the first ten.
    published = granite4.PUBLISHED
    assert [
        layer for layer, kind in enumerate(published["layer_types"])
        if kind == "attention"
    ] == [5, 15, 25, 35]
    assert len(published["layer_types"]) == published["num_layers"] == 40
    assert published["layer_period"] == published["layer_types"][:10]
    for depth in (10, 20, 40):
        net = Granite4Net(num_actions=A, **dict(published, num_layers=depth))
        assert net.pattern() == published["layer_types"][:depth]
        assert len(net.initial_state(1)) == depth
    assert net.layer_caches()[0] == Recurrent(
        ((64, 64, 128), (3, 64 * 64 + 2 * 128))
    )
    assert net.layer_caches()[5] == (4095, 8, 64)
    with pytest.raises(ValueError, match="whole periods of 10"):
        Granite4Net(num_actions=A, **dict(published, num_layers=12))
    # The toy: two periods, and its own `layer_types` when all are asked.
    assert Granite4Net(
        num_actions=A, **dict(SMALL, num_layers=6)
    ).pattern() == ("mamba", "attention", "mamba") * 2
    with pytest.raises(ValueError, match="whole periods of 3"):
        Granite4Net(num_actions=A, **dict(SMALL, num_layers=4))


def test_the_scopes_are_in_the_lowered_update():
    model, params = scaffold.build("granite4")
    batch = scaffold.learner_batch(1, ENDS, t=T)
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    text = jax.jit(jax.grad(
        lambda p: learner_lib.compute_loss(
            model, p, batch, model.initial_state(B), hp
        )[0]
    )).lower(params).as_text(debug_info=True)
    for scope in (
        "mamba_in_proj", "mamba_conv", "ssd_scan/ssd_intra",
        "ssd_scan/ssd_states", "ssd_scan/ssd_inter", "mamba_gate_norm",
        "mamba_out_proj", "attention_full", "dense_mlp",
    ):
        assert scope in text, scope
