"""The `ling3` family (models/ling3.py: Kimi Delta Attention, a decay a
key channel inside the chunked delta rule; models/kanana2.py's latent
block with a gate a head; group-limited selection in models/moe.py
DroplessMoE): against the plain reference on seeded weights (loss,
gradients, new states, both gate forms), the chunked scan against the
recurrence a step at a time (the floor case among them), batch forward
against stepwise acting through the carried states and through the
state table, the router's groups. The shares of the routed experts
adding up to the uncut layer: an id of tests/test_families_shares.py."""

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from tests.test_trinity import _one_pass_dense
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import Ling3Net, ling3, moe
from torchbeast_tpu.models.transformer import Recurrent

T, B, A = scaffold.FAMILIES["ling3"].t, scaffold.B, scaffold.A
# The shrunken `PUBLISHED` (tests/family_scaffold.py): the leading dense
# layer (KDA), then `K M`: KDA of 4 heads of 8 scanned in chunks of 4
# steps built from sub-blocks of 2 (the 11 steps of an unroll are two
# whole chunks and one padded), a latent layer over a cache of 5 slots.
SMALL = scaffold.FAMILIES["ling3"].small
M = SMALL["memory_len"]
# On the CPU both sides compute in float32 at full precision and differ
# by the order of their sums: the chunked form's sub-block products, its
# solve and its [Dk, Dk] hand-on a chunk against 11 rank-one steps. One
# bfloat16 pass in the scan or in a projection reads 1e-3 to 1e-2 here
# (`test_the_tolerance_sees`).
RTOL = ATOL = 2e-5

# Episode ends at a chunk's first step (4), at its last (7), twice in a
# row across a chunk's edge (7, 8), inside a sub-block (9: the second
# step of sub-block [8, 9]) in one row; the other row ends one on step
# 0, where the state the unroll starts from is dropped whole, one at a
# sub-block's first step (2) and one at its second (5).
ENDS = [(4, 0), (7, 0), (8, 0), (9, 0), (0, 1), (2, 1), (5, 1)]


@pytest.mark.parametrize(
    "overrides, ends",
    [({}, ENDS), ({}, []), (dict(expert_share=(1, 4)), ENDS),
     (dict(expert_share=(3, 8)), []), (dict(safe_gate=False), ENDS)],
    ids=["everything-held-ends", "everything-held-none",
         "experts-1-of-4-ends", "experts-3-of-8-none",
         "unbounded-gate-ends"],
)
def test_family_agrees_with_the_reference(overrides, ends):
    """Logits, baseline, the states handed on, the loss and every
    gradient, from states an actor carried, with and without episode
    ends in the batch; a share of a whole group (4 of 16 in 4 groups of
    4) and of half a group; the bounded gate (the row's) and Kimi
    Linear's unbounded one."""
    model, params = scaffold.build("ling3", **overrides)
    state = scaffold.warm_state(model, params, seed=5)
    assert all(np.any(leaf) for leaf in jax.tree_util.tree_leaves(state))
    assert len(jax.tree_util.tree_leaves(state)) == 2 + 2 + 3
    batch = scaffold.learner_batch(7, ends, t=T)
    stats, grads, _, _ = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    # Every parameter of the three layers (a mixer and a feed-forward
    # part each) takes a gradient, but the selection biases.
    for block in (f"block_{i}" for i in range(6)):
        for name, leaf in grads["params"][block].items():
            if name == "moe":
                assert not np.any(leaf["e_score_correction_bias"])
                leaf = leaf["router"]
            assert np.any(jax.tree_util.tree_leaves(leaf)[0]), (block, name)
    # What the layers say of themselves.
    assert float(stats["kda_applications"]) == 2
    assert float(stats["kda_chunks"]) == 3  # 11 steps in chunks of 4
    assert float(stats["kda_sub_blocks"]) == 2
    assert float(stats["kda_resets_per_row"]) == len(ends) / 2
    assert float(stats["kda_state_bytes_per_row"]) == 2 * 4 * (
        4 * 8 * 8 + 3 * 3 * 4 * 8
    )
    assert float(stats["attention_latent_applications"]) == 1
    assert "attention_latent_fused_applications" not in stats  # toy widths
    assert float(stats["moe_shared_applications"]) == 2
    assert float(stats["moe_assignments"]) == 2 * 3 * T * B
    # The perturbed `dt_bias` spreads the decays over the gate's range.
    if model.safe_gate:
        assert -5.0 <= float(stats["kda_log_decay_min"]) < -4.9
        assert 0.05 < float(stats["kda_gate_at_floor_share"]) < 0.9
        # Every layer's mean, summed: two layers inside (-5, 0).
        assert -2 * 5.0 < float(stats["kda_log_decay_mean"]) < -0.5
    else:
        assert float(stats["kda_log_decay_min"]) < -5.0
    # Two of four groups chosen: the fullest holds a quarter to all.
    assert 0.25 <= float(stats["router_group_load_max_share"]) <= 1.0
    held = model.held_experts()
    assert float(stats["experts_held_rows_mean"]) == pytest.approx(
        float(stats["moe_held_assignments"]) / 2 / held[1]
        if held else 3 * T * B / 16, rel=0.5,
    )
    steps = scaffold.reference_bias_steps(model)(params, batch, state)
    for layer, want in zip((3, 5), steps):
        got = stats[learner_lib.PARAM_STEPS_KEY][f"block_{layer}"]["moe"][
            "e_score_correction_bias"
        ]
        np.testing.assert_array_equal(got, want)
        assert set(np.unique(np.abs(want))) <= {0.0, np.float32(0.001)}


def _recurrence(q, k, v, g, beta, state, done):
    """KDA a step at a time, float64 on the host: the module's header,
    term for term."""
    q, k, v, g, beta, S = (
        np.asarray(a, np.float64) for a in (q, k, v, g, beta, state)
    )
    rows, steps = q.shape[:2]
    out = np.zeros(v.shape)
    for t in range(steps):
        S = np.where(np.asarray(done)[:, t, None, None, None], 0.0, S)
        S = np.exp(g[:, t])[..., None] * S  # [B, H, Dk, Dv]
        read = np.einsum("bhkv,bhk->bhv", S, k[:, t])
        u = beta[:, t, :, None] * (v[:, t] - read)
        S = S + np.einsum("bhk,bhv->bhkv", k[:, t], u)
        out[:, t] = np.einsum("bhkv,bhk->bhv", S, q[:, t])
    return out, S


def _scan_inputs(seed, rows, steps, H, D, g_range, ends=0.1):
    rng = np.random.default_rng(seed)

    def unit(shape):
        x = rng.standard_normal(shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    low, high = g_range
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        unit((rows, steps, H, D)) * D ** -0.5, unit((rows, steps, H, D)),
        rng.standard_normal((rows, steps, H, D)),
        rng.uniform(low, high, (rows, steps, H, D)),
        rng.uniform(0.05, 0.95, (rows, steps, H)),
        rng.standard_normal((rows, H, D, D)),
    )) + (jnp.asarray(rng.random((rows, steps)) < ends),)


@pytest.mark.parametrize(
    "steps, chunk, sub, g_range",
    [
        (64, 64, 16, (-5.0, -4.9999)),  # every channel at the floor
        (128, 64, 16, (-5.0, 0.0)),  # the cell's chunk, two of them
        (50, 16, 4, (-5.0, 0.0)),  # padded, ends in every position
        (12, 16, 16, (-3.0, 0.0)),  # one chunk shorter than `chunk`
        (24, 8, 3, (-1.0, 0.0)),  # a sub-block that does not divide
        (1, 64, 16, (-5.0, 0.0)),  # acting: the recurrence
    ],
    ids=["floor", "two-chunks", "padded", "short", "whole-chunk", "T=1"],
)
def test_the_chunked_scan_is_the_recurrence(steps, chunk, sub, g_range):
    """`kda_scan` against the recurrence a step at a time, outputs, the
    state handed on and (where the unroll is one) finite gradients;
    with every channel at -5 for 64 steps a chunk's decays span e^-320,
    which only the sub-blocks keep inside float32."""
    inputs = _scan_inputs(steps, 2, steps, 2, 8, g_range)
    scan = jax.jit(
        lambda *a: ling3.kda_scan(*a, chunk, sub)
    )
    o, last = scan(*inputs)
    want_o, want_last = _recurrence(*inputs)
    assert np.all(np.isfinite(o)) and np.all(np.isfinite(last))
    scale = float(np.max(np.abs(want_o)))
    np.testing.assert_allclose(o, want_o, rtol=0, atol=2e-5 * scale)
    np.testing.assert_allclose(
        last, want_last, rtol=0,
        atol=2e-5 * max(float(np.max(np.abs(want_last))), 1e-3),
    )
    if steps > 1:
        gradients = jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(ling3.kda_scan(
                *a, inputs[-1], chunk, sub
            )[0])),
            argnums=tuple(range(6)),
        ))
        grads = gradients(*inputs[:-1])
        assert all(np.all(np.isfinite(x)) for x in grads)
        assert all(np.any(x) for x in grads)


def test_without_sub_blocks_the_floor_overflows():
    """What the sub-blocks are for: the same chunk of 64 steps at -5 as
    ONE block measures its columns from the chunk's first step, e^315
    at the last, and is not finite."""
    inputs = _scan_inputs(64, 2, 64, 2, 8, (-5.0, -4.9999))
    scan = jax.jit(lambda *a: ling3.kda_scan(*a, 64, 64))
    o, _ = scan(*inputs)
    assert not np.all(np.isfinite(o))


@pytest.mark.parametrize("safe", [True, False], ids=["bounded", "unbounded"])
def test_the_gate_is_a_decay_a_channel(safe):
    a = jnp.asarray(np.random.default_rng(0).normal(0, 4, (3, 5, 2, 8)))
    A_log = jnp.log(jnp.asarray([1.0, 9.0]))
    gate = jax.jit(lambda a: ling3.kda_gate(a, A_log, -5.0, safe))
    g = gate(a)
    assert g.shape == a.shape and g.dtype == jnp.float32
    if safe:
        # The logistic as a tanh: no overflow at 9 x 16.
        want = -2.5 * (1.0 + np.tanh(
            0.5 * np.asarray([1.0, 9.0])[:, None] * np.asarray(a, np.float64)
        ))
        assert np.all(g > -5.0 - 1e-6) and np.all(g <= 0.0)
        assert float(jnp.min(g)) < -4.99
    else:
        want = -np.asarray([1.0, 9.0])[:, None] * np.logaddexp(0.0, a)
        assert float(jnp.min(g)) < -5.0
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)


def _one_pass_intra(q, k, G, sub):
    rounded = [x.astype(jnp.bfloat16).astype(jnp.float32) for x in (q, k)]
    return _KDA_INTRA(*rounded, G, sub)


_KDA_INTRA = ling3.kda_intra
# fault: the names of models/ling3.py it replaces.
FAULTS = {
    "one_bf16_pass_in_the_projections": {
        "nn": scaffold.Through(nn, Dense=_one_pass_dense),
    },
    "one_bf16_pass_in_the_chunk_matrices": {"kda_intra": _one_pass_intra},
    "output_gate_left_out": {
        "nn": scaffold.Through(nn, sigmoid=jnp.ones_like),
    },
    "keys_not_normalised": {"l2_normalise": lambda x: x},
    "decay_a_head_not_a_channel": {
        "kda_gate": lambda a, A_log, low, safe: jnp.broadcast_to(
            jnp.mean(
                _KDA_GATE(a, A_log, low, safe), axis=-1, keepdims=True
            ), a.shape,
        ),
    },
    "state_not_reset": {
        "kda_scan": lambda q, k, v, g, beta, state, done, chunk, sub: (
            _KDA_SCAN(
                q, k, v, g, beta, state, jnp.zeros_like(done), chunk, sub
            )
        ),
    },
}
_KDA_GATE, _KDA_SCAN = ling3.kda_gate, ling3.kda_scan


@pytest.mark.parametrize("fault", [*FAULTS, "head_gate_left_out", "one_group"])
def test_the_tolerance_sees(fault, monkeypatch):
    """What `RTOL` is for, and the reference seeing a fault planted in
    the program: one bfloat16 pass where the family states float32 (in
    the projections; in the chunk's sub-block products), an output gate
    left out (KDA's and, apart, the latent layer's), keys not
    normalised, ONE decay a head where the row has one a channel, a
    state not reset at an episode's end, a router that chooses without
    its groups: each moves the loss (over its scale) or the logits past
    twenty times the tolerance."""
    model, params = scaffold.build("ling3")
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(7, ENDS, t=T)
    for name, replaced in FAULTS.get(fault, {}).items():
        monkeypatch.setattr(ling3, name, replaced)
    faulty_model = model
    if fault == "head_gate_left_out":
        from torchbeast_tpu.models import kanana2

        monkeypatch.setattr(
            kanana2, "nn", scaffold.Through(nn, sigmoid=jnp.ones_like)
        )
    elif fault == "one_group":
        # The reference reads the groups from its config, not the model.
        faulty_model = model.clone(n_group=1, topk_group=1)
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)

    # A trace of its own: the fault is read when the block is traced.
    def faulty(params, batch, state):
        out, _ = faulty_model.apply(params, batch, state, sample_action=False)
        loss, _ = learner_lib.compute_loss(
            faulty_model, params, batch, state, hp
        )
        return out.policy_logits, loss

    run = jax.jit(faulty)
    logits, loss = run(params, batch, state)
    ref_logits, _, _, _ = scaffold.reference_forward(model)(
        params, batch, state
    )
    ref_loss, scale, _ = scaffold.reference_loss_and_grads(model)(
        params, batch, state
    )
    off = max(
        abs(float(loss) - float(ref_loss)) / float(scale),
        float(jnp.max(jnp.abs(logits - ref_logits)))
        / float(jnp.max(jnp.abs(ref_logits))),
    )
    assert off > 20 * RTOL, off


@pytest.mark.parametrize("unrolls", [0, 2], ids=["empty", "warm"])
def test_batch_forward_equals_stepwise_acting_through_the_carried_states(
    unrolls
):
    """The learner's [T, B] forward (KDA in chunks of 4 from sub-blocks
    of 2, the convolution as shifted adds over the unroll, the latent
    layer's cache leg absorbed) and the actor's T=1 forwards through
    the matrix states, the conv tails and the rolling latent cache (5
    slots: the 11 steps evict on the way) give the same logits and leave
    the same states, across episode ends inside chunks and sub-blocks."""
    model, params = scaffold.build("ling3")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, ENDS, t=T)
    )


@pytest.mark.parametrize("via", ["reset", "rebuild"])
def test_stepwise_acting_through_the_state_table_equals_the_batch_forward(
    via
):
    """Three actors' slots in a `DeviceStateTable` whose rows hold BOTH
    kinds of state: two KDA layers' matrix state and conv tail (S [4,
    1, 8, 8], tail [3, 1, 96]) and the latent layer's window (latent
    [M, 1, 1, 12], rope key [M, 1, 1, 4], valid [M, 1]). The rows arrive
    in another order every step and episodes end on the way; every
    step's logits equal the batch forward's and the table ends with
    what that forward leaves; reset and rebuild bring back zeros."""
    model, params = scaffold.build("ling3")
    kda = [(4, 1, 8, 8), (3, 1, 96)]
    shapes = [kda, kda, [(M, 1, 1, 12), (M, 1, 1, 4), (M, 1)]]
    table = scaffold.assert_state_table_acting_equals_the_batch_forward(
        model, params,
        scaffold.inputs(4, [(3, 2), (4, 2), (1, 0)], t=6, rows=3),
        shapes=shapes,
    )
    if via == "reset":
        table.reset([1])
        assert all(
            np.any(leaf) for item in table.read_slot(0) for leaf in item
        )
    else:
        table.poison()
        table.rebuild()
    held = table.read_slot(1)
    assert [[np.shape(leaf) for leaf in item] for item in held] == shapes
    assert not any(np.any(leaf) for item in held for leaf in item)


def test_one_group_is_the_selection_as_it_was():
    """`n_group` 1 traces nothing: the layer lowers to the same text as
    the layer that names no group (Kanana-2's, Xing4's, Trinity's and
    LFM2's router), bit for bit, whatever `topk_group` says."""
    layer, x, params = scaffold.expert_layer("kanana2")
    assert (layer.n_group, layer.topk_group) == (1, 1)

    def lowered(layer):
        return jax.jit(
            lambda p, x: layer.apply(p, x)
        ).lower(params, x).as_text()

    text = lowered(layer)
    assert "router_groups" not in text
    assert lowered(layer.clone(n_group=1, topk_group=1)) == text
    grouped = lowered(layer.clone(n_group=4, topk_group=2))
    assert "router_groups" in lowered(
        layer.clone(n_group=4, topk_group=2)
    ) or grouped != text


@pytest.mark.parametrize(
    "E, groups, best, K", [(512, 8, 4, 8), (16, 4, 2, 3), (12, 3, 1, 2)]
)
def test_the_groups_against_a_loop(E, groups, best, K):
    """`moe.within_best_groups` and the top-k after it against a loop
    over tokens and groups: a group's score the sum of its two largest,
    the `best` groups with the largest, the K largest entries inside
    them; nothing outside them is chosen even where every entry inside
    is negative."""
    rng = np.random.default_rng(E)
    choice = rng.standard_normal((40, E)).astype(np.float32)
    choice[:5] -= 10.0  # tokens whose every score + bias is negative
    within = jax.jit(lambda c: moe.within_best_groups(c, groups, best))
    kept = within(jnp.asarray(choice))
    _, idx = jax.lax.top_k(kept, K)
    size = E // groups
    for t in range(choice.shape[0]):
        scores = [
            np.sort(choice[t, g * size : (g + 1) * size])[-2:].sum()
            for g in range(groups)
        ]
        chosen = np.argsort(scores)[-best:]
        inside = np.concatenate(
            [np.arange(g * size, (g + 1) * size) for g in sorted(chosen)]
        )
        want = inside[np.argsort(-choice[t, inside], kind="stable")[:K]]
        assert sorted(np.asarray(idx[t])) == sorted(want), t
        outside = np.setdiff1d(np.arange(E), inside)
        assert np.all(np.isneginf(np.asarray(kept)[t, outside]))
        np.testing.assert_array_equal(
            np.asarray(kept)[t, inside], choice[t, inside]
        )


def test_a_share_is_whole_groups_or_a_group_is_whole_shares():
    # The cell's: 64 shares of 8 cut each of 8 groups of 64 into eight.
    assert moe.held_experts((0, 64), 512, 8) == (0, 8)
    assert moe.held_experts((63, 64), 512, 8) == (504, 8)
    assert moe.held_experts((1, 4), 512, 8) == (128, 128)  # two groups
    assert moe.held_experts((0, 1), 512, 8) is None
    assert moe.held_experts((1, 3), 12) == (4, 4)  # no groups: as it was
    with pytest.raises(ValueError, match="splits the router's 2 groups of 6"):
        moe.held_experts((0, 3), 12, 2)
    with pytest.raises(ValueError, match="0 <= i < n"):
        moe.held_experts((4, 4), 512, 8)
    with pytest.raises(ValueError, match="groups of two or more"):
        layer, x, params = scaffold.expert_layer("ling3", n_group=5)


def test_layers_follow_the_group_and_the_state_holds_what_they_carry():
    model, params = scaffold.build("ling3")
    carried = Recurrent(((4, 8, 8), (3, 3 * 4 * 8)))
    window = (M, 1, (12, 4))
    # A layer is its mixer's entry, then its feed-forward part's.
    assert model.layer_caches() == (
        carried, None, carried, None, window, None
    )
    state = model.initial_state(3)
    assert [[leaf.shape for leaf in item] for item in state] == [
        [(4, 3, 8, 8), (3, 3, 96)], [(4, 3, 8, 8), (3, 3, 96)],
        [(M, 3, 1, 12), (M, 3, 1, 4), (M, 3)],
    ]
    blocks = params["params"]
    for kda in ("block_0", "block_2"):
        assert sorted(blocks[kda]) == [
            "A_log", "conv_kernel", "dt_bias", "gate_norm", "in_proj",
            "in_proj_bg", "norm", "out_proj",
        ]
        # q, k, v and the decay's f, 4 heads of 8 each; beta and gate.
        assert blocks[kda]["in_proj"]["kernel"].shape == (32, 4 * 32)
        assert blocks[kda]["in_proj_bg"]["kernel"].shape == (32, 2 * 4)
        assert blocks[kda]["conv_kernel"].shape == (4, 96)  # no bias
        assert blocks[kda]["A_log"].shape == (4,)  # a head
        assert blocks[kda]["dt_bias"].shape == (32,)  # a channel
        assert blocks[kda]["gate_norm"].shape == (8,)  # one for all heads
    assert sorted(blocks["block_4"]) == [
        "attn_norm", "head_gate", "kv_a", "kv_a_norm", "kv_b", "o", "q",
    ]
    assert blocks["block_4"]["head_gate"]["kernel"].shape == (32, 4)
    assert sorted(blocks["block_1"]) == ["down", "gate", "norm", "up"]
    assert sorted(blocks["block_3"]) == sorted(blocks["block_5"]) == [
        "moe", "norm",
    ]
    assert sorted(blocks["block_3"]["moe"]) == [
        "e_score_correction_bias", "router", "shared_down", "shared_gate",
        "shared_up", "w_down", "w_gate", "w_up",
    ]
    # As initialised (the scaffold perturbs them): norms at one, A in
    # (1, 16), softplus(dt_bias) in [0.001, 0.1], the bias at zero.
    fresh = scaffold.init_params(model, scaffold.inputs(0, t=T))["params"]
    assert np.all(np.asarray(fresh["block_0"]["norm"]["scale"]) == 1)
    assert np.all(np.asarray(fresh["block_0"]["gate_norm"]) == 1)
    A_init = np.exp(fresh["block_0"]["A_log"])
    assert np.all(A_init >= 1) and np.all(A_init <= 16)
    step = jax.nn.softplus(fresh["block_0"]["dt_bias"])
    assert np.all(step >= 0.001 - 1e-6) and np.all(step <= 0.1 + 1e-6)
    assert not np.any(fresh["block_3"]["moe"]["e_score_correction_bias"])
    # The published group: five KDA layers, then latent attention; both
    # leading layers dense where all 42 are asked for, one in a cut.
    published = dict(SMALL, layer_group_size=6, dense_layers=2)
    whole = Ling3Net(
        num_actions=A, **dict(published, num_layers=12, published_layers=12)
    )
    assert [whole.is_latent(i) for i in range(12)] == (
        [False] * 5 + [True]
    ) * 2
    assert whole.leading_dense_layers() == 2
    cut = Ling3Net(
        num_actions=A, **dict(published, num_layers=7, published_layers=42)
    )
    assert [cut.is_latent(i) for i in range(7)] == [False] * 6 + [True]
    assert cut.leading_dense_layers() == 1
    assert [type(entry) for entry in cut.layer_caches()[::2]] == (
        [Recurrent] * 6 + [tuple]
    )
    assert cut.layer_caches()[1::2] == (None,) * 7
    for layers in (6, 8, 42 + 6):
        with pytest.raises(ValueError, match="whole periods of 6"):
            Ling3Net(num_actions=A, **dict(
                published, num_layers=layers, published_layers=42
            ))


def test_the_new_scopes_are_in_the_lowered_update():
    model, params = scaffold.build("ling3")
    batch = scaffold.learner_batch(1, ENDS, t=T)
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    text = jax.jit(jax.grad(
        lambda p: learner_lib.compute_loss(
            model, p, batch, model.initial_state(B), hp
        )[0]
    )).lower(params).as_text(debug_info=True)
    for scope in (
        "kda_in_proj", "kda_conv", "kda_gate", "kda_scan/kda_intra",
        "kda_scan/kda_intra/kda_solve", "kda_scan/kda_states",
        "kda_scan/kda_inter", "kda_out", "attention_latent",
        "attention_latent/latent_head_gate", "mlp", "moe_route",
        "moe_route/router_groups", "moe_dispatch", "moe_experts",
        "moe_combine", "moe_shared",
    ):
        assert scope in text, scope


# sha256 (first 16 hex digits) of the toy updates' lowered text at commit
# 4b3306e, PR 67's, the parent of the PR that gave `DroplessMoE` its
# groups, `_Kanana2Block` its `head_gate` and ops/delta_rule.py its
# `hand_on`: made by `_lowered_update` below in a `git archive` of that
# commit. A later PR that changes one of these programs on purpose
# computes its own.
PARENTS = {
    # The scalar delta rule, the solve and the conv it shares with KDA.
    ("qwen3next", (1, 4)): "1ff1768b0aeab927",
    # The latent block whose attention part gained the gate, and the
    # sigmoid routers that name no group.
    ("kanana2", (1, 8)): "c4a5662976b35171",
    ("xing4", (1, 8)): "d384c83c0deea8be",
    ("trinity", (0, 8)): "073ef5a2f7a92866",
    ("lfm2", (1, 4)): "b48c7c682ce88d84",
    # This family's own at commit e7a654d, PR 68's: the parent of the PR
    # that gave ops/delta_rule.py the cells of W, U, Kd and A, which
    # Qwen3-Next's toy widths do not take and this family not at all
    # (PERF.md section 6, PR 69): both as they were, op for op.
    ("ling3", (0, 8)): "24b5e154d419546c",
}


def _lowered_update(family, expert_share):
    """The toy family's update step as `learner.make_update_step`
    lowers it, inner functions' counters stripped (two lowerings in one
    process differ in them), as tests/test_attention_scale.py's."""
    import re

    model, params = scaffold.build(family, expert_share=expert_share)
    t = scaffold.FAMILIES[family].t
    hp = learner_lib.HParams(batch_size=B, unroll_length=t - 1)
    optimizer = learner_lib.make_optimizer(hp)
    text = learner_lib.make_update_step(model, optimizer, hp).lower(
        params, optimizer.init(params),
        scaffold.learner_batch(9, [(1, 1)], t=t), model.initial_state(B),
    ).as_text()
    return re.sub(r"(@[A-Za-z_][A-Za-z_0-9.]*?)_\d+\b", r"\1", text)


@pytest.mark.parametrize(
    "family,expert_share", list(PARENTS), ids=lambda v: str(v)
)
def test_lowered_updates_are_the_parents(family, expert_share):
    """The families that share code with this one and name none of what
    it added lower to the parent's text, byte for byte: one group is
    the selection as it was, no head gate is traced, the scalar delta
    rule is as it was."""
    import hashlib

    text = _lowered_update(family, expert_share)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS[
        (family, expert_share)
    ]


@pytest.mark.parametrize(
    "steps, precision, ends",
    [(256, "high", 0.1), (200, "highest", 0.1), (64, "high", 0.0)],
    ids=["the-cell's", "padded-six-passes", "one-chunk"],
)
def test_the_kernels_pass_is_the_jax_numpy_pass(
    steps, precision, ends, monkeypatch
):
    """`kda_scan` at widths ops/delta_rule.py's kernels take (128 x
    128, chunks of 64), the kernels interpreted, against the same scan
    through `_pass_in_hbm`: outputs, the state handed on and every
    gradient, the log-decays' through the hand-on a key channel among
    them, with episode ends inside chunks and a state that enters."""
    from torchbeast_tpu.ops import delta_rule

    inputs = _scan_inputs(steps, 2, steps, 2, 128, (-5.0, 0.0), ends)
    assert delta_rule.kernels_apply(steps, 64, 128, 128)

    def total(kernels):
        def scalar(*args):
            with monkeypatch.context() as patched:
                if not kernels:
                    patched.setattr(
                        delta_rule, "kernels_apply", lambda *shape: False
                    )
                with jax.default_matmul_precision(precision):
                    o, last = ling3.kda_scan(*args, inputs[-1], 64, 16)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(last)), (o, last)

        return jax.jit(jax.value_and_grad(
            scalar, argnums=tuple(range(6)), has_aux=True
        ))

    (_, (o, last)), grads = total(True)(*inputs[:-1])
    (_, (want_o, want_last)), want_grads = total(False)(*inputs[:-1])
    # Three bf16 passes against float32 on the CPU: 2e-5; six: 2e-6.
    tol = 2e-5 if precision == "high" else 2e-6
    for got, want in ((o, want_o), (last, want_last), *zip(grads, want_grads)):
        np.testing.assert_allclose(
            got, want, rtol=0, atol=tol * float(jnp.max(jnp.abs(want)))
        )
    assert all(np.any(g) for g in grads)
