"""The `nemotron3` family (models/nemotron3.py; layers that are one
mixer or one feed-forward part, and states of three kinds side by side,
in models/transformer.py; experts that are not gated and live in a
latent in models/moe.py DroplessMoE): against the plain reference on
seeded weights (loss, gradients, new states), the chunked scan against
the step-by-step recurrence with episode ends inside a chunk, batch
forward against stepwise acting through the carried states and through
the state table, and the shares of the mixers' heads adding up to the
uncut layer (those of the routed experts: an id of
tests/test_families_shares.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench.reference import nemotron3_policy as reference
from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import Nemotron3Net, moe, nemotron3
from torchbeast_tpu.models.transformer import Recurrent

T, B, A = scaffold.FAMILIES["nemotron3"].t, scaffold.B, scaffold.A
# The shrunken `PUBLISHED` (tests/family_scaffold.py): one attention
# layer, one latent MoE layer, one Mamba-2 layer scanned in chunks of 4
# steps: the 11 steps of an unroll are two whole chunks and one padded.
SMALL = scaffold.FAMILIES["nemotron3"].small
M = SMALL["memory_len"]
# On the CPU both sides compute in float32 at full precision and differ
# by the order of their sums.
RTOL = ATOL = 2e-5

# Episode ends at a chunk's first step (4), at its last (7), and twice in
# one chunk (8 and 10), in one row; the other row ends one on step 0,
# where the state the unroll starts from is dropped whole.
ENDS = [(4, 0), (7, 0), (8, 0), (10, 0), (0, 1), (5, 1)]


@pytest.mark.parametrize(
    "expert_share,mixer_share",
    [((0, 1), (0, 1)), ((1, 8), (3, 4))],
    ids=["everything-held", "experts-1-of-8-mixers-3-of-4"],
)
def test_family_agrees_with_the_reference(expert_share, mixer_share):
    model, params = scaffold.build(
        "nemotron3", expert_share=expert_share, mixer_share=mixer_share
    )
    state = scaffold.warm_state(model, params, seed=5)
    assert all(np.any(leaf) for leaf in jax.tree_util.tree_leaves(state))
    assert len(jax.tree_util.tree_leaves(state)) == 5
    batch = scaffold.learner_batch(7, ENDS[:4] + [(5, 1)], t=T)
    stats, grads, ref_grads, _ = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    # Every parameter of the Mamba layer takes a gradient.
    for name, leaf in grads["params"]["block_2"].items():
        assert np.any(jax.tree_util.tree_leaves(leaf)[0]), name
    # No auxiliary loss; the bias takes no gradient on either side, and
    # the step its layer sows is the reference's rule.
    assert float(stats["aux_loss"]) == 0.0
    for tree in (grads, ref_grads):
        assert not np.any(
            tree["params"]["block_1"]["moe"]["e_score_correction_bias"]
        )
    (want,) = scaffold.reference_bias_steps(model)(params, batch, state)
    got = stats[learner_lib.PARAM_STEPS_KEY]["block_1"]["moe"][
        "e_score_correction_bias"
    ]
    np.testing.assert_array_equal(got, want)
    # What the layers say of themselves.
    assert float(stats["ssm_applications"]) == 1
    assert float(stats["ssm_chunks"]) == 3  # 11 steps in chunks of 4
    assert float(stats["ssm_resets_per_row"]) == 2.5  # 4 and 1, two rows
    heads, groups, _, _ = model.held_mixers()
    assert float(stats["ssm_state_bytes_per_row"]) == 4 * (
        heads * 4 * 6 + 3 * (heads * 4 + 2 * groups * 6)
    )
    assert float(stats["moe_latent_applications"]) == 1
    assert float(stats["moe_shared_applications"]) == 1
    assert float(stats["moe_assignments"]) == 3 * T * B
    assert "attention_fused_applications" not in stats  # toy widths
    if expert_share == (0, 1):
        assert "moe_held_assignments" not in stats
        assert "moe_window_rows" not in stats
    else:
        assert 0 < float(stats["moe_held_assignments"]) < 3 * T * B
        # Two held under three chosen: a window, of one rung at 22
        # tokens (a rung is never under the kernels' 256-row tile).
        assert float(stats["moe_window_rows"]) == 2 * T * B
        assert float(stats["moe_window_short_applications"]) == 0


@pytest.mark.parametrize(
    "to_held, sweeps", [(None, 1), (0, 0), (3.0, 3)],
    ids=["as-routed", "no-row", "every-token-on-both-held-experts"],
)
def test_update_stats_say_how_far_the_window_was_swept(to_held, sweeps):
    """Two of 32 experts held under three a token, 352 tokens: the
    window of 704 sorted rows has rungs of 256 (`moe.window_rungs`),
    and the update's stats carry the rows the kernels swept and the
    layers that needed one rung alone, as the held experts' sizes
    imply: one rung as initialised, none with the held experts at
    every token's bottom, three (`moe_window_short_applications` 0)
    with both at every token's top."""
    rows = 32
    model, params = scaffold.build(
        "nemotron3", expert_share=(1, 16), num_experts=32
    )
    assert moe.window_rungs(T * rows, 3, 2, 32) == (256, 2 * T * rows)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    if to_held is not None:
        bias = bias.at[2:4].set(to_held - 1.5)
    inner = dict(params["params"])
    block = dict(inner["block_1"])
    block["moe"] = dict(block["moe"], e_score_correction_bias=bias)
    params = {"params": dict(inner, block_1=block)}
    stats = scaffold.forward_stats(model, params, rows, ENDS, T)
    held = float(stats["moe_held_assignments"])
    assert held == {None: held, 0: 0, 3.0: 2 * T * rows}[to_held]
    assert float(stats["moe_window_rows"]) == 256 * sweeps
    assert sweeps == -(-held // 256)
    assert float(stats["moe_window_short_applications"]) == (sweeps <= 1)


def _recurrence(x, dt, A, B_in, C_in, state, done):
    """The Mamba-2 recurrence a step at a time, by its definition."""
    per = x.shape[2] // B_in.shape[2]

    def step(h, inputs):
        x_t, dt_t, B_t, C_t, done_t = inputs
        h = jnp.where(done_t[:, None, None, None], 0.0, h)
        B_t, C_t = (jnp.repeat(a, per, axis=1) for a in (B_t, C_t))
        h = jnp.exp(dt_t * A)[..., None, None] * h + jnp.einsum(
            "bh,bhp,bhn->bhpn", dt_t, x_t, B_t
        )
        return h, jnp.einsum("bhpn,bhn->bhp", h, C_t)

    h, y = jax.lax.scan(step, state, jax.tree_util.tree_map(
        lambda a: jnp.swapaxes(a, 0, 1), (x, dt, B_in, C_in, done)
    ))
    return jnp.swapaxes(y, 0, 1), h


@pytest.mark.parametrize("steps,chunk", [(12, 4), (11, 4), (1, 4), (7, 128)])
def test_chunked_scan_equals_the_recurrence_with_ends_inside_a_chunk(
    steps, chunk
):
    """Value and gradients (with respect to every input and the state
    the unroll starts from), with `done` at a chunk's first step, at its
    last, twice in one chunk, and at step 0."""
    rows, H, P, G, N = 2, 4, 3, 2, 5
    keys = jax.random.split(jax.random.PRNGKey(steps), 6)
    x = jax.random.normal(keys[0], (rows, steps, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (rows, steps, H)))
    A = -jnp.exp(jax.random.normal(keys[2], (H,)))
    B_in = jax.random.normal(keys[3], (rows, steps, G, N))
    C_in = jax.random.normal(keys[4], (rows, steps, G, N))
    state = jax.random.normal(keys[5], (rows, H, P, N))
    done = np.zeros((rows, steps), bool)
    for step, row in ENDS:
        if step < steps:
            done[row, step] = True
    done = jnp.asarray(done)

    def total(f):
        def scalar(x, dt, A, B_in, C_in, state):
            y, last = f(x, dt, A, B_in, C_in, state)
            return jnp.sum(jnp.sin(y)) + jnp.sum(jnp.cos(last))

        return jax.jit(jax.value_and_grad(scalar, argnums=range(6)))

    def chunked(x, dt, A, B_in, C_in, state):
        return nemotron3.ssd_scan(x, dt, A, B_in, C_in, state, done, chunk)

    def stepwise(x, dt, A, B_in, C_in, state):
        return _recurrence(x, dt, A, B_in, C_in, state, done)

    args = (x, dt, A, B_in, C_in, state)
    jitted = jax.jit(chunked)
    y, last = jitted(*args)
    jitted = jax.jit(stepwise)
    want_y, want_last = jitted(*args)
    np.testing.assert_allclose(y, want_y, RTOL, ATOL)
    np.testing.assert_allclose(last, want_last, RTOL, ATOL)
    value, grads = total(chunked)(*args)
    want_value, want_grads = total(stepwise)(*args)
    assert float(value) == pytest.approx(float(want_value), rel=1e-5)
    for got, want in zip(grads, want_grads):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    if steps > 1:
        # The state the unroll starts from reaches row 0 (no end at its
        # first step) and not row 1 (`done` at step 0 drops it).
        assert np.any(grads[5][0]) and not np.any(grads[5][1])
        # A scan that did not reset is another function.
        free, _ = nemotron3.ssd_scan(
            *args, jnp.zeros_like(done), chunk
        )
        assert float(jnp.max(jnp.abs(free - want_y))) > 1e-2


@pytest.mark.parametrize("unrolls", [0, 2], ids=["empty", "warm"])
def test_batch_forward_equals_stepwise_acting_through_the_carried_states(
    unrolls
):
    """The learner's [T, B] forward (the scan in chunks of 4, the
    convolution as shifted adds over the unroll, attention over [cache;
    unroll]) and the actor's T=1 forwards through the Mamba state, the
    conv tail and the rolling cache give the same logits and leave the
    same states, across episode ends inside a chunk."""
    model, params = scaffold.build("nemotron3")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, ENDS, t=T)
    )


@pytest.mark.parametrize("via", ["reset", "rebuild"])
def test_stepwise_acting_through_the_state_table_equals_the_batch_forward(
    via
):
    """Three actors' slots in a `DeviceStateTable` whose rows hold BOTH
    kinds of state: the attention layer's window (k, v [M, 1, 2, 8],
    valid [M, 1]) and the Mamba layer's (h [8, 1, 4, 6], tail
    [3, 1, 80]); the MoE layer has no item. The rows arrive in another
    order every step and episodes end on the way; every step's logits
    equal the batch forward's and the table ends with what that forward
    leaves; reset and rebuild bring back zeros of every shape."""
    model, params = scaffold.build("nemotron3")
    shapes = [
        [(M, 1, 2, 8), (M, 1, 2, 8), (M, 1)], [(8, 1, 4, 6), (3, 1, 80)],
    ]
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


def _mamba_share(p, state, share, of):
    """Share `share` of `of` of an uncut Mamba layer's weights and
    state: its heads with their groups."""
    H, P, G, N = 8, 4, 4, 6
    inner = H * P
    heads = slice(share * H // of, (share + 1) * H // of)
    wide = slice(share * inner // of, (share + 1) * inner // of)
    group = slice(share * G * N // of, (share + 1) * G * N // of)

    def channels(a, axis):  # [x | B | C] along `axis`
        x, b, c = jnp.split(a, [inner, inner + G * N], axis=axis)
        index = [slice(None)] * a.ndim
        parts = []
        for part, cut in ((x, wide), (b, group), (c, group)):
            index[axis] = cut
            parts.append(part[tuple(index)])
        return jnp.concatenate(parts, axis=axis)

    kernel = p["in_proj"]["kernel"]
    z, xBC, dt = jnp.split(kernel, [inner, 2 * inner + 2 * G * N], axis=1)
    cut = dict(
        p,
        in_proj={"kernel": jnp.concatenate(
            [z[:, wide], channels(xBC, 1), dt[:, heads]], axis=1
        )},
        conv_kernel=channels(p["conv_kernel"], 1),
        conv_bias=channels(p["conv_bias"], 0),
        dt_bias=p["dt_bias"][heads], A_log=p["A_log"][heads],
        D=p["D"][heads], gate_norm=p["gate_norm"][wide],
        out_proj={"kernel": p["out_proj"]["kernel"][wide]},
    )
    return cut, (state[0][heads], channels(state[1], 2))


def _attention_share(p, cache, share, of):
    Hq, Hkv, hd = 4, 2, 8
    q = slice(share * Hq * hd // of, (share + 1) * Hq * hd // of)
    # The key/value head the share's query heads read.
    head = share * Hkv // of
    kv = slice(head * hd, (head + 1) * hd)
    cut = dict(
        p, q={"kernel": p["q"]["kernel"][:, q]},
        k={"kernel": p["k"]["kernel"][:, kv]},
        v={"kernel": p["v"]["kernel"][:, kv]},
        o={"kernel": p["o"]["kernel"][q]},
    )
    return cut, tuple(c[:, :, head : head + 1] for c in cache)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_four_mixer_shares_add_up_to_the_uncut_mixers(side):
    """The test that ties the mixer share to the model: each of the
    four shares, holding its quarter of the uncut layer's heads (a
    Mamba head with its B/C group and its part of the convolution and
    the grouped norm; a query head with the key/value head it reads),
    gives its part of `out_proj` / `o`; the four parts add up to the
    uncut layer's. Warm states, episode ends inside a chunk; on the
    program's blocks and on the reference's functions."""
    model, params = scaffold.build("nemotron3")
    state = scaffold.warm_state(model, params, seed=3)
    done = scaffold.inputs(6, ENDS, t=T)["done"]
    x = jax.random.normal(jax.random.PRNGKey(2), (B, T, 32))
    p = params["params"]
    config = scaffold.reference_config(model)
    window, carried = state
    valid = window[2]
    allowed = reference._may_attend(done, valid, M)
    cache_mask, seq_mask = allowed[..., :M] > 0, allowed[..., M:] > 0

    def mamba(p, carried, of):
        if side == "reference":
            h = reference._rmsnorm(x, p["norm"], 1e-5).transpose(1, 0, 2)
            jitted = jax.jit(lambda h, p, carried: reference._mamba(
                h, done, p, carried,
                dict(config, mamba_num_heads=8 // of, n_groups=4 // of),
            ))
            out, _ = jitted(h, p, carried)
            return out.transpose(1, 0, 2)
        block = nemotron3._MambaBlock(
            d_model=32, heads=8 // of, head_dim=4, groups=4 // of,
            state_size=6, conv_kernel=4, chunk_size=4, rms_norm_eps=1e-5,
            time_step=(0.001, 0.1, 0.0001),
        )
        jitted = jax.jit(block.apply)
        return jitted({"params": p}, x, carried, done.T)[0] - x

    def attend(p, cache, of):
        heads = dict(
            num_attention_heads=4 // of, num_key_value_heads=max(1, 2 // of)
        )
        if side == "reference":
            h = reference._rmsnorm(x, p["norm"], 1e-5)
            return reference._attention(
                h, p, tuple(c.transpose(1, 0, 2, 3) for c in cache),
                allowed, dict(config, **heads),
            )[0]
        block = nemotron3._AttentionBlock(
            d_model=32, num_heads=heads["num_attention_heads"],
            kv_heads=heads["num_key_value_heads"], head_dim=8, memory_len=M,
            rms_norm_eps=1e-5,
        )
        jitted = jax.jit(block.apply)
        return jitted(
            {"params": p}, x, cache, cache_mask, seq_mask
        )[0] - x

    whole = mamba(p["block_2"], carried, 1)
    parts = [
        mamba(*_mamba_share(p["block_2"], carried, share, 4), 4)
        for share in range(4)
    ]
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in parts)
    np.testing.assert_allclose(sum(parts), whole, RTOL, ATOL)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3

    whole = attend(p["block_0"], window[:2], 1)
    parts = [
        attend(*_attention_share(p["block_0"], window[:2], share, 4), 4)
        for share in range(4)
    ]
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in parts)
    np.testing.assert_allclose(sum(parts), whole, RTOL, ATOL)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3


def test_the_gates_sum_to_five_and_the_experts_are_relu_squared():
    """One token, by hand: 22-of-512's rule at 3 of 16. The gates are
    the chosen sigmoid scores over their sum, times 5; an expert is
    W2 relu(W1 l)^2 on the latent l = W_down u; the shared expert reads
    u itself and is not scaled."""
    layer, x, params = scaffold.expert_layer("nemotron3", tokens=1, seed=3)
    p = params["params"]
    u = x[0]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])
    chosen = np.argsort(-np.asarray(scores))[:3]
    gates = 5.0 * scores[chosen] / jnp.sum(scores[chosen])
    assert float(jnp.sum(gates)) == pytest.approx(5.0, rel=1e-6)
    latent = u @ p["latent_down"]["kernel"]
    routed = sum(
        g * (jnp.square(jax.nn.relu(latent @ p["w_up"][e])) @ p["w_down"][e])
        for g, e in zip(gates, chosen)
    )
    want = routed @ p["latent_up"]["kernel"] + jnp.square(
        jax.nn.relu(u @ p["shared_up"]["kernel"])
    ) @ p["shared_down"]["kernel"]
    np.testing.assert_allclose(
        scaffold.apply(layer)(params, x)[0], want, RTOL, ATOL
    )


def test_layers_follow_the_pattern_and_the_state_holds_what_they_carry():
    model, params = scaffold.build("nemotron3")
    assert model.pattern() == "*EM"
    assert model.layer_caches() == (
        (M, 2, 8), None, Recurrent(((8, 4, 6), (3, 8 * 4 + 2 * 4 * 6))),
    )
    state = model.initial_state(3)
    assert [[leaf.shape for leaf in item] for item in state] == [
        [(M, 3, 2, 8), (M, 3, 2, 8), (M, 3)], [(8, 3, 4, 6), (3, 3, 80)],
    ]
    blocks = params["params"]
    assert sorted(blocks["block_0"]) == ["k", "norm", "o", "q", "v"]
    assert sorted(blocks["block_1"]) == ["moe", "norm"]
    assert sorted(blocks["block_1"]["moe"]) == [
        "e_score_correction_bias", "latent_down", "latent_up", "router",
        "shared_down", "shared_up", "w_down", "w_up",
    ]
    assert sorted(blocks["block_2"]) == [
        "A_log", "D", "conv_bias", "conv_kernel", "dt_bias", "gate_norm",
        "in_proj", "norm", "out_proj",
    ]
    assert blocks["block_2"]["in_proj"]["kernel"].shape == (
        32, 2 * 32 + 2 * 4 * 6 + 8
    )
    # A = -exp(A_log) in [-16, -1]; softplus(dt_bias) in [0.001, 0.1].
    assert np.all(np.exp(blocks["block_2"]["A_log"]) >= 1)
    assert np.all(np.exp(blocks["block_2"]["A_log"]) <= 16)
    step = jax.nn.softplus(blocks["block_2"]["dt_bias"])
    assert np.all(step >= 0.001 - 1e-6) and np.all(step <= 0.1 + 1e-6)
    # Two periods; the published order when all its layers are asked for.
    assert Nemotron3Net(
        num_actions=A, **dict(SMALL, num_layers=6)
    ).pattern() == "*EM*EM"
    whole = Nemotron3Net(
        num_actions=A, **dict(SMALL, num_layers=7)
    )
    assert whole.pattern() == "MEM*EMM"
    assert [type(entry) for entry in whole.layer_caches()] == [
        Recurrent, type(None), Recurrent, tuple, type(None), Recurrent,
        Recurrent,
    ]
    assert len(whole.initial_state(1)) == 5
    with pytest.raises(ValueError, match="whole periods of 3"):
        Nemotron3Net(num_actions=A, **dict(SMALL, num_layers=4))


def test_the_new_scopes_are_in_the_lowered_update():
    model, params = scaffold.build("nemotron3")
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
        "mamba_out_proj", "attention_full", "moe_route", "moe_latent_down",
        "moe_dispatch", "moe_experts", "moe_combine", "moe_latent_up",
        "moe_shared",
    ):
        assert scope in text, scope
