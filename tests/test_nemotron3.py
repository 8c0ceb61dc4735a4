"""The `nemotron3` family (models/nemotron3.py; layers that are one
mixer or one feed-forward part, and states of three kinds side by side,
in models/transformer.py; experts that are not gated and live in a
latent in models/moe.py DroplessMoE): against the plain reference on
seeded weights (loss, gradients, new states), the chunked scan against
the step-by-step recurrence with episode ends inside a chunk, batch
forward against stepwise acting through the carried states and through
the state table, and the shares of the mixers' heads and of the routed
experts adding up to the uncut layers."""

import numpy as np
import pytest

import jax
import jax.flatten_util
import jax.numpy as jnp

from perfbench.reference import nemotron3_policy as reference
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import monobeast, polybeast
from torchbeast_tpu.models import Nemotron3Net, create_model, moe, nemotron3
from torchbeast_tpu.models.transformer import Recurrent
from torchbeast_tpu.ops import attention
from torchbeast_tpu.runtime.state_table import DeviceStateTable

T, B, A = 11, 2, 4
FRAME = (8, 8, 1)
# A shrunken `PUBLISHED`: one attention layer of 4 query heads of 8 on 2
# key/value heads, one latent MoE layer (16 experts of 10 in a latent of
# 12, top 3, a shared expert of 20), one Mamba-2 layer of 8 heads of 4
# in 4 groups over a state of 6, scanned in chunks of 4 steps: the 11
# steps of an unroll are two whole chunks and one padded.
SMALL = dict(
    d_model=32, num_heads=4, kv_heads=2, head_dim=8, mamba_heads=8,
    mamba_head_dim=4, mamba_groups=4, state_size=6, chunk_size=4,
    num_experts=16, experts_per_token=3, expert_width=10, latent_width=12,
    shared_width=20, layer_period="*EM", layer_pattern="MEM*EMM",
)
LAYERS = 3
M = 5
# On the CPU both sides compute in float32 at full precision and differ
# by the order of their sums.
RTOL = ATOL = 2e-5


def _inputs(seed, done_steps=(), t=T, rows=B):
    rng = np.random.default_rng(seed)
    done = np.zeros((t, rows), bool)
    for step, row in done_steps:
        done[step, row] = True
    return {
        "frame": jnp.asarray(
            rng.integers(0, 256, (t, rows) + FRAME, dtype=np.uint8)
        ),
        "reward": jnp.asarray(rng.standard_normal((t, rows)), jnp.float32),
        "done": jnp.asarray(done),
        "last_action": jnp.asarray(rng.integers(0, A, (t, rows))),
    }


def _learner_batch(seed, done_steps):
    rng = np.random.default_rng(seed + 100)
    lead = (T, B)
    return dict(
        _inputs(seed, done_steps),
        episode_return=jnp.asarray(rng.standard_normal(lead), jnp.float32),
        episode_step=jnp.zeros(lead, jnp.int32),
        action=jnp.asarray(rng.integers(0, A, lead)),
        policy_logits=jnp.asarray(
            rng.standard_normal(lead + (A,)), jnp.float32
        ),
        baseline=jnp.asarray(rng.standard_normal(lead), jnp.float32),
    )


def _model(expert_share=(0, 1), mixer_share=(0, 1), seed=0, **overrides):
    model = Nemotron3Net(
        num_actions=A, memory_len=M,
        expert_share=expert_share, mixer_share=mixer_share,
        **{**SMALL, "num_layers": LAYERS, **overrides},
    )
    params = model.init(
        {"params": jax.random.PRNGKey(seed), "action": jax.random.PRNGKey(1)},
        _inputs(0), model.initial_state(B),
    )
    # The family starts its side inputs' projection and its selection
    # biases at zero, and D and the norms at one: give them values, so
    # that the comparisons cover those paths too.
    inner = dict(params["params"])
    assert not np.any(inner["extras"]["kernel"])
    inner["extras"] = dict(inner["extras"], kernel=0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 7), inner["extras"]["kernel"].shape
    ))
    for layer, letter in enumerate(model.pattern()):
        block = dict(inner[f"block_{layer}"])
        if letter == "E":
            assert not np.any(block["moe"]["e_score_correction_bias"])
            block["moe"] = dict(
                block["moe"],
                e_score_correction_bias=0.1 * jax.random.normal(
                    jax.random.PRNGKey(seed + layer),
                    (SMALL["num_experts"],),
                ),
            )
        elif letter == "M":
            for i, name in enumerate(("D", "gate_norm")):
                block[name] = block[name] + 0.3 * jax.random.normal(
                    jax.random.PRNGKey(seed + 10 * layer + i),
                    block[name].shape,
                )
        inner[f"block_{layer}"] = block
    return model, {"params": inner}


def _reference_config(model):
    heads, groups, query_heads, kv_heads = model.held_mixers()
    held = model.held_experts()
    return {
        "hybrid_override_pattern": model.pattern(),
        "num_hidden_layers": model.num_layers,
        "mamba_num_heads": heads, "mamba_head_dim": model.mamba_head_dim,
        "n_groups": groups, "ssm_state_size": model.state_size,
        "conv_kernel": model.conv_kernel, "use_conv_bias": True,
        "mamba_proj_bias": False, "mamba_hidden_act": "silu",
        "num_attention_heads": query_heads,
        "num_key_value_heads": kv_heads, "head_dim": model.head_dim,
        "attention_bias": False,
        "published_n_routed_experts": model.num_experts,
        "n_routed_experts": held[1] if held else model.num_experts,
        "expert_share": list(model.expert_share),
        "mixer_share": list(model.mixer_share),
        "num_experts_per_tok": model.experts_per_token,
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 5.0, "n_shared_experts": 1,
        "mlp_hidden_act": "relu2", "mlp_bias": False,
        "bias_update_rate": 0.001, "layer_norm_epsilon": 1e-5,
        "memory_len": M, "num_actions": A,
        "discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
    }


def _warm_state(model, params, seed, unrolls=1, rows=B):
    """What an actor would hold `unrolls` unrolls of 11 steps in, an
    episode end in the first: Mamba states and conv tails that are not
    zeros, an attention cache that is full."""
    state = model.initial_state(rows)
    apply = jax.jit(lambda x, s: model.apply(
        params, x, s, sample_action=False
    )[1])
    for i in range(unrolls):
        state = apply(
            _inputs(seed + i, [(2, 1)] if i == 0 else (), rows=rows), state
        )
    return state


def _loss_and_grads(model, params, batch, state):
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    jitted = jax.jit(jax.value_and_grad(
        lambda p: learner_lib.compute_loss(model, p, batch, state, hp),
        has_aux=True,
    ))
    (loss, stats), grads = jitted(params)
    return loss, stats, grads


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
    model, params = _model(expert_share, mixer_share)
    config = _reference_config(model)
    state = _warm_state(model, params, seed=5)
    assert all(np.any(leaf) for leaf in jax.tree_util.tree_leaves(state))
    batch = _learner_batch(7, done_steps=ENDS[:4] + [(5, 1)])

    jitted = jax.jit(lambda p, b, s: model.apply(
        p, b, s, sample_action=False
    ))
    out, new_state = jitted(params, batch, state)
    jitted = jax.jit(
        lambda p, b, s: reference.forward(p, b, s, config)
    )
    logits, baseline, ref_state, _ = jitted(params, batch, state)
    np.testing.assert_allclose(out.policy_logits, logits, RTOL, ATOL)
    np.testing.assert_allclose(out.baseline, baseline, RTOL, ATOL)
    leaves, ref_leaves = (
        jax.tree_util.tree_leaves(s) for s in (new_state, ref_state)
    )
    assert len(leaves) == len(ref_leaves) == 5
    for got, want in zip(leaves, ref_leaves):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, RTOL, ATOL)

    loss, stats, grads = _loss_and_grads(model, params, batch, state)
    jitted = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_and_scale(p, batch, state, config),
        has_aux=True,
    ))
    (ref_loss, scale), ref_grads = jitted(params)
    scale = float(scale)
    assert abs(float(loss) - float(ref_loss)) <= RTOL * scale
    flat, ref_flat = (
        jax.flatten_util.ravel_pytree(g)[0] for g in (grads, ref_grads)
    )
    np.testing.assert_allclose(
        flat, ref_flat, rtol=0, atol=RTOL * float(jnp.max(jnp.abs(ref_flat)))
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
    jitted = jax.jit(
        lambda p: reference.bias_steps(p, batch, state, config)
    )
    (want,) = jitted(params)
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
    from torchbeast_tpu.models import moe

    rows = 32
    model, params = _model((1, 16), num_experts=32)
    assert moe.window_rungs(T * rows, 3, 2, 32) == (256, 2 * T * rows)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    if to_held is not None:
        bias = bias.at[2:4].set(to_held - 1.5)
    inner = dict(params["params"])
    block = dict(inner["block_1"])
    block["moe"] = dict(block["moe"], e_score_correction_bias=bias)
    params = {"params": dict(inner, block_1=block)}
    batch = {
        k: jnp.concatenate([v] * (rows // B), axis=1)
        for k, v in _learner_batch(3, done_steps=ENDS).items()
    }
    hp = learner_lib.HParams(batch_size=rows, unroll_length=T - 1)
    jitted = jax.jit(lambda p: learner_lib.compute_loss(
        model, p, batch, model.initial_state(rows), hp
    ))
    _, stats = jitted(params)
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
    model, params = _model()
    state = _warm_state(model, params, seed=2, unrolls=unrolls)
    inputs = _inputs(3, done_steps=ENDS)
    apply = jax.jit(lambda x, s: model.apply(
        params, x, s, sample_action=False
    ))
    full, full_state = apply(inputs, state)
    logits = []
    for t in range(T):
        step = {k: v[t : t + 1] for k, v in inputs.items()}
        out, state = apply(step, state)
        logits.append(out.policy_logits[0])
    np.testing.assert_allclose(
        np.stack(logits), full.policy_logits, rtol=2e-4, atol=2e-5
    )
    for got, want in zip(
        jax.tree_util.tree_leaves(state),
        jax.tree_util.tree_leaves(full_state),
    ):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


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
    model, params = _model()
    rows, steps = 3, 6
    inputs = _inputs(4, done_steps=[(3, 2), (4, 2), (1, 0)], t=steps, rows=rows)
    jitted = jax.jit(lambda x, s: model.apply(
        params, x, s, sample_action=False
    ))
    full, full_state = jitted(inputs, model.initial_state(rows))

    def act(ctx, env_outputs, agent_state):
        out, new_state = model.apply(
            params, env_outputs, agent_state, sample_action=False
        )
        return {"logits": out.policy_logits}, new_state

    table = DeviceStateTable(
        model.initial_state(1), num_slots=rows, act_fn=act, batch_dim=1
    )
    orders = [[0, 1, 2], [2, 0, 1], [1, 2, 0], [2, 1, 0], [0, 2, 1], [1, 0, 2]]
    for t, order in enumerate(orders):
        step = {
            k: np.asarray(v[t : t + 1])[:, order] for k, v in inputs.items()
        }
        out = table.step(
            np.asarray(order, np.int32), np.ones(rows, bool), step
        )
        np.testing.assert_allclose(
            table.fetch(out, rows)["logits"][0],
            np.asarray(full.policy_logits)[t][order],
            rtol=2e-4, atol=2e-5,
        )
    shapes = [
        [(M, 1, 2, 8), (M, 1, 2, 8), (M, 1)], [(8, 1, 4, 6), (3, 1, 80)],
    ]
    for slot in range(rows):
        held = table.read_slot(slot)
        assert [[np.shape(leaf) for leaf in item] for item in held] == shapes
        for item, want_item in zip(held, full_state):
            for got, want in zip(item, want_item):
                np.testing.assert_allclose(
                    got, np.asarray(want)[:, slot : slot + 1],
                    rtol=2e-4, atol=2e-5,
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
    model, params = _model()
    state = _warm_state(model, params, seed=3)
    inputs = _inputs(6, done_steps=ENDS)
    done = inputs["done"]
    x = jax.random.normal(jax.random.PRNGKey(2), (B, T, 32))
    p = params["params"]
    config = _reference_config(model)
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


def _latent_layer(held=None, tokens=40, seed=0, E=16, K=3):
    layer = moe.DroplessMoE(
        d_ff=8, num_experts=E, top_k=K, aux_loss_weight=0.0,
        renormalise=True, held=held, scoring="sigmoid", selection_bias=True,
        bias_update_rate=0.001, routed_scaling=5.0, shared_width=12,
        gated=False, activation="relu2", latent_width=10,
    )
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, 16))
    return layer, x, layer.init(jax.random.PRNGKey(seed + 1), x)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_expert_shares_add_up_to_the_uncut_latent_layer(side):
    """The routed parts of eight shares of 16 experts (the interpreted
    grouped kernels are slow over 512; two held under three a token, so
    a share's experts see the window of the sorted rows that can be
    theirs, models/moe.py), each with its own eighth of the
    uncut layer's expert weights, LIFTED OUT OF THE LATENT by the one
    `latent_up` every chip holds, plus the shared expert COUNTED ONCE,
    add up to the uncut layer's output; `latent_down` is applied on
    every chip alike and is no part of the sum. Program (values and the
    gradient with respect to x) and reference."""
    E, K, tokens, shares = 16, 3, 40, 8
    _, x, params = _latent_layer(tokens=tokens, seed=4)
    p = dict(params["params"])
    assert "w_gate" not in p and "shared_gate" not in p
    p["e_score_correction_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(9), (E,)
    )

    def shared(x):
        return jnp.square(jax.nn.relu(x @ p["shared_up"]["kernel"])) @ (
            p["shared_down"]["kernel"]
        )

    def run(first, count, x):
        cut = dict(p, **{
            k: p[k][first : first + count] for k in ("w_up", "w_down")
        })
        if side == "program":
            held = None if count == E else (first, count)
            return _latent_layer(held, tokens=tokens)[0].apply(
                {"params": cut}, x
            )
        return reference._experts(x, cut, {
            "published_n_routed_experts": E, "n_routed_experts": count,
            "expert_share": [first // count, E // count],
            "num_experts_per_tok": K, "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "routed_scaling_factor": 5.0,
            "n_shared_experts": 1, "mlp_hidden_act": "relu2",
            "mlp_bias": False,
        })

    firsts = range(0, E, E // shares)
    whole = run(0, E, x)
    parts = [run(first, E // shares, x) - shared(x) for first in firsts]
    assert all(float(jnp.max(jnp.abs(part))) > 0 for part in parts)
    np.testing.assert_allclose(sum(parts) + shared(x), whole, RTOL, ATOL)
    assert float(jnp.max(jnp.abs(parts[0] + shared(x) - whole))) > 1e-3
    assert float(
        jnp.max(jnp.abs(sum(parts) + shares * shared(x) - whole))
    ) > 1e-3

    grad_whole = jax.grad(lambda x: jnp.sum(jnp.sin(run(0, E, x))))(x)
    weight = jnp.cos(whole)
    grad_parts = sum(
        jax.grad(lambda x, f=first: jnp.sum(
            weight * (run(f, E // shares, x) - shared(x))
        ))(x)
        for first in firsts
    ) + jax.grad(lambda x: jnp.sum(weight * shared(x)))(x)
    np.testing.assert_allclose(grad_parts, grad_whole, rtol=1e-4, atol=1e-5)


def test_the_gates_sum_to_five_and_the_experts_are_relu_squared():
    """One token, by hand: 22-of-512's rule at 3 of 16. The gates are
    the chosen sigmoid scores over their sum, times 5; an expert is
    W2 relu(W1 l)^2 on the latent l = W_down u; the shared expert reads
    u itself and is not scaled."""
    layer, x, params = _latent_layer(tokens=1, seed=3)
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
    np.testing.assert_allclose(layer.apply(params, x)[0], want, RTOL, ATOL)


def test_layers_follow_the_pattern_and_the_state_holds_what_they_carry():
    model, params = _model()
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
        num_actions=A, memory_len=M, **dict(SMALL, num_layers=7)
    )
    assert whole.pattern() == "MEM*EMM"
    assert [type(entry) for entry in whole.layer_caches()] == [
        Recurrent, type(None), Recurrent, tuple, type(None), Recurrent,
        Recurrent,
    ]
    assert len(whole.initial_state(1)) == 5
    with pytest.raises(ValueError, match="whole periods of 3"):
        Nemotron3Net(num_actions=A, **dict(SMALL, num_layers=4))


def test_registry_builds_the_published_widths_and_refuses_lstm():
    model = create_model(
        "nemotron3", num_actions=6, num_layers=11, mixer_share=(0, 4),
        expert_share=(0, 64),
    )
    assert isinstance(model, Nemotron3Net)
    assert model.zero_init_extras and model.frame_range == (-1.0, 1.0)
    assert (model.d_model, model.num_heads, model.kv_heads, model.head_dim) == (
        4096, 32, 2, 128
    )
    assert (
        model.mamba_heads, model.mamba_head_dim, model.mamba_groups,
        model.state_size, model.conv_kernel, model.chunk_size,
    ) == (128, 64, 8, 128, 4, 128)
    assert model.mamba_heads * model.mamba_head_dim == 2 * model.d_model
    assert (
        model.num_experts, model.experts_per_token, model.expert_width,
        model.latent_width, model.shared_width,
    ) == (512, 22, 2688, 1024, 5376)
    assert model.renormalise and model.routed_scaling == 5.0
    assert (model.rms_norm_eps, model.memory_len) == (1e-5, 4095)
    assert model.pattern() == "*EMEMEMEMEM"
    assert model.held_mixers() == (32, 2, 8, 1)
    assert model.held_experts() == (0, 8)
    window, nothing, carried = model.layer_caches()[:3]
    assert window == (4095, 1, 128) and nothing is None
    assert carried == Recurrent(((32, 64, 128), (3, 2048 + 2 * 2 * 128)))
    whole = create_model("nemotron3", num_actions=6)
    assert whole.num_layers == 88 == len(whole.pattern())
    assert whole.pattern()[25:36] == model.pattern()
    assert [whole.pattern().count(c) for c in "M*E"] == [40, 8, 40]
    assert whole.held_mixers() == (128, 8, 32, 2)
    # Two chips a layer halve the key/value heads; eight hold one each.
    assert create_model(
        "nemotron3", num_actions=6, mixer_share=(1, 2)
    ).held_mixers() == (64, 4, 16, 1)
    assert create_model(
        "nemotron3", num_actions=6, mixer_share=(7, 8)
    ).held_mixers() == (16, 1, 4, 1)
    with pytest.raises(ValueError, match="use_lstm"):
        create_model("nemotron3", num_actions=6, use_lstm=True)
    with pytest.raises(ValueError, match="whole periods of 11"):
        create_model("nemotron3", num_actions=6, num_layers=5)
    for bad in [(4, 4), (0, 3), (-1, 8), (0, 16)]:
        with pytest.raises(ValueError, match="mixer_share"):
            create_model("nemotron3", num_actions=6, mixer_share=bad)
    with pytest.raises(ValueError, match="expert_share"):
        create_model("nemotron3", num_actions=6, expert_share=(0, 7))
    # The cell's attention layer (8 query heads of 128 on one key/value
    # head over 4,095 + 256 keys, 570 MB of f32 scores at B=16) is
    # `fused_attend`'s; a T=1 act step is not.
    assert attention.fused_pass_applies(
        (16, 256, 8, 128), (16, 4351, 1, 128), None
    )
    assert not attention.fused_pass_applies(
        (16, 1, 8, 128), (16, 4096, 1, 128), None
    )


@pytest.mark.parametrize("driver", [monobeast, polybeast], ids=["mono", "poly"])
def test_parsers_take_the_family_and_its_flags(driver, monkeypatch):
    parse = driver.make_parser().parse_args
    flags = parse([
        "--model", "nemotron3", "--num_layers", "3", "--memory_len", "9",
        "--expert_share", "1/8", "--mixer_share", "1/2",
    ])
    assert (flags.model, flags.expert_share, flags.mixer_share) == (
        "nemotron3", "1/8", "1/2"
    )
    monkeypatch.setattr(
        nemotron3, "PUBLISHED", dict(nemotron3.PUBLISHED, **SMALL)
    )
    model, _ = monobeast._init_model_and_params(
        flags, A, B, FRAME, init_params=False
    )
    assert isinstance(model, Nemotron3Net)
    assert (model.num_layers, model.memory_len, model.d_model) == (3, 9, 32)
    assert model.held_experts() == (2, 2)
    assert model.held_mixers() == (4, 2, 2, 1)
    with pytest.raises(ValueError, match="whole periods of 3"):
        monobeast._init_model_and_params(
            parse(["--model", "nemotron3", "--num_layers", "2"]),
            A, B, FRAME, init_params=False,
        )
    with pytest.raises(ValueError, match="must be 'i/n'"):
        monobeast._init_model_and_params(
            parse(["--model", "nemotron3", "--num_layers", "3",
                   "--mixer_share", "half"]),
            A, B, FRAME, init_params=False,
        )
    with pytest.raises(ValueError, match="mixer_share .* nemotron3 only"):
        monobeast._init_model_and_params(
            parse(["--model", "kanana2", "--mixer_share", "0/2"]),
            A, B, FRAME, init_params=False,
        )
    with pytest.raises(ValueError, match="use_lstm"):
        monobeast._init_model_and_params(
            parse(["--model", "nemotron3", "--use_lstm"]),
            A, B, FRAME, init_params=False,
        )
    # --remat reaches the family's blocks.
    model, _ = monobeast._init_model_and_params(
        parse(["--model", "nemotron3", "--num_layers", "3", "--remat", "all"]),
        A, B, FRAME, init_params=False,
    )
    assert model.remat is True


def test_rematerialised_blocks_give_the_same_loss_gradients_and_steps():
    model, params = _model((1, 8), (1, 2))
    remat = model.clone(remat=True)
    state = _warm_state(model, params, seed=5)
    batch = _learner_batch(9, done_steps=ENDS)
    loss, stats, grads = _loss_and_grads(model, params, batch, state)
    loss_r, stats_r, grads_r = _loss_and_grads(remat, params, batch, state)
    assert float(loss) == pytest.approx(float(loss_r), rel=1e-5)
    flat, flat_r = (
        jax.flatten_util.ravel_pytree(g)[0] for g in (grads, grads_r)
    )
    np.testing.assert_allclose(
        flat, flat_r, rtol=0, atol=RTOL * float(jnp.max(jnp.abs(flat)))
    )
    for name in (
        "moe_held_assignments", "ssm_applications", "ssm_chunks",
        "ssm_resets_per_row", "moe_latent_applications",
    ):
        assert float(stats[name]) == float(stats_r[name])
    np.testing.assert_array_equal(
        *(s[learner_lib.PARAM_STEPS_KEY]["block_1"]["moe"][
            "e_score_correction_bias"
        ] for s in (stats, stats_r))
    )


def test_the_new_scopes_are_in_the_lowered_update():
    model, params = _model()
    batch = _learner_batch(1, done_steps=ENDS)
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
