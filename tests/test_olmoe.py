"""The `olmoe` family (models/olmoe.py, models/moe.py DroplessMoE):
against the plain reference on seeded weights, batch forward against
stepwise acting through the rolling cache, and what makes the expert
layer OLMoE's and not the capacity path's: gates as they are, every
assignment computed whatever the router does."""

import math

import numpy as np
import pytest

import jax
import jax.flatten_util
import jax.numpy as jnp

from perfbench.reference import olmoe_policy as reference
from tests.seeded_pin import assert_seeded_outputs
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import monobeast, polybeast
from torchbeast_tpu.models import OLMoENet, create_model, moe, olmoe

T, B, A, M = 6, 2, 4, 4
FRAME = (8, 8, 1)
SMALL = dict(
    d_model=64, num_heads=4, num_layers=2, num_experts=8,
    experts_per_token=2, expert_width=32,
)
WIDE_ROUTER = dict(SMALL, num_experts=64, experts_per_token=8)
# On the CPU the program and the reference both compute in float32 at
# full precision, so they differ only by the order of their sums: the
# program adds each token's experts as sorted rows, the reference adds
# all the experts under a mask. Lower precision in either (a bf16 matmul
# is 4e-3 off) fails this by two orders of magnitude.
RTOL = ATOL = 1e-5


def _inputs(seed, done_steps=(), t=T):
    rng = np.random.default_rng(seed)
    done = np.zeros((t, B), bool)
    for step, row in done_steps:
        done[step, row] = True
    return {
        "frame": jnp.asarray(
            rng.integers(0, 256, (t, B) + FRAME, dtype=np.uint8)
        ),
        "reward": jnp.asarray(rng.standard_normal((t, B)), jnp.float32),
        "done": jnp.asarray(done),
        "last_action": jnp.asarray(rng.integers(0, A, (t, B))),
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


def _model(widths, seed=0):
    model = OLMoENet(num_actions=A, memory_len=M, **widths)
    params = model.init(
        {"params": jax.random.PRNGKey(seed), "action": jax.random.PRNGKey(1)},
        _inputs(0), model.initial_state(B),
    )
    return model, params


def _reference_config(widths):
    return {
        "num_attention_heads": widths["num_heads"],
        "num_experts": widths["num_experts"],
        "num_experts_per_tok": widths["experts_per_token"],
        "num_hidden_layers": widths["num_layers"],
        "rms_norm_eps": 1e-5, "rope_theta": 10000, "memory_len": M,
        "load_balance_weight": 0.01, "num_actions": A,
        "discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
    }


def _warm_state(model, params, seed):
    """A cache an actor would hold: one unroll in, an episode end in it."""
    _, state = model.apply(
        params, _inputs(seed, done_steps=[(2, 1)]), model.initial_state(B),
        sample_action=False,
    )
    return state


@pytest.mark.parametrize(
    "widths", [SMALL, WIDE_ROUTER], ids=["8-experts-top-2", "64-top-8"]
)
def test_family_agrees_with_the_reference(widths):
    model, params = _model(widths)
    config = _reference_config(widths)
    state = _warm_state(model, params, seed=5)
    batch = _learner_batch(7, done_steps=[(3, 0)])

    out, new_state = model.apply(params, batch, state, sample_action=False)
    logits, baseline, ref_state, _ = reference.forward(
        params, batch, state, config
    )
    np.testing.assert_allclose(out.policy_logits, logits, RTOL, ATOL)
    np.testing.assert_allclose(out.baseline, baseline, RTOL, ATOL)
    for got, want in zip(
        jax.tree_util.tree_leaves(new_state),
        jax.tree_util.tree_leaves(ref_state),
    ):
        np.testing.assert_allclose(got, want, RTOL, ATOL)

    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    (loss, stats), grads = jax.value_and_grad(
        lambda p: learner_lib.compute_loss(model, p, batch, state, hp),
        has_aux=True,
    )(params)
    ref_loss, ref_grads = jax.value_and_grad(reference.loss)(
        params, batch, state, config
    )
    scale = float(reference.loss_and_scale(params, batch, state, config)[1])
    assert abs(float(loss) - float(ref_loss)) <= RTOL * scale
    flat, ref_flat = (
        jax.flatten_util.ravel_pytree(g)[0] for g in (grads, ref_grads)
    )
    np.testing.assert_allclose(
        flat, ref_flat, rtol=0, atol=RTOL * float(jnp.max(jnp.abs(ref_flat)))
    )
    tokens = T * B
    assert float(stats["moe_assignments"]) == (
        widths["experts_per_token"] * tokens * widths["num_layers"]
    )
    assert float(stats["moe_load_max_over_mean"]) >= 1.0
    assert float(stats["aux_loss"]) > 0
    # Every block counts its own two-leg application: summed by name.
    assert float(stats["attention_two_leg_applications"]) == (
        widths["num_layers"]
    )
    assert "attention_fused_applications" not in stats


def test_batch_forward_equals_stepwise_acting_across_an_episode_end():
    """The learner's [T, B] forward and the actor's T=1 forwards through
    the rolling cache (M=4 < T: slots are evicted on the way) give the
    same logits and leave the same cache, with an episode ending
    mid-unroll in one row: RoPE sees time differences alone."""
    model, params = _model(SMALL)
    state = _warm_state(model, params, seed=2)
    inputs = _inputs(3, done_steps=[(3, 1)])
    full, full_state = model.apply(params, inputs, state, sample_action=False)
    logits = []
    for t in range(T):
        step = {k: v[t : t + 1] for k, v in inputs.items()}
        out, state = model.apply(params, step, state, sample_action=False)
        logits.append(out.policy_logits[0])
    np.testing.assert_allclose(
        np.stack(logits), full.policy_logits, rtol=2e-4, atol=2e-5
    )
    for got, want in zip(
        jax.tree_util.tree_leaves(state),
        jax.tree_util.tree_leaves(full_state),
    ):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _layer(num_experts=8, top_k=2, d=16, width=8, tokens=12, seed=0):
    layer = moe.DroplessMoE(d_ff=width, num_experts=num_experts, top_k=top_k)
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, d))
    return layer, x, layer.init(jax.random.PRNGKey(seed + 1), x)


def _every_expert_masked(x, idx, gate, w_gate, w_up, w_down):
    y = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)
        hidden = jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
        y = y + weight[:, None] * (hidden @ w_down[e])
    return y


@pytest.mark.parametrize("factor", [1.0, 1.25], ids=["plain", "yarn-factor"])
def test_rope_in_the_states_layout_is_rope(factor):
    """`rope_rotate_state` on a cache as the state holds it, [S, B, H,
    D] at negative times, = `rope_rotate` on the same keys batch-first
    to the last bit or two (jitted, as the models run them: the two
    forms' multiply-adds contract differently), and both = the
    reference's rotate-half."""
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.standard_normal((5, 2, 4, 16)), jnp.float32)
    times = jnp.arange(5) - 5
    inv_freq = 10000.0 ** (-jnp.arange(8, dtype=jnp.float32) / 8)
    rotate_state = jax.jit(olmoe.rope_rotate_state, static_argnums=3)
    rotate = jax.jit(olmoe.rope_rotate, static_argnums=3)
    in_state = rotate_state(keys, times, inv_freq, factor)
    batch_first = rotate(keys.transpose(1, 0, 2, 3), times, inv_freq, factor)
    np.testing.assert_allclose(
        in_state, batch_first.transpose(1, 0, 2, 3), rtol=1e-5, atol=1e-6
    )
    if factor == 1.0:
        np.testing.assert_allclose(
            batch_first,
            reference._rope(keys.transpose(1, 0, 2, 3), times, 10000.0),
            rtol=1e-6, atol=1e-6,
        )
        np.testing.assert_allclose(
            olmoe.rope_state(keys, times, 10000.0), in_state,
            rtol=1e-5, atol=1e-6,
        )


def test_gates_are_not_renormalised():
    """With every expert the same matrix the layer's output is that
    expert's, times the SUM of the chosen probabilities (under one: the
    capacity path's renormalised gates would make it exactly one)."""
    layer, x, params = _layer()
    p = params["params"]
    same = {
        k: jnp.broadcast_to(p[k][:1], p[k].shape)
        for k in ("w_gate", "w_up", "w_down")
    }
    y = layer.apply({"params": dict(p, **same)}, x)
    probs = jax.nn.softmax(x @ p["router"]["kernel"])
    chosen = jnp.sort(probs, axis=-1)[:, -2:].sum(axis=-1)
    expert = (
        jax.nn.silu(x @ p["w_gate"][0]) * (x @ p["w_up"][0])
    ) @ p["w_down"][0]
    assert float(chosen.max()) < 0.9
    np.testing.assert_allclose(y, chosen[:, None] * expert, RTOL, ATOL)


def test_a_router_forced_onto_one_expert_drops_nothing():
    """Every token's first choice is expert 3, its second expert 5: the
    capacity path (capacity 1.25 x K t / E = 4 rows) would drop 8 of
    each expert's 12; here every token still gets both its experts."""
    tokens, E, K = 12, 8, 2
    layer, x, params = _layer(E, K, tokens=tokens)
    forced = jnp.zeros_like(params["params"]["router"]["kernel"])
    params = {"params": dict(params["params"], router={"kernel": forced})}
    x = x.at[:, 0].set(1.0)
    bias_row = jnp.zeros((E,)).at[3].set(20.0).at[5].set(10.0)
    params["params"]["router"]["kernel"] = forced.at[0].set(bias_row)
    assert math.ceil(K * tokens / E * 1.25) < tokens

    y, sown = layer.apply(params, x, mutable=["losses", "moe_stats"])
    stats = sown["moe_stats"]
    assert float(stats["assignments"]) == K * tokens
    assert float(stats["load_max_over_mean"]) == pytest.approx(E / K)
    p = params["params"]
    gate, idx = jax.lax.top_k(jax.nn.softmax(x @ p["router"]["kernel"]), K)
    assert set(np.asarray(idx).ravel()) == {3, 5}
    want = _every_expert_masked(
        x, idx, gate, p["w_gate"], p["w_up"], p["w_down"]
    )
    np.testing.assert_allclose(y, want, RTOL, ATOL)
    assert float(jnp.min(jnp.linalg.norm(y, axis=-1))) > 0


@pytest.mark.parametrize("tokens", [12, 200])
def test_dropless_dispatch_equals_the_every_expert_masked_sum(tokens):
    """Values and gradients (x, gates, all three weights), at a row
    count under one kernel tile and at one that is padded to two."""
    layer, x, params = _layer(tokens=tokens, seed=3)
    p = params["params"]
    gate, idx = jax.lax.top_k(jax.nn.softmax(x @ p["router"]["kernel"]), 2)
    weights = (p["w_gate"], p["w_up"], p["w_down"])

    def total(fn, x, gate, *w):
        out = fn(x, idx, gate, *w)
        return jnp.sum(jnp.sin(out[0] if isinstance(out, tuple) else out))

    y, sizes = moe.dropless_experts(x, idx, gate, *weights)
    np.testing.assert_allclose(
        y, _every_expert_masked(x, idx, gate, *weights), RTOL, ATOL
    )
    assert int(sizes.sum()) == 2 * tokens
    got = jax.grad(total, argnums=(1, 2, 3, 4, 5))(
        moe.dropless_experts, x, gate, *weights
    )
    want = jax.grad(total, argnums=(1, 2, 3, 4, 5))(
        _every_expert_masked, x, gate, *weights
    )
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_registry_builds_the_published_widths_and_refuses_lstm():
    model = create_model("olmoe", num_actions=6, num_layers=2)
    assert isinstance(model, OLMoENet)
    assert (model.d_model, model.num_heads, model.num_layers) == (2048, 16, 2)
    assert (model.num_experts, model.experts_per_token) == (64, 8)
    assert (model.expert_width, model.memory_len) == (1024, 128)
    # Centred frames are this family's; the d128 transformer keeps [0, 1].
    assert model.frame_range == (-1.0, 1.0)
    assert create_model("transformer", num_actions=6).frame_range == (0.0, 1.0)
    assert create_model("olmoe", num_actions=6).num_layers == 16
    k, v, valid = model.initial_state(3)[1]
    assert k.shape == v.shape == (128, 3, 16, 128)
    assert valid.shape == (128, 3)
    with pytest.raises(ValueError, match="use_lstm"):
        create_model("olmoe", num_actions=6, use_lstm=True)


@pytest.mark.parametrize("driver", [monobeast, polybeast], ids=["mono", "poly"])
def test_parsers_take_the_family_and_its_two_flags(driver, monkeypatch):
    flags = driver.make_parser().parse_args(
        ["--model", "olmoe", "--num_layers", "3", "--memory_len", "9"]
    )
    assert (flags.model, flags.num_layers, flags.memory_len) == ("olmoe", 3, 9)
    monkeypatch.setattr(olmoe, "PUBLISHED", dict(olmoe.PUBLISHED, **SMALL))
    model, _ = monobeast._init_model_and_params(
        flags, A, B, FRAME, init_params=False
    )
    assert isinstance(model, OLMoENet)
    assert (model.num_layers, model.memory_len, model.d_model) == (3, 9, 64)
    # The flags are the transformer families' alone.
    flags = driver.make_parser().parse_args(["--model", "mlp", "--num_layers", "3"])
    with pytest.raises(ValueError, match="num_layers"):
        monobeast._init_model_and_params(flags, A, B, FRAME, init_params=False)
    flags = driver.make_parser().parse_args(
        ["--model", "transformer", "--num_layers", "1", "--memory_len", "7"]
    )
    model, _ = monobeast._init_model_and_params(
        flags, A, B, FRAME, init_params=False
    )
    assert (model.num_layers, model.memory_len) == (1, 7)


def _tree_shapes(tree):
    return {
        "/".join(k.key for k in path): tuple(x.shape)
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


_OLMOE_BLOCK = {
    "attn_norm/scale": (32,), "q/kernel": (32, 32), "k/kernel": (32, 32),
    "v/kernel": (32, 32), "o/kernel": (32, 32), "q_norm/scale": (32,),
    "k_norm/scale": (32,), "moe_norm/scale": (32,),
    "moe/router/kernel": (32, 4), "moe/w_gate": (4, 32, 8),
    "moe/w_up": (4, 32, 8), "moe/w_down": (4, 8, 32),
}
_TRANSFORMER_BLOCK = {
    "LayerNorm_0/scale": (32,), "LayerNorm_0/bias": (32,),
    "LayerNorm_1/scale": (32,), "LayerNorm_1/bias": (32,),
    "q/kernel": (32, 2, 16), "q/bias": (2, 16), "k/kernel": (32, 2, 16),
    "k/bias": (2, 16), "v/kernel": (32, 2, 16), "v/bias": (2, 16),
    "out/kernel": (2, 16, 32), "out/bias": (32,), "rel_bias": (2, 6),
    "Dense_0/kernel": (32, 128), "Dense_0/bias": (128,),
    "Dense_1/kernel": (128, 32), "Dense_1/bias": (32,),
}
_SCAFFOLDING = {
    "Dense_0/kernel": (64, 32), "Dense_0/bias": (32,),
    "extras/kernel": (5, 32), "extras/bias": (32,),
    "head/policy/kernel": (32, 4), "head/policy/bias": (4,),
    "head/baseline/kernel": (32, 1), "head/baseline/bias": (1,),
}


@pytest.mark.parametrize("family", ["olmoe", "transformer"])
def test_tree_state_and_output_are_what_they_were_before_layer_caches(family):
    """The scaffolding asks each layer for its cache since PR 32
    (`layer_caches`); the two families that answer with their one pair
    keep the parameter tree, the state and the numbers they had: the
    tree and the state spelt out here, the logits pinned by their first
    values from the parent commit's program on the same seeds."""
    from torchbeast_tpu.models import TransformerNet

    if family == "olmoe":
        model = OLMoENet(
            num_actions=4, memory_len=5, d_model=32, num_heads=2,
            num_layers=2, num_experts=4, experts_per_token=2, expert_width=8,
        )
        block, last = _OLMOE_BLOCK, {"final_norm/scale": (32,)}
        pinned = [-2.460061, 1.2367259, 1.4908557]
    else:
        model = TransformerNet(
            num_actions=4, memory_len=5, d_model=32, num_heads=2,
            num_layers=2,
        )
        block = _TRANSFORMER_BLOCK
        last = {"LayerNorm_0/scale": (32,), "LayerNorm_0/bias": (32,)}
        pinned = [0.02496201, 1.0791285, -1.4319977]
    assert model.layer_caches() == ((5, 2, 16),) * 2
    steps, rows = 3, 2
    inputs = {
        "frame": jnp.zeros((steps, rows, 8, 8, 1), jnp.uint8),
        "reward": jnp.zeros((steps, rows)),
        "done": jnp.zeros((steps, rows), bool),
        "last_action": jnp.zeros((steps, rows), jnp.int32),
    }
    state = model.initial_state(rows)
    assert [tuple(x.shape for x in layer) for layer in state] == [
        ((5, rows, 2, 16), (5, rows, 2, 16), (5, rows))
    ] * 2
    params = model.init(
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        inputs, state,
    )
    want = dict(_SCAFFOLDING, **last)
    for layer in range(2):
        want.update({f"block_{layer}/{k}": v for k, v in block.items()})
    assert _tree_shapes(params["params"]) == want
    frames = np.random.default_rng(0).integers(
        0, 256, (steps, rows, 8, 8, 1), dtype=np.uint8
    )
    out, new_state = model.apply(
        params, dict(inputs, frame=jnp.asarray(frames)), state,
        sample_action=False,
    )
    np.testing.assert_allclose(
        np.asarray(out.policy_logits).ravel()[:3], pinned, rtol=2e-6
    )
    assert jax.tree_util.tree_map(jnp.shape, new_state) == (
        jax.tree_util.tree_map(jnp.shape, state)
    )


def test_seeded_logits_are_what_they_were_before_pr_38():
    """PR 38 let a cache entry's two leaves differ (models/transformer.
    py `layer_caches`, `initial_state`) and gave `DroplessMoE` a second
    router: this family's tree, state and outputs at a seeded tiny size
    are the numbers the parent commit gave (tests/seeded_pin.py, run on
    both trees)."""
    assert_seeded_outputs(
        OLMoENet(
            num_actions=4, num_layers=2, memory_len=5, d_model=32,
            num_heads=2, num_experts=4, experts_per_token=2, expert_width=16,
        ),
        params=23461,
        logits=[
            0.9347226619720459, 2.0615499019622803, 0.7423094511032104,
            1.6697852611541748,
        ],
        baseline=-2.277128219604492,
        leaf_shapes=[[5, 2, 2, 16], [5, 2, 2, 16], [5, 2], [5, 2, 2, 16]],
        state_sum=1020.148193359375,
    )
