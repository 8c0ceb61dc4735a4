"""The `olmoe` family (models/olmoe.py, models/moe.py DroplessMoE):
against the plain reference on seeded weights, batch forward against
stepwise acting through the rolling cache, and what makes the expert
layer OLMoE's and not the capacity path's: gates as they are, every
assignment computed whatever the router does."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench.reference import olmoe_policy as reference
from tests import family_scaffold as scaffold
from torchbeast_tpu.models import OLMoENet, moe, olmoe
from torchbeast_tpu.models import stats as model_stats

T, B = 6, scaffold.B
WIDE_ROUTER = dict(num_experts=64, experts_per_token=8)
# On the CPU the program and the reference both compute in float32 at
# full precision, so they differ only by the order of their sums: the
# program adds each token's experts as sorted rows, the reference adds
# all the experts under a mask. Lower precision in either (a bf16 matmul
# is 4e-3 off) fails this by two orders of magnitude.
RTOL = ATOL = 1e-5


@pytest.mark.parametrize(
    "widths", [{}, WIDE_ROUTER], ids=["8-experts-top-2", "64-top-8"]
)
def test_family_agrees_with_the_reference(widths):
    model, params = scaffold.build("olmoe", **widths)
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(7, done_steps=[(3, 0)])
    stats, _, _, _ = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    tokens = T * B
    assert float(stats["moe_assignments"]) == (
        model.experts_per_token * tokens * model.num_layers
    )
    assert float(stats["moe_load_max_over_mean"]) >= 1.0
    assert float(stats["aux_loss"]) > 0
    # Every block counts its own two-leg application: summed by name.
    assert float(stats["attention_two_leg_applications"]) == (
        model.num_layers
    )
    assert "attention_fused_applications" not in stats


def test_batch_forward_equals_stepwise_acting_across_an_episode_end():
    """The learner's [T, B] forward and the actor's T=1 forwards through
    the rolling cache (M=4 < T: slots are evicted on the way) give the
    same logits and leave the same cache, with an episode ending
    mid-unroll in one row: RoPE sees time differences alone."""
    model, params = scaffold.build("olmoe")
    state = scaffold.warm_state(model, params, seed=2)
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, done_steps=[(3, 1)])
    )


def _layer(num_experts=8, top_k=2, d=16, width=8, tokens=12, seed=0):
    layer = moe.DroplessMoE(d_ff=width, num_experts=num_experts, top_k=top_k)
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, d))
    return layer, x, scaffold.init(layer, jax.random.PRNGKey(seed + 1), x)


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
    y = scaffold.apply(layer)({"params": dict(p, **same)}, x)
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

    y, sown = scaffold.apply(
        layer, mutable=("losses",) + model_stats.COLLECTIONS
    )(params, x)
    stats = model_stats.folded(sown)
    assert float(stats["moe_assignments"]) == K * tokens
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(E / K)
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
    grad = jax.jit(
        jax.grad(total, argnums=(1, 2, 3, 4, 5)), static_argnums=0
    )
    got = grad(moe.dropless_experts, x, gate, *weights)
    want = grad(_every_expert_masked, x, gate, *weights)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


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
    # The d128 transformer EAGERLY, as its numbers were read: as one
    # program its first logit rounds 4e-5 from the pin's 2e-6.
    jit = family == "olmoe"
    params = scaffold.init_params(model, inputs, jit=jit)
    want = dict(_SCAFFOLDING, **last)
    for layer in range(2):
        want.update({f"block_{layer}/{k}": v for k, v in block.items()})
    assert _tree_shapes(params["params"]) == want
    frames = np.random.default_rng(0).integers(
        0, 256, (steps, rows, 8, 8, 1), dtype=np.uint8
    )
    out, new_state = scaffold.forward(model, jit)(
        params, dict(inputs, frame=jnp.asarray(frames)), state
    )
    np.testing.assert_allclose(
        np.asarray(out.policy_logits).ravel()[:3], pinned, rtol=2e-6
    )
    assert jax.tree_util.tree_map(jnp.shape, new_state) == (
        jax.tree_util.tree_map(jnp.shape, state)
    )
