"""The `qwen3next` family (models/qwen3next.py; a matrix-valued carried
state beside a window in models/transformer.py's walk; a shared expert
scaled by a token's gate in models/moe.py DroplessMoE): against the
plain reference on seeded weights (loss, gradients, new states), and
batch forward against stepwise acting through the carried states and
through the state table. The chunked delta rule and its triangular
solve on their own: tests/test_qwen3next_delta.py; the shares of the
routed experts adding up to the uncut layer: an id of
tests/test_families_shares.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import Qwen3NextNet, moe, qwen3next
from torchbeast_tpu.models.transformer import Recurrent

T, B, A = scaffold.FAMILIES["qwen3next"].t, scaffold.B, scaffold.A
# The shrunken `PUBLISHED` (tests/family_scaffold.py): one Gated DeltaNet
# layer scanned in chunks of 4 steps (the 11 steps of an unroll are two
# whole chunks and one padded) and one gated attention layer over a
# cache of 5 slots, which the unroll evicts on the way.
SMALL = scaffold.FAMILIES["qwen3next"].small
M = SMALL["memory_len"]
# On the CPU both sides compute in float32 at full precision and differ
# by the order of their sums: the chunked form's solve and its [Dk, Dk]
# matrix a chunk against 11 rank-one steps. One bfloat16 pass in the
# scan would read 1e-3 to 1e-2 here.
RTOL = ATOL = 2e-5

# Episode ends at a chunk's first step (4), at its last (7), and twice in
# a row (8, 9), in one row; the other row ends one on step 0, where the
# state the unroll starts from is dropped whole.
ENDS = [(4, 0), (7, 0), (8, 0), (9, 0), (0, 1), (5, 1)]


@pytest.mark.parametrize("ends", [ENDS[:4] + [(5, 1)], []], ids=["ends", "none"])
@pytest.mark.parametrize(
    "expert_share", [(0, 1), (1, 4), (1, 8)],
    ids=["everything-held", "experts-1-of-4", "experts-1-of-8"],
)
def test_family_agrees_with_the_reference(expert_share, ends):
    """Logits, baseline, the states handed on, the loss and every
    gradient, from states an actor carried, with and without episode
    ends in the batch; with 4 of 16 experts held (as many as a token
    chooses, or more) 22 tokens leave no room for a rung and all the
    sorted rows are permuted, 2 of 16 see a window of one rung."""
    model, params = scaffold.build("qwen3next", expert_share=expert_share)
    state = scaffold.warm_state(model, params, seed=5)
    assert all(np.any(leaf) for leaf in jax.tree_util.tree_leaves(state))
    assert len(jax.tree_util.tree_leaves(state)) == 5
    batch = scaffold.learner_batch(7, ends, t=T)
    stats, grads, _, aux = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    # Every parameter of both layers (a mixer and a MoE block each)
    # takes a gradient.
    for block in ("block_0", "block_1", "block_2", "block_3"):
        for name, leaf in grads["params"][block].items():
            assert np.any(jax.tree_util.tree_leaves(leaf)[0]), (block, name)
    # The softmax load-balance term of both layers is in the loss.
    assert float(stats["aux_loss"]) == pytest.approx(float(aux), rel=1e-5)
    assert float(aux) > 0.001 * 2 * 0.99
    # What the layers say of themselves.
    assert float(stats["delta_applications"]) == 1
    assert float(stats["delta_chunks"]) == 3  # 11 steps in chunks of 4
    assert float(stats["delta_resets_per_row"]) == len(ends) / 2
    assert float(stats["delta_state_bytes_per_row"]) == 4 * (
        4 * 6 * 5 + 3 * (2 * 2 * 6 + 4 * 5)
    )
    assert float(stats["attention_gated_applications"]) == 1
    assert float(stats["moe_shared_applications"]) == 2
    assert float(stats["moe_assignments"]) == 2 * 3 * T * B
    assert "attention_fused_applications" not in stats  # toy widths
    if expert_share == (0, 1):
        assert "moe_held_assignments" not in stats
    else:
        assert 0 < float(stats["moe_held_assignments"]) < 2 * 3 * T * B
        # Four held are no fewer than the three chosen, and 66 sorted
        # rows are under a rung: no window.
        assert ("moe_window_rows" in stats) == (expert_share == (1, 8))


@pytest.mark.parametrize(
    "expert_share, even_router, sweeps",
    [((1, 16), False, 1), ((1, 16), True, 0), ((0, 16), True, 5)],
    ids=["as-routed", "no-row", "every-token-on-three-held-experts"],
)
def test_update_stats_say_how_far_the_window_was_swept(
    expert_share, even_router, sweeps
):
    """Four of 64 experts held under three a token (`held >= K`, the
    cell's side of `moe.window_rungs`), 352 tokens: the window is all
    1,056 sorted rows, its rungs 256, and the update's stats carry the
    rows the kernels swept and the layers that needed one rung alone,
    as the held experts' sizes imply, summed over the two MoE parts:
    one rung each as initialised; with a router of zeros every token's
    three are experts 0, 1, 2 (ties go to the first), so none with
    experts 4-7 held, and with 0-3 held all five rungs, every one of
    the 1,056 assignments computed (`moe_window_short_applications`
    0)."""
    rows = 32
    model, params = scaffold.build(
        "qwen3next", expert_share=expert_share, num_experts=64
    )
    assert moe.window_rungs(T * rows, 3, 4, 64) == (256, 3 * T * rows)
    if even_router:
        params = scaffold.with_zeroed(params, ("block_1", "block_3"))
    stats = scaffold.forward_stats(model, params, rows, ENDS, T)
    held = float(stats["moe_held_assignments"]) / 2  # a layer
    if even_router:
        assert held == (3 * T * rows if sweeps else 0)
    assert sweeps == -(-held // 256)
    assert float(stats["moe_window_rows"]) == 2 * 256 * sweeps
    assert float(stats["moe_window_short_applications"]) == 2 * (sweeps <= 1)


@pytest.mark.parametrize("unrolls", [0, 2], ids=["empty", "warm"])
def test_batch_forward_equals_stepwise_acting_through_the_carried_states(
    unrolls
):
    """The learner's [T, B] forward (the delta rule in chunks of 4, the
    convolution as shifted adds over the unroll, attention over [cache;
    unroll] with RoPE on a head's first 4 columns) and the actor's T=1
    forwards through the matrix state, the conv tail and the rolling
    cache of un-rotated keys (5 slots: the 11 steps evict on the way)
    give the same logits and leave the same states, across episode ends
    inside a chunk."""
    model, params = scaffold.build("qwen3next")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, ENDS, t=T)
    )


@pytest.mark.parametrize("via", ["reset", "rebuild"])
def test_stepwise_acting_through_the_state_table_equals_the_batch_forward(
    via
):
    """Three actors' slots in a `DeviceStateTable` whose rows hold BOTH
    kinds of state: the DeltaNet layer's matrix state and conv tail (S
    [4, 1, 6, 5], tail [3, 1, 44]) and the attention layer's window (k,
    v [M, 1, 2, 16], valid [M, 1]). The rows arrive in another order
    every step and episodes end on the way; every step's logits equal
    the batch forward's and the table ends with what that forward
    leaves; reset and rebuild bring back zeros of every shape."""
    model, params = scaffold.build("qwen3next")
    shapes = [
        [(4, 1, 6, 5), (3, 1, 44)], [(M, 1, 2, 16), (M, 1, 2, 16), (M, 1)],
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


def test_rope_turns_a_heads_first_columns_alone():
    """The attention block on one query and a cache: the same keys one
    slot OLDER give another output (their positions enter the scores),
    and with the query zero on a head's first `rotary_dim` columns they
    give the same: the other 12 columns of a head are not turned. (That
    scores depend on time differences alone is the stepwise case above,
    whose cache of un-rotated keys rolls.)"""
    block = qwen3next._GatedAttentionBlock(
        d_model=32, num_heads=4, kv_heads=2, head_dim=16, rotary_dim=4,
        rope_theta=1e7, memory_len=M, rms_norm_eps=1e-6,
    )
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (B, 1, 32))
    cache = tuple(jax.random.normal(key, (M, B, 2, 16)) for key in keys[1:3])
    older = tuple(jnp.roll(c, -1, axis=0) for c in cache)
    slots = jnp.arange(M)[None, None, :]
    seq_mask = jnp.ones((B, 1, 1), bool)
    params = scaffold.init(
        block, keys[3], x, cache, jnp.ones((B, 1, M), bool), seq_mask
    )
    apply = scaffold.apply(block)

    def both(params):
        return (
            apply(params, x, cache, jnp.broadcast_to(slots >= 1, (B, 1, M)),
                  seq_mask)[0],
            apply(params, x, older,
                  jnp.broadcast_to(slots < M - 1, (B, 1, M)), seq_mask)[0],
        )

    here, there = both(params)
    assert float(jnp.max(jnp.abs(here - there))) > 1e-4
    # The query's part of `q` (a head's [query 16 | gate 16]) zero on
    # its first 4 columns; the norm keeps zeros.
    inner = dict(params["params"])
    kernel = inner["q"]["kernel"].reshape(32, 4, 2, 16)
    kernel = kernel.at[:, :, 0, :4].set(0.0).reshape(32, -1)
    here, there = both({"params": dict(inner, q={"kernel": kernel})})
    np.testing.assert_allclose(here, there, RTOL, ATOL)


def test_the_gates_sum_to_one_and_the_shared_expert_has_a_gate_a_token():
    """One token, by hand: 10-of-512's rule at 3 of 16. The gates are
    the chosen softmax probabilities over their sum; the shared SwiGLU
    is scaled by sigmoid(w_g . u), one number a token."""
    layer, x, params = scaffold.expert_layer("qwen3next", tokens=1, seed=3)
    p = params["params"]
    u = x[0]
    probs = jax.nn.softmax(u @ p["router"]["kernel"])
    chosen = np.argsort(-np.asarray(probs))[:3]
    gates = probs[chosen] / jnp.sum(probs[chosen])
    assert float(jnp.sum(gates)) == pytest.approx(1.0, rel=1e-6)

    def swiglu(gate, up, down):
        return (jax.nn.silu(u @ gate) * (u @ up)) @ down

    routed = sum(
        g * swiglu(p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        for g, e in zip(gates, chosen)
    )
    token_gate = jax.nn.sigmoid(u @ p["shared_expert_gate"]["kernel"])
    assert token_gate.shape == (1,) and 0.05 < float(token_gate[0]) < 0.95
    want = routed + token_gate * swiglu(
        p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"],
    )
    np.testing.assert_allclose(
        scaffold.apply(layer)(params, x)[0], want, RTOL, ATOL
    )


def test_layers_follow_the_interval_and_the_state_holds_what_they_carry():
    model, params = scaffold.build("qwen3next")
    carried = Recurrent(((4, 6, 5), (3, 2 * 2 * 6 + 4 * 5)))
    # A layer is its mixer's entry, then its MoE part's (nothing).
    assert model.layer_caches() == (carried, None, (M, 2, 16), None)
    state = model.initial_state(3)
    assert [[leaf.shape for leaf in item] for item in state] == [
        [(4, 3, 6, 5), (3, 3, 44)], [(M, 3, 2, 16), (M, 3, 2, 16), (M, 3)],
    ]
    blocks = params["params"]
    assert sorted(blocks["block_0"]) == [
        "A_log", "conv_kernel", "dt_bias", "gate_norm", "in_proj_ba",
        "in_proj_qkvz", "norm", "out_proj",
    ]
    assert sorted(blocks["block_2"]) == [
        "k", "k_norm", "norm", "o", "q", "q_norm", "v",
    ]
    assert sorted(blocks["block_1"]) == sorted(blocks["block_3"]) == [
        "moe", "norm",
    ]
    assert sorted(blocks["block_1"]["moe"]) == [
        "router", "shared_down", "shared_expert_gate", "shared_gate",
        "shared_up", "w_down", "w_gate", "w_up",
    ]
    # q, k for 2 key heads of 6; v, z for 4 value heads of 5; b, a.
    assert blocks["block_0"]["in_proj_qkvz"]["kernel"].shape == (
        32, 2 * 2 * 6 + 2 * 4 * 5
    )
    assert blocks["block_0"]["in_proj_ba"]["kernel"].shape == (32, 8)
    assert blocks["block_0"]["conv_kernel"].shape == (4, 44)  # no bias
    assert blocks["block_0"]["gate_norm"].shape == (5,)  # one for all heads
    # The query and its gate side by side, a head.
    assert blocks["block_2"]["q"]["kernel"].shape == (32, 4 * 2 * 16)
    assert blocks["block_2"]["q_norm"]["scale"].shape == (16,)
    # As initialised (the scaffold perturbs the norms): zero-centred
    # scales at zero, the gated norm at one; A in (0, 16), softplus(dt_
    # bias) in [0.001, 0.1].
    fresh = scaffold.init_params(model, scaffold.inputs(0, t=T))["params"]
    assert not np.any(fresh["block_0"]["norm"]["scale"])
    assert not np.any(fresh["final_norm"]["scale"])
    assert np.all(np.asarray(fresh["block_0"]["gate_norm"]) == 1)
    assert np.all(np.exp(fresh["block_0"]["A_log"]) <= 16)
    step = jax.nn.softplus(fresh["block_0"]["dt_bias"])
    assert np.all(step >= 0.001 - 1e-6) and np.all(step <= 0.1 + 1e-6)
    # The published interval: three DeltaNet layers, then attention.
    whole = Qwen3NextNet(
        num_actions=A, **dict(SMALL, attention_interval=4, num_layers=8)
    )
    assert [type(entry) for entry in whole.layer_caches()[::2]] == [
        Recurrent, Recurrent, Recurrent, tuple,
    ] * 2
    assert whole.layer_caches()[1::2] == (None,) * 8
    assert len(whole.initial_state(1)) == 8
    with pytest.raises(ValueError, match="whole periods of 4"):
        Qwen3NextNet(
            num_actions=A, **dict(SMALL, attention_interval=4, num_layers=6)
        )


def test_the_new_scopes_are_in_the_lowered_update():
    model, params = scaffold.build("qwen3next")
    batch = scaffold.learner_batch(1, ENDS, t=T)
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    text = jax.jit(jax.grad(
        lambda p: learner_lib.compute_loss(
            model, p, batch, model.initial_state(B), hp
        )[0]
    )).lower(params).as_text(debug_info=True)
    for scope in (
        "deltanet_in_proj", "deltanet_conv", "delta_scan/delta_intra",
        "delta_scan/delta_intra/delta_solve", "delta_scan/delta_states",
        "delta_scan/delta_inter", "deltanet_gate_norm", "deltanet_out_proj",
        "attention_full", "attention_full/attention_gate", "moe_route",
        "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
        "moe_shared/moe_shared_gate",
    ):
        assert scope in text, scope
