"""The `lfm2` family (models/lfm2.py; a `Recurrent` entry that is nothing
but a convolution's tail in models/transformer.py's walk; heads of 64
through ops/fused_attention.py; a sum of chosen scores raised by a
floor in models/moe.py DroplessMoE): against the plain reference on
seeded weights (loss, gradients, new states), and what is the family's
own, by hand. Batch forward against stepwise acting through the carried
tails and cache and through the state table: tests/test_lfm2_acting.py.
The shares of the routed experts adding up to the uncut expert layer:
an id of tests/test_families_shares.py; to the uncut LAYER, operator and
residual counted once, and what the update's stats say of a share:
tests/test_lfm2_shares.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from tests.test_attention import _dense_body, _fused_case, _gradients_of
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import Lfm2Net, lfm2
from torchbeast_tpu.models.transformer import Recurrent
from torchbeast_tpu.ops import attention

T, B, A = scaffold.FAMILIES["lfm2"].t, scaffold.B, scaffold.A
# The shrunken `PUBLISHED` (tests/family_scaffold.py): the dense conv
# layer, then one period cut to `A c` over a cache of 5 slots, which the
# 6 steps of an unroll evict on the way.
SMALL = scaffold.FAMILIES["lfm2"].small
M, D = SMALL["memory_len"], SMALL["d_model"]
RTOL = ATOL = 2e-5

# Episode ends two steps in a row (2, 3: the second reads ONE product of
# its episode's, no tap from before it) in one row; the other row ends
# one on step 0, where the tail the unroll starts from is dropped whole,
# and one on the last step but one.
ENDS = [(2, 0), (3, 0), (0, 1), (4, 1)]


@pytest.mark.parametrize("ends", [ENDS, []], ids=["ends", "none"])
@pytest.mark.parametrize(
    "expert_share", [(0, 1), (0, 4), (1, 8)],
    ids=["everything-held", "experts-0-of-4", "experts-1-of-8"],
)
def test_family_agrees_with_the_reference(expert_share, ends):
    """Logits, baseline, the tails and the cache handed on, the loss
    and every gradient, from states an actor carried (non-zero tails, a
    part-filled cache), with and without episode ends in the batch; all
    16 experts, a quarter of them (four held, no fewer than the three a
    token chooses: the cell's side) and an eighth (two held under three:
    a window)."""
    model, params = scaffold.build("lfm2", expert_share=expert_share)
    state = scaffold.warm_state(model, params, seed=5)
    assert all(np.any(leaf) for leaf in jax.tree_util.tree_leaves(state))
    assert len(jax.tree_util.tree_leaves(state)) == 1 + 3 + 1
    batch = scaffold.learner_batch(7, ends, t=T)
    stats, grads, _, aux = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    # Every parameter of the three layers takes a gradient, but the
    # biases that choose: the choice is an index.
    for block in ("block_0", "block_1", "block_2"):
        for name, leaf in grads["params"][block].items():
            leaves = dict(leaf) if name == "moe" else {"": leaf}
            bias = leaves.pop("e_score_correction_bias", None)
            assert bias is None or not np.any(bias)
            for inner, value in leaves.items():
                assert np.any(jax.tree_util.tree_leaves(value)[0]), (
                    block, name, inner
                )
    assert float(aux) == 0.0 == float(stats["aux_loss"])
    # What the layers say of themselves.
    assert float(stats["conv_layers"]) == 2
    assert float(stats["conv_state_bytes_per_row"]) == 2 * 4 * 2 * D
    assert float(stats["conv_resets_per_row"]) == len(ends) / 2
    assert float(stats["moe_assignments"]) == 2 * 3 * T * B
    assert "attention_fused_applications" not in stats  # toy widths
    # The steps the two MoE layers sow for their biases are the
    # reference's rule.
    steps = scaffold.reference_bias_steps(model)(params, batch, state)
    for layer, want in zip((1, 2), steps):
        got = stats[learner_lib.PARAM_STEPS_KEY][f"block_{layer}"]["moe"][
            "e_score_correction_bias"
        ]
        np.testing.assert_array_equal(got, want)
        assert set(np.unique(np.abs(want))) <= {0.0, np.float32(0.001)}
    if expert_share == (0, 1):
        assert "moe_held_assignments" not in stats
    else:
        assert 0 < float(stats["moe_held_assignments"]) < 2 * 3 * T * B
        # Two held under three chosen are a window of one rung; four
        # held are no fewer than the three chosen, and at these 12
        # tokens a rung of whole 256-row tiles (1.25 times the even
        # load since PR 56, as twice it before) is over the 36 sorted
        # rows: no window, the pin as it was. The quarter share SWEEPS
        # from 192 tokens on: tests/test_lfm2_shares.py.
        assert ("moe_window_rows" in stats) == (expert_share == (1, 8))


def _conv_block(**overrides):
    fields = dict(
        d_model=D, norm_eps=1e-5, dense_width=48, num_experts=16, held=None,
        experts_per_token=3, expert_width=10, renormalise=True,
        gate_sum_floor=1e-6, routed_scaling=1.0, use_expert_bias=True,
        bias_update_rate=0.001, dtype=jnp.float32, conv_kernel=3,
    )
    return lfm2._ConvBlock(**dict(fields, **overrides))


@pytest.mark.parametrize("end", [1, 3, 5])
def test_a_step_after_done_reads_no_tap_from_before_it(end):
    """The conv operator on two unrolls that differ BEFORE step `end`
    (and start from different tails) and agree from it on: with `done`
    at `end` the outputs from `end` on and the tail handed on are the
    same, the taps of the PRODUCT B * u cut there; without it the two
    steps after `end` differ (a tap reaches two steps back) and the
    third does not."""
    block = _conv_block()
    keys = jax.random.split(jax.random.PRNGKey(end), 5)
    x = jax.random.normal(keys[0], (B, T, D))
    other = x.at[:, :end].set(jax.random.normal(keys[1], (B, end, D)))
    tails = [(jax.random.normal(key, (2, B, D)),) for key in keys[2:4]]
    done = jnp.zeros((B, T), bool)
    params = scaffold.init(block, keys[4], x, tails[0], done)
    apply = scaffold.apply(block)

    def both(done):
        (y, (tail,)), (y_other, (tail_other,)) = (
            apply(params, x, tails[0], done),
            apply(params, other, tails[1], done),
        )
        return np.asarray(y - y_other), np.asarray(tail - tail_other)

    gap, tail_gap = both(done.at[:, end].set(True))
    assert np.any(gap[:, :end])
    np.testing.assert_array_equal(gap[:, end:], 0.0)
    np.testing.assert_array_equal(tail_gap, 0.0)
    gap, tail_gap = both(done)
    assert all(np.any(gap[:, step]) for step in range(end, min(end + 2, T)))
    np.testing.assert_array_equal(gap[:, end + 2 :], 0.0)
    # The tail is the products of the last two steps: it differs where
    # one of them lies before `end`.
    assert np.any(tail_gap) == (end > T - 2)


def test_the_gate_is_on_the_product_before_the_taps_and_after_them():
    """One row by hand: y_t = x_t + out_proj(C_t * sum_k w_k (B * u)_{t -
    2 + k}) over the normed input, the tail standing for steps -2, -1,
    then the dense SwiGLU on the sum's norm. No activation anywhere in
    the operator."""
    block = _conv_block()
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (1, T, D))
    tail = jax.random.normal(keys[1], (2, 1, D))
    done = jnp.zeros((1, T), bool)
    params = scaffold.init(block, keys[2], x, (tail,), done)
    p = params["params"]
    assert sorted(p) == [
        "conv_kernel", "ffn_norm", "in_proj", "operator_norm", "out_proj",
        "w1", "w2", "w3",
    ]
    assert p["conv_kernel"].shape == (3, D)  # no bias

    def norm(v, scale):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-5) * scale

    h = norm(x[0], p["operator_norm"]["scale"]) @ p["in_proj"]["kernel"]
    gate_in, gate_out, u = h[:, :D], h[:, D : 2 * D], h[:, 2 * D :]
    product = jnp.concatenate([tail[:, 0], gate_in * u])  # times -2 .. T-1
    conv = sum(p["conv_kernel"][k] * product[k : k + T] for k in range(3))
    mixed = x[0] + (gate_out * conv) @ p["out_proj"]["kernel"]
    g = norm(mixed, p["ffn_norm"]["scale"])
    want = mixed + (
        jax.nn.silu(g @ p["w1"]["kernel"]) * (g @ p["w3"]["kernel"])
    ) @ p["w2"]["kernel"]
    y, (new_tail,) = scaffold.apply(block)(params, x, (tail,), done)
    np.testing.assert_allclose(y[0], want, RTOL, ATOL)
    np.testing.assert_allclose(new_tail[:, 0], product[-2:], RTOL, ATOL)


@pytest.mark.parametrize("terms", [1, 2], ids=["bf16", "precise"])
def test_heads_of_64_through_the_fused_pass_are_the_dense_body(
    monkeypatch, terms
):
    """`dense_transformer_attend` at heads of 64 (8 query heads on 2
    key/value heads over a part-filled cache of 684 slots, ragged
    blocks): the fused pass (interpreted here; a head padded to the 128
    lanes with zero columns, the scores still over sqrt(64)) gives the
    dense body's output and gradients, the result 64 wide, and a caller
    at `high` gets the kernels that cut two terms an operand (the
    `precise` id: within what two terms leave of a product)."""
    q, k_all, v_all, mask = _fused_case(8, 2, "partly", 684, head_size=64)
    assert q.shape[-1] == k_all.shape[-1] == 64
    assert not attention.fused_pass_applies(q.shape, k_all.shape, None)
    dense = jax.jit(_dense_body)
    want = dense(q, k_all, v_all, mask)
    want_grads = _gradients_of(_dense_body, mask)(q, k_all, v_all)
    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    assert attention.fused_pass_applies(q.shape, k_all.shape, None)
    for narrow in (32, 96, 192):
        assert not attention.fused_pass_applies(
            q.shape[:3] + (narrow,), k_all.shape[:3] + (narrow,), None
        )
    seen = []
    fused_attend = attention.fused_attend

    def counted(q, k_all, v_all, mask, no_grad_keys, terms, scale):
        assert scale is None  # LFM2 names none: head_dim^-0.5
        seen.append((q.shape[-1], terms))
        return fused_attend(q, k_all, v_all, mask, no_grad_keys, terms)

    monkeypatch.setattr(attention, "fused_attend", counted)

    # A FRESH function: traces are cached by function.
    def fused_body(q, k_all, v_all, mask):
        with jax.default_matmul_precision("high" if terms > 1 else "default"):
            return attention.dense_transformer_attend(
                q, k_all, v_all, mask, None, None
            )

    fused = jax.jit(fused_body)
    got = fused(q, k_all, v_all, mask)
    assert got.shape == want.shape and seen == [(64, terms)]
    tolerance = 1e-5 if terms == 1 else 1e-4
    np.testing.assert_allclose(got, want, rtol=tolerance, atol=tolerance)
    got_grads = _gradients_of(fused_body, mask)(q, k_all, v_all)
    for name, a, b in zip(("q", "k_all", "v_all"), got_grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            a, b, rtol=tolerance,
            atol=tolerance * float(jnp.max(jnp.abs(b))), err_msg=name,
        )


def test_the_gates_are_the_chosen_scores_over_their_sum_and_a_floor():
    """One token, by hand: top 4 of 32's rule at 3 of 16. The biased
    sigmoid scores choose; the gates are the chosen scores, bias left
    out, over (their sum + 1e-6); with the floor 0.25 the same gates
    come out smaller by sum / (sum + 0.25), which tells the floor from
    models/moe.py's own 1e-20."""
    layer, x, params = scaffold.expert_layer("lfm2", tokens=1, seed=3)
    assert layer.gate_sum_floor == 1e-6
    p = dict(params["params"])
    p["e_score_correction_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), (16,)
    )
    u = x[0]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])
    chosen = np.argsort(-np.asarray(scores + p["e_score_correction_bias"]))[:3]
    assert set(chosen) != set(np.argsort(-np.asarray(scores))[:3])

    def expert(e):
        return (
            jax.nn.silu(u @ p["w_gate"][e]) * (u @ p["w_up"][e])
        ) @ p["w_down"][e]

    total = jnp.sum(scores[chosen])
    for floor in (1e-6, 0.25):
        want = sum(
            scores[e] / (total + floor) * expert(e) for e in chosen
        )
        got = scaffold.apply(layer.clone(gate_sum_floor=floor))(
            {"params": p}, x
        )[0]
        np.testing.assert_allclose(got, want, RTOL, ATOL)


def test_layers_follow_the_published_order_and_the_state_holds_tails():
    model, params = scaffold.build("lfm2")
    tail = Recurrent(((2, D),))
    assert model.layers() == (
        ("conv", True), ("full_attention", False), ("conv", False),
    )
    assert model.layer_caches() == (tail, (M, 2, 8), tail)
    state = model.initial_state(3)
    assert [[leaf.shape for leaf in item] for item in state] == [
        [(2, 3, D)], [(M, 3, 2, 8), (M, 3, 2, 8), (M, 3)], [(2, 3, D)],
    ]
    blocks = params["params"]
    assert sorted(blocks["block_0"]) == [
        "conv_kernel", "ffn_norm", "in_proj", "operator_norm", "out_proj",
        "w1", "w2", "w3",
    ]
    assert sorted(blocks["block_1"]) == [
        "ffn_norm", "k", "k_norm", "moe", "o", "operator_norm", "q",
        "q_norm", "v",
    ]
    assert sorted(blocks["block_2"]) == [
        "conv_kernel", "ffn_norm", "in_proj", "moe", "operator_norm",
        "out_proj",
    ]
    assert sorted(blocks["block_1"]["moe"]) == [
        "e_score_correction_bias", "router", "w_down", "w_gate", "w_up",
    ]
    assert blocks["block_0"]["in_proj"]["kernel"].shape == (D, 3 * D)
    assert blocks["block_1"]["q_norm"]["scale"].shape == (8,)
    # As initialised (the scaffold perturbs them): scales at one, the
    # biases at zero, the taps within +-1/sqrt(3).
    fresh = scaffold.init_params(model, scaffold.inputs(0, t=T))["params"]
    assert np.all(np.asarray(fresh["block_1"]["q_norm"]["scale"]) == 1)
    assert not np.any(fresh["block_2"]["moe"]["e_score_correction_bias"])
    assert np.all(np.abs(fresh["block_0"]["conv_kernel"]) <= 3 ** -0.5)
    # The published order: `c c A` then four `c c c A`, then `c c A c c`,
    # the first two dense; a cut is published layer 1 and whole periods.
    whole = Lfm2Net(num_actions=A, **dict(SMALL, num_layers=24))
    kinds = "".join("A" if k == "full_attention" else "c" for k, _ in whole.layers())
    assert kinds == "ccA" + "cccA" * 4 + "ccAcc"
    assert [dense for _, dense in whole.layers()] == [True] * 2 + [False] * 22
    assert [type(e) is tuple for e in whole.layer_caches()].count(True) == 6
    cut = Lfm2Net(num_actions=A, **dict(
        SMALL, layer_period=lfm2.PUBLISHED["layer_period"], num_layers=9
    ))
    assert cut.layers() == tuple(
        (kind, layer < 2) for layer, kind in enumerate(
            lfm2.PUBLISHED["layer_types"]
        )
    )[1:6] + (("full_attention", False),) + (("conv", False),) * 3
    for bad in (4, 1, 6, 23):
        with pytest.raises(ValueError, match=r"1 \+ 4k layers, or is all 24"):
            Lfm2Net(num_actions=A, **dict(
                SMALL, layer_period=lfm2.PUBLISHED["layer_period"],
                num_layers=bad,
            ))


def test_the_new_scopes_are_in_the_lowered_update():
    model, params = scaffold.build("lfm2")
    batch = scaffold.learner_batch(1, ENDS, t=T)
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    text = jax.jit(jax.grad(
        lambda p: learner_lib.compute_loss(
            model, p, batch, model.initial_state(B), hp
        )[0]
    )).lower(params).as_text(debug_info=True)
    for scope in (
        "conv_operator/conv_in_proj", "conv_operator/conv_gate_taps",
        "conv_operator/conv_out_proj", "/attention/", "dense_mlp",
        "moe_route", "moe_dispatch", "moe_experts", "moe_combine",
    ):
        assert scope in text, scope
