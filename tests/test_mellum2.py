"""The `mellum2` family (models/mellum2.py; grouped-query heads in
ops/attention.py; the share of the experts in models/moe.py DroplessMoE;
per-layer caches in models/transformer.py): against the plain reference
on seeded weights, batch forward against stepwise acting through the two
rolling caches, what a window layer cannot see and a full layer can,
and YaRN by hand (the shares of the experts adding up to the layer: an
id of tests/test_families_shares.py)."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench.reference import mellum2_policy as reference
from tests import family_scaffold as scaffold
from tests.family_scaffold import YARN_CONFIG
from torchbeast_tpu.models import Mellum2Net, create_model, mellum2, moe
from torchbeast_tpu.ops.attention import dense_transformer_attend

T, B, A = 6, scaffold.B, scaffold.A
# The shrunken `PUBLISHED` (tests/family_scaffold.py): the window layers'
# caches are 3 slots, the full layer's `M`.
SMALL = scaffold.FAMILIES["mellum2"].small
M = SMALL["memory_len"]
# As tests/test_olmoe.py: on the CPU both sides compute in float32 at
# full precision and differ by the order of their sums.
RTOL = ATOL = 1e-5


@pytest.mark.parametrize(
    "share", [(0, 1), (1, 4)], ids=["all-8-experts", "share-1-of-4"]
)
def test_family_agrees_with_the_reference(share):
    model, params = scaffold.build("mellum2", expert_share=share)
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(7, done_steps=[(3, 0)])
    stats, _, _, _ = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    # Routing is over all the experts whatever is held.
    assert float(stats["moe_assignments"]) == 2 * T * B * 4
    assert float(stats["aux_loss"]) > 0
    if share == (0, 1):
        assert "moe_held_assignments" not in stats
    else:
        assert 0 < float(stats["moe_held_assignments"]) < 2 * T * B * 4
        assert float(stats["moe_held_load_max_over_mean"]) >= 1.0
    # As many held as chosen, and at these 12 tokens a rung of whole
    # 256-row tiles (1.25 times the even load since PR 56, as twice it
    # before) is over the 24 sorted rows: no window, the pin as it was.
    # The quarter share SWEEPS from 384 tokens on: the next test.
    assert "moe_window_rows" not in stats
    assert "moe_window_short_applications" not in stats


@pytest.mark.parametrize(
    "expert_share, even_router, sweeps",
    [((1, 4), False, 1), ((1, 4), True, 0), ((0, 4), True, 3)],
    ids=["as-routed", "no-row", "every-token-on-both-held-experts"],
)
def test_update_stats_say_how_far_the_quarter_share_was_swept(
    expert_share, even_router, sweeps
):
    """PR 56: two of 8 experts held under two a token (a quarter with
    `held >= K`, the cell's 16 of 64 under 8), 384 tokens. Twice the
    even load's 192 rows in row tiles is 512, not under half the 768
    sorted rows, and until PR 56 they were all permuted; 1.25 times it
    is a rung of 256. The update's stats carry what the sweep took,
    summed over the four layers: one rung each as initialised; with a
    router of zeros every token's two are experts 0 and 1 (ties go to
    the first), so none with experts 2-3 held, and with 0-1 held all
    three rungs of the window, every one of the 768 assignments
    computed."""
    rows = 64
    model, params = scaffold.build("mellum2", expert_share=expert_share)
    assert moe.window_rungs(T * rows, 2, 2, 8) == (256, 2 * T * rows)
    if even_router:
        params = scaffold.with_zeroed(
            params, ("block_0", "block_1", "block_2", "block_3")
        )
    stats = scaffold.forward_stats(model, params, rows, [(3, 1)], T)
    held = float(stats["moe_held_assignments"]) / 4  # a layer
    if even_router:
        assert held == (2 * T * rows if sweeps else 0)
    else:
        assert 0 < held <= 256
    assert float(stats["moe_window_rows"]) == 4 * 256 * sweeps
    assert float(stats["moe_window_short_applications"]) == 4 * (sweeps <= 1)


@pytest.mark.parametrize("unrolls", [0, 1, 2], ids=["empty", "part", "full"])
def test_batch_forward_equals_stepwise_acting_through_both_caches(unrolls):
    """The learner's [T, B] forward and the actor's T=1 forwards through
    the rolling caches (window layers 3 slots, the full layer 9; T=6
    evicts from the first on the way) give the same logits and leave the
    same caches, from caches of any fill (after one unroll the 3-slot
    window caches are full and the 9-slot full cache is not; after two,
    both are) and across an episode end."""
    model, params = scaffold.build("mellum2")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, done_steps=[(3, 1)])
    )


def test_layers_carry_caches_of_their_kind():
    model, _ = scaffold.build("mellum2")
    assert [model.layer_kind(i) for i in range(4)] == [
        mellum2.SLIDING, mellum2.SLIDING, mellum2.SLIDING, mellum2.FULL,
    ]
    state = model.initial_state(3)
    assert [layer[0].shape for layer in state] == (
        [(3, 3, 2, 16)] * 3 + [(M, 3, 2, 16)]
    )
    assert [layer[2].shape for layer in state] == [(3, 3)] * 3 + [(M, 3)]
    # A full cache shorter than the window: every layer carries it.
    short = Mellum2Net(num_actions=A, **dict(SMALL, memory_len=2))
    assert [m for m, _, _ in short.layer_caches()] == [2, 2, 2, 2]
    published = create_model("mellum2", num_actions=6, num_layers=8)
    assert [m for m, _, _ in published.layer_caches()] == (
        [1023, 1023, 1023, 4095] * 2
    )
    assert published.layer_caches()[0][1:] == (4, 128)


def _one_block(kind, memory_len):
    block = mellum2._Mellum2Block(
        kind=kind, d_model=48, num_heads=4, kv_heads=2, head_dim=16,
        memory_len=memory_len, num_experts=8, held=None,
        experts_per_token=2, expert_width=24, renormalise=True,
        rms_norm_eps=1e-6, rope_theta=500000.0,
        yarn=mellum2.PUBLISHED["yarn"], aux_loss_weight=0.001,
    )
    return block


def test_a_window_layer_ignores_the_key_a_full_layer_reads():
    """One query (T=1) over a cache of 9 valid slots. A layer with the
    window's cache of 3 slots is handed the last 3 and cannot tell what
    came before; a full layer's output moves when the key and value 4
    steps back (one past the window of 4) change."""
    from torchbeast_tpu.ops.attention import (
        band_by_leg,
        band_relative_offsets,
    )

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, 1, 48)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, M, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, M, 2, 16)), jnp.float32)
    # The slot 4 steps before the query: slot M - 4.
    k_moved = k.at[:, M - 4].add(1.0)
    v_moved = v.at[:, M - 4].add(1.0)

    def run(kind, slots, k, v):
        cache_band, seq_band = band_by_leg(1, slots)
        masks = (
            jnp.broadcast_to(cache_band[None], (B, 1, slots)),
            jnp.broadcast_to(seq_band[None], (B, 1, 1)),
        )
        block = _one_block(kind, slots)
        # The block contract: the cache as the state holds it.
        cache = tuple(c[:, -slots:].transpose(1, 0, 2, 3) for c in (k, v))
        params = scaffold.init(block, jax.random.PRNGKey(0), x, cache, *masks)
        return scaffold.apply(block)(params, x, cache, *masks)[0]

    window = SMALL["sliding_window"] - 1
    np.testing.assert_array_equal(
        run(mellum2.SLIDING, window, k, v),
        run(mellum2.SLIDING, window, k_moved, v_moved),
    )
    full, full_moved = (
        run(mellum2.FULL, M, k, v), run(mellum2.FULL, M, k_moved, v_moved)
    )
    assert float(jnp.max(jnp.abs(full - full_moved))) > 1e-3
    # And in the net: the window layers' band ends at 3 steps back.
    band, _ = band_relative_offsets(T, window)
    assert bool(band[4, window + 1]) and not bool(band[4, window])


def test_yarn_by_hand():
    """`rope_parameters.full_attention` at the published numbers, by the
    formulas of ISSUE 32: c(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000);
    c(32) = 18.08 -> low 18, c(1) = 34.98 -> high 35; dimension i keeps
    its frequency up to 18, has it divided by 16 from 35, a ramp
    between."""
    factor, original, fast, slow, attention = mellum2.PUBLISHED["yarn"]
    theta, dim = mellum2.PUBLISHED["rope_theta"], 128
    c = lambda r: dim * math.log(original / (2 * math.pi * r)) / (
        2 * math.log(theta)
    )
    assert (math.floor(c(fast)), math.ceil(c(slow))) == (18, 35)
    assert c(fast) == pytest.approx(18.0806, abs=1e-3)
    assert c(slow) == pytest.approx(34.984, abs=1e-3)
    inv_freq = mellum2.rope_yarn(theta, dim, factor, original, fast, slow)
    plain = np.array([theta ** (-2 * i / dim) for i in range(64)])
    np.testing.assert_allclose(mellum2.rope_default(theta, dim), plain, 1e-12)
    np.testing.assert_allclose(inv_freq[:19], plain[:19], 1e-12)
    np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16, 1e-12)
    # i = 26: ramp (26 - 18) / 17 = 8/17.
    assert inv_freq[26] == pytest.approx(
        plain[26] * ((8 / 17) / 16 + 9 / 17), rel=1e-12
    )
    assert plain[1] == pytest.approx(0.814617, rel=1e-5)
    # The attention factor is 0.1 ln(16) + 1, on cos and sin alike.
    assert attention == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    # The reference works the same numbers out on its own.
    ref_freq, ref_factor = reference.inv_freq_and_factor(YARN_CONFIG, dim)
    np.testing.assert_allclose(ref_freq, inv_freq, 1e-12)
    assert ref_factor == attention
    x = jnp.ones((1, 1, 1, 128))
    rotated = mellum2.rope_rotate(
        x, jnp.zeros((1,)), jnp.asarray(inv_freq, jnp.float32), attention
    )
    np.testing.assert_allclose(rotated, attention * x, 1e-6)


def test_renormalised_gates_sum_to_one():
    """With every expert the same matrix the layer's output is exactly
    that expert's: the chosen gates sum to one (OLMoE's, as they are,
    sum to less: tests/test_olmoe.py)."""
    layer, x, params = scaffold.expert_layer("mellum2")
    p = params["params"]
    same = {
        k: jnp.broadcast_to(p[k][:1], p[k].shape)
        for k in ("w_gate", "w_up", "w_down")
    }
    y = scaffold.apply(layer)({"params": dict(p, **same)}, x)
    expert = (
        jax.nn.silu(x @ p["w_gate"][0]) * (x @ p["w_up"][0])
    ) @ p["w_down"][0]
    np.testing.assert_allclose(y, expert, RTOL, ATOL)
    probs = jax.nn.softmax(x @ p["router"]["kernel"])
    gate, _ = jax.lax.top_k(probs, 2)
    assert float(gate.sum(axis=-1).max()) < 0.95


def test_a_share_costs_no_rows_of_the_other_experts():
    """The grouped matmul over the rows of all 8 experts with the
    weights of experts 2..3: rows of the other groups come out zero,
    forward and in the gradient with respect to the rows, and the weight
    gradient is the held experts' alone."""
    rng = np.random.default_rng(1)
    sizes = jnp.asarray([3, 0, 5, 2, 4, 1, 0, 1], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    weights = jnp.asarray(rng.standard_normal((8, 8, 4)), jnp.float32)
    held = weights[2:4]
    out = moe.grouped_matmul(rows, held, sizes, 2)
    whole = moe.grouped_matmul(rows, weights, sizes)
    np.testing.assert_allclose(out[3:10], whole[3:10], RTOL, ATOL)
    assert not np.any(out[:3]) and not np.any(out[10:])
    loss = lambda r, w, first: jnp.sum(
        jnp.sin(moe.grouped_matmul(r, w, sizes, first))[3:10]
    )
    grad = jax.jit(jax.grad(loss, argnums=(0, 1)), static_argnums=2)
    d_rows, d_held = grad(rows, held, 2)
    w_rows, w_all = grad(rows, weights, None)
    np.testing.assert_allclose(d_rows[3:10], w_rows[3:10], RTOL, ATOL)
    assert not np.any(d_rows[:3]) and not np.any(d_rows[10:])
    np.testing.assert_allclose(d_held, w_all[2:4], RTOL, ATOL)


def test_grouped_query_heads_equal_repeated_keys():
    """[B, T, Hkv, G, D] against K and V repeated G times, which is what
    the contraction never materialises."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((2, 3, 8, 4)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 7, 2, 4)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 7, 2, 4)), jnp.float32)
    mask = jnp.asarray(rng.random((2, 3, 7)) < 0.7).at[:, :, -1].set(True)
    offsets = jnp.zeros((3, 7), jnp.int32)
    bias = jnp.asarray(rng.standard_normal((8, 5)), jnp.float32)
    for rel_bias in (None, bias):
        grouped = dense_transformer_attend(q, k, v, mask, offsets, rel_bias)
        repeated = dense_transformer_attend(
            q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2),
            mask, offsets, rel_bias,
        )
        np.testing.assert_allclose(grouped, repeated, RTOL, ATOL)
    with pytest.raises(ValueError, match="do not divide"):
        dense_transformer_attend(q, k[:, :, :1].repeat(3, 2), v, mask, offsets, None)


def test_blocks_over_the_threshold_take_the_fused_pass_and_are_counted(
    monkeypatch
):
    """Which body attends is chosen from the shapes (ops/attention.py
    `fused_pass_applies`), so at these toy widths every block keeps the
    dense body and the update's stats hold no `attention_fused_
    applications`. With the threshold lowered to reach them the four
    blocks take the fused pass (interpreted here; rematerialised, as
    the cell runs them), the stats count 4, and the loss and the
    gradients are the dense body's."""
    from torchbeast_tpu.ops import attention

    # Heads of 128: the fused pass reads a head as a block of lanes.
    model, params = scaffold.build(
        "mellum2", expert_share=(1, 4), head_dim=128
    )
    model = model.clone(remat=True)
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(9, done_steps=[(1, 1)])

    def run():
        # A trace of its own each time (`__wrapped__`: not the
        # scaffold's memoised one): traces are cached by the function
        # traced, the rule is read at the trace.
        loss, stats, grads = scaffold.loss_and_grads.__wrapped__(model)(
            params, batch, state
        )
        return loss, stats, scaffold.flat(grads)

    loss, stats, grads = run()
    assert "attention_fused_applications" not in stats
    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    loss_f, stats_f, grads_f = run()
    assert float(stats_f["attention_fused_applications"]) == 4.0
    # One term an operand: no product is cut in a kernel, and no zero
    # is sown to say so.
    assert "attention_products_cut_in_kernel" not in stats_f
    assert float(loss_f) == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_allclose(
        grads_f, grads, rtol=0, atol=RTOL * float(jnp.max(jnp.abs(grads)))
    )
