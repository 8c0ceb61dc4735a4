"""The `lfm2` family's shares of the routed experts: a conv MoE layer's
four quarter shares add up to the uncut layer, operator and residual
counted once; the update's stats say how far a quarter share was swept
and whether the fused pass cut its products in the kernel."""

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from tests import family_scaffold as scaffold
from tests.test_lfm2 import ENDS, B, D, T, _conv_block
from torchbeast_tpu.models import moe
from torchbeast_tpu.ops import attention


@pytest.mark.parametrize(
    "expert_share, even_router, sweeps",
    [((1, 4), False, 1), ((1, 4), True, 0), ((0, 4), True, 3)],
    ids=["as-routed", "no-row", "every-token-on-three-held-experts"],
)
def test_update_stats_say_how_far_the_quarter_share_was_swept(
    expert_share, even_router, sweeps
):
    """PR 56: four of 16 experts held under three a token (a quarter
    with `held >= K`, the cell's 8 of 32 under 4), 192 tokens. Twice
    the even load's 144 rows in row tiles is 512, not under half the
    576 sorted rows, and until PR 56 they were all permuted; 1.25 times
    it is a rung of 256. The update's stats carry what the sweep took,
    summed over the two MoE layers: one rung each as initialised; with
    a router of zeros every token's three are experts 0, 1, 2 (ties go
    to the first, the bias zeroed too), so none with experts 4-7 held, and
    with 0-3 held all three rungs of the window, every one of the 576
    assignments computed."""
    rows = 32
    model, params = scaffold.build("lfm2", expert_share=expert_share)
    assert moe.window_rungs(T * rows, 3, 4, 16) == (256, 3 * T * rows)
    if even_router:
        # The router, and the bias the scaffold moved off zero.
        params = scaffold.with_zeroed(
            params, ("block_1", "block_2"),
            ("router", "e_score_correction_bias"),
        )
    stats = scaffold.forward_stats(model, params, rows, ENDS, T)
    held = float(stats["moe_held_assignments"]) / 2  # a layer
    if even_router:
        assert held == (3 * T * rows if sweeps else 0)
    assert sweeps == -(-held // 256)
    assert float(stats["moe_window_rows"]) == 2 * 256 * sweeps
    assert float(stats["moe_window_short_applications"]) == 2 * (sweeps <= 1)


def _layer_side(side, count, E):
    """`(x, tail, done, p, first) -> the conv MoE layer holding experts
    first .. first + count of E`, on the program (`_ConvBlock`) or built
    of the reference's parts as its `forward` builds a layer."""
    if side == "program":
        def layer(x, tail, done, p, first):
            block = _conv_block(
                dense_width=0, num_experts=E, experts_per_token=4,
                held=None if count == E else (first, count),
            )
            return block.apply({"params": p}, x, (tail,), done)[0]

        return layer
    reference = scaffold.FAMILIES["lfm2"].reference
    config = {
        "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
        "published_num_experts": E, "num_experts": count,
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1.0,
    }

    def layer(x, tail, done, p, first):
        out, _ = reference._conv_operator(
            reference._norm(x, p["operator_norm"], 1e-5).transpose(1, 0, 2),
            done.T, p, tail, config,
        )
        h = x + out.transpose(1, 0, 2)
        g = reference._norm(h, p["ffn_norm"], 1e-5).reshape(-1, D)
        y = reference._experts(
            g, p["moe"], dict(config, expert_share=[first // count, 0])
        )
        return h + y.reshape(h.shape)

    return layer


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_four_shares_add_up_to_the_uncut_layer(side):
    """A whole conv MoE layer at the published counts (32 experts, top
    4, a quarter held a chip): every chip computes the operator and the
    residual alike, so with h = x + operator(norm(x)) COUNTED ONCE the
    four shares' routed parts, y_i - h, add up to the uncut layer's y -
    h; values and the gradient with respect to x, on the program and on
    the reference. h is the layer with its experts' `w_down` zeroed."""
    E, shares = 32, 4
    count = E // shares
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(keys[0], (B, T, D))
    tail = jax.random.normal(keys[1], (2, B, D))
    done = jnp.zeros((B, T), bool).at[0, 3].set(True)
    uncut = _conv_block(dense_width=0, num_experts=E, experts_per_token=4)
    p = dict(scaffold.init(uncut, keys[2], x, (tail,), done)["params"])
    p["moe"] = dict(
        p["moe"],
        e_score_correction_bias=0.05 * jax.random.normal(keys[3], (E,)),
    )
    stacked = ("w_gate", "w_up", "w_down")

    def cut(first, count, zero_down=False):
        moe = dict(p["moe"], **{
            k: p["moe"][k][first : first + count] for k in stacked
        })
        if zero_down:
            moe["w_down"] = jnp.zeros_like(moe["w_down"])
        return dict(p, moe=moe)

    def value_and_pullback(layer, params, first, weight=None):
        def program(x):
            out, pull = jax.vjp(
                lambda x: layer(x, tail, done, params, first), x
            )
            return out, pull(jnp.cos(out) if weight is None else weight)[0]

        traced = jax.jit(program)
        return traced(x)

    whole_layer = _layer_side(side, E, E)
    share_layer = _layer_side(side, count, E)
    whole, grad_whole = value_and_pullback(whole_layer, cut(0, E), 0)
    weight = jnp.cos(whole)
    once, grad_once = value_and_pullback(
        whole_layer, cut(0, E, zero_down=True), 0, weight
    )
    parts, grads = zip(*(
        value_and_pullback(share_layer, cut(first, count), first, weight)
        for first in range(0, E, count)
    ))
    routed = [part - once for part in parts]
    assert all(float(jnp.max(jnp.abs(r))) > 0 for r in routed)
    np.testing.assert_allclose(sum(routed) + once, whole, 1e-5, 1e-5)
    # No share is the whole, and the operator counted once a share is
    # not it either.
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3
    assert float(jnp.max(jnp.abs(sum(parts) - whole))) > 1e-3
    np.testing.assert_allclose(
        sum(grads) - (shares - 1) * grad_once, grad_whole,
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.parametrize(
    "precision, cut", [("high", True), ("default", False)],
    ids=["two-terms", "one-term"],
)
def test_the_fused_pass_says_whether_its_products_are_cut_in_the_kernel(
    monkeypatch, precision, cut
):
    """With the threshold lowered to reach a toy width (heads of 64)
    the attention layer takes the fused pass (interpreted here) and
    the stats count it; at the family's `high` they also count it
    under `attention_products_cut_in_kernel` (two terms an operand,
    cut in VMEM), and at one term that key is NOT SOWN: Mellum2's
    update has no such output (tests/test_mellum2.py). The loss is the
    dense body's either way."""
    model, params = scaffold.build(
        "lfm2", head_dim=64, matmul_precision=precision
    )
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(7, ENDS, t=T)

    def run():
        # A trace of its own each time: the rule is read at the trace.
        loss, stats, _ = scaffold.loss_and_grads.__wrapped__(model)(
            params, batch, state
        )
        return loss, stats

    loss, stats = run()
    assert "attention_fused_applications" not in stats
    assert "attention_products_cut_in_kernel" not in stats
    monkeypatch.setattr(attention, "FUSED_SCORE_BYTES", 1)
    loss_f, stats_f = run()
    assert float(stats_f["attention_fused_applications"]) == 1.0
    if cut:
        assert float(stats_f["attention_products_cut_in_kernel"]) == 1.0
    else:
        assert "attention_products_cut_in_kernel" not in stats_f
    assert float(loss_f) == pytest.approx(float(loss), rel=1e-4)
