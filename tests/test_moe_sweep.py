"""The sweep of a window's rungs (models/moe.py `_sweep`, `_swept_
experts`): the first rung is taken outside the loop and its results are
the loop's starting sums (PR 58), so a step on one rung is that rung to
the bit, a step with no row gives zeros, and the traced backward holds
no zeros for the sums to start from and adds nothing outside a loop's
body. The sweep against the experts written out, at every number of
rungs: tests/test_moe_window.py."""

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_moe_window import (
    FEWER_HELD,
    MORE_HELD,
    QUARTER_E,
    QUARTER_FIRST,
    QUARTER_HELD,
    QUARTER_K,
    QUARTER_RUNG,
    QUARTER_TOKENS,
    RUNG_E,
    RUNG_FIRST,
    RUNG_TOKENS,
    _expert_operands,
    _routed_with,
    _sorted_indices,
)

WEIGHTS = ("x", "gate", "w_gate", "w_up", "w_down")
# tokens, K, held, experts, first, the rung: fewer held than chosen, as
# many or more, and the quarter share.
FIRST_RUNG_SHAPES = {
    "fewer-held": (RUNG_TOKENS, *FEWER_HELD, RUNG_E, RUNG_FIRST, 256),
    "more-held": (RUNG_TOKENS, *MORE_HELD, RUNG_E, RUNG_FIRST, 256),
    "quarter-share": (
        QUARTER_TOKENS, QUARTER_K, QUARTER_HELD, QUARTER_E, QUARTER_FIRST,
        QUARTER_RUNG,
    ),
}


def _sweep_operands(shape, live, gated):
    """(how, weights, indices, tangent) as `_swept_experts` and
    `_window_experts` take them, `live` assignments on the held
    experts."""
    from torchbeast_tpu.models import moe

    tokens, top_k, held, experts, first, rung = FIRST_RUNG_SHAPES[shape]
    rungs = moe.window_rungs(tokens, top_k, held, experts)
    assert rungs == (rung, tokens * min(top_k, held))
    tangent, x, gate, w_gate, w_up, w_down = _expert_operands(
        live + 58, tokens, top_k, held
    )
    idx = _routed_with(
        live, live, top_k, held, tokens=tokens, experts=experts, first=first
    )
    indices = (idx, *_sorted_indices(idx, experts))
    how = moe._Experts(first, held, "silu", 1, rungs)
    mine = indices[-1][first : first + held]
    assert int(moe.window_sweeps(rungs, mine)) == -(-live // rung)
    weights = (x, gate, w_gate if gated else None, w_up, w_down)
    return how, weights, indices, tangent


def _value_and_gradients(experts, weights, tangent):
    """`experts(weights)` and its gradients against `tangent` of every
    weight that is there (None for `w_gate` where the experts are not
    gated), each as one program."""
    value = jax.jit(experts)
    gradients = jax.jit(jax.grad(
        lambda weights: jnp.sum(experts(weights) * tangent)
    ))
    return value(weights), gradients(weights)


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "two-matrix"])
@pytest.mark.parametrize(
    "live_of_rung", [0.4, 1.0], ids=["under-a-rung", "exactly-a-rung"]
)
@pytest.mark.parametrize("shape", sorted(FIRST_RUNG_SHAPES))
def test_a_one_rung_sweep_is_its_first_rung_to_the_bit(
    shape, live_of_rung, gated
):
    """PR 58: the first rung is taken outside the loop and its results
    are the loop's starting sums, so on a step of one rung the sweep's
    value and its gradients of x, the gates and every weight ARE
    `_window_experts`' at row 0, forward and backward: equal to the
    bit, nothing added to them (the loop from zeros computed `0 + g`,
    which differs by the sign of a zero and cost three passes over a
    weight's gradient)."""
    from torchbeast_tpu.models import moe

    live = int(FIRST_RUNG_SHAPES[shape][-1] * live_of_rung)
    how, weights, indices, tangent = _sweep_operands(shape, live, gated)
    swept, swept_grads = _value_and_gradients(
        lambda w: moe._swept_experts(how, w, indices), weights, tangent
    )
    rung, rung_grads = _value_and_gradients(
        lambda w: moe._window_experts(how, 0, *w, *indices), weights, tangent
    )
    np.testing.assert_array_equal(swept, rung)
    assert np.any(rung)
    for name, a, b in zip(WEIGHTS, swept_grads, rung_grads):
        if b is None:
            assert a is None and name == "w_gate" and not gated
            continue
        np.testing.assert_array_equal(a, b, err_msg=name)
        assert np.any(b), name


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "two-matrix"])
@pytest.mark.parametrize("shape", sorted(FIRST_RUNG_SHAPES))
def test_a_sweep_of_no_rung_gives_zeros(shape, gated):
    """`window_sweeps` == 0 (no token chose a held expert): the first
    rung is taken all the same, visits no row, and the grouped kernels
    give zeros for groups without rows; the value and every gradient
    are zeros, as the loop that never turned left them."""
    from torchbeast_tpu.models import moe

    how, weights, indices, tangent = _sweep_operands(shape, 0, gated)
    value, grads = _value_and_gradients(
        lambda w: moe._swept_experts(how, w, indices), weights, tangent
    )
    assert value.shape == tangent.shape and not np.any(value)
    for name, w, g in zip(WEIGHTS, weights, grads):
        if w is None:
            assert g is None
            continue
        assert g.shape == w.shape and not np.any(g), name


def _eqns(jaxpr, in_loop=False):
    """(eqn, the jaxpr that holds it, whether a loop's body does) of a
    jaxpr, calls inside calls too."""
    for eqn in jaxpr.eqns:
        yield eqn, jaxpr, in_loop
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(inner, in_loop or eqn.primitive.name == "while")


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "two-matrix"])
@pytest.mark.parametrize("shape", sorted(FIRST_RUNG_SHAPES))
def test_the_sweeps_sums_start_from_the_first_rung_in_the_jaxpr(shape, gated):
    """The traced backward of a swept shape: each loop's starting sums
    are values the first rung made, none a `zeros` of a weight's (or
    x's, or the gates') shape broadcast for the purpose, and the only
    `add`s of a weight gradient's shape are inside a loop's body, a
    second rung's: a step on one rung writes each gradient once."""
    from torchbeast_tpu.models import moe

    how, weights, indices, tangent = _sweep_operands(shape, 1, gated)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda w: jnp.sum(moe._swept_experts(how, w, indices) * tangent)
    ))(weights)
    summed = {
        w.shape for w in weights if w is not None and w.shape != tangent.shape
    }
    assert len(summed) == 3  # the gates, w_gate / w_up, w_down
    summed.add(tangent.shape)  # y's and x's

    def is_sum(v):
        return v.aval.dtype == jnp.float32 and v.aval.shape in summed

    carried_sums, adds_in_loops = [], 0
    for eqn, holder, in_loop in _eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name == "while" and not in_loop:
            made_by = {
                out: e.primitive.name for e in holder.eqns for out in e.outvars
            }
            carried = eqn.invars[
                eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]:
            ]
            sums = [v for v in carried if is_sum(v)]
            for v in sums:
                assert not isinstance(v, jax.extend.core.Literal)
                assert made_by[v] != "broadcast_in_dim", v.aval
            if sums:  # not a `searchsorted`'s loop
                carried_sums.append(len(sums))
        if name in ("add", "add_any") and is_sum(eqn.outvars[0]):
            assert in_loop, eqn
            adds_in_loops += 1
    # The forward's sweep carries y; the backward's, a sum a weight.
    assert carried_sums == [1, 4 + gated]
    assert adds_in_loops >= 5 + gated
