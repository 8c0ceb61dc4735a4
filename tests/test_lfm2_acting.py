"""The `lfm2` family's acting: the learner's batch forward against the
actor's T=1 forwards through the carried two-step tails and the rolling
cache, and through a `DeviceStateTable` whose rows hold both kinds of
state."""

import numpy as np
import pytest

import jax

from tests import family_scaffold as scaffold
from tests.test_lfm2 import ENDS, D, M, T


@pytest.mark.parametrize("unrolls", [0, 2], ids=["empty", "warm"])
def test_batch_forward_equals_stepwise_acting_through_the_carried_states(
    unrolls
):
    """The learner's [T, B] forward (the convolution as three shifted
    adds over the unroll, attention over [cache; unroll] with RoPE over
    the whole head) and the actor's T=1 forwards through the two-step
    tails and the rolling cache of un-rotated keys (5 slots: the 6 steps
    evict on the way) give the same logits and leave the same states,
    from empty and from filled tails and caches, across episode ends
    one step apart."""
    model, params = scaffold.build("lfm2")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    assert bool(unrolls) == any(
        np.any(leaf) for leaf in jax.tree_util.tree_leaves(state)
    )
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, ENDS, t=T)
    )


@pytest.mark.parametrize("via", ["reset", "rebuild"])
def test_stepwise_acting_through_the_state_table_equals_the_batch_forward(
    via
):
    """Three actors' slots in a `DeviceStateTable` whose rows hold BOTH
    kinds of state: a conv layer's tail alone ([2, 1, 32], an entry of
    ONE leaf) and the attention layer's window (k, v [M, 1, 2, 8], valid
    [M, 1]). The rows arrive in another order every step and episodes
    end on the way; every step's logits equal the batch forward's and
    the table ends with what that forward leaves; reset and rebuild
    bring back zeros of every shape."""
    model, params = scaffold.build("lfm2")
    shapes = [
        [(2, 1, D)], [(M, 1, 2, 8), (M, 1, 2, 8), (M, 1)], [(2, 1, D)],
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
