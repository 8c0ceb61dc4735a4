"""What a family's seeded tiny model gave at the parent of a PR that
touched the scaffolding under it, pinned: the one script every family's
`test_seeded_logits_are_what_they_were_before_pr_*` runs, the numbers
(read from the same script on the parent commit's tree) the caller's."""

import jax
import jax.numpy as jnp
import numpy as np


def _inputs(seed):
    draw = np.random.default_rng(seed)
    done = np.zeros((6, 2), bool)
    done[3, 1] = True
    return {
        "frame": jnp.asarray(
            draw.integers(0, 256, (6, 2, 8, 8, 1), dtype=np.uint8)
        ),
        "reward": jnp.asarray(draw.standard_normal((6, 2)), jnp.float32),
        "done": jnp.asarray(done),
        "last_action": jnp.asarray(draw.integers(0, 4, (6, 2))),
    }


def assert_seeded_outputs(
    model, *, params, logits, baseline, leaf_shapes, state_sum
):
    """`model` (4 actions) initialised from fixed keys, warmed by one
    unroll and run on a second: its parameter count, the last step's
    logits of row 0 and baseline of row 1, the first four state leaves'
    shapes and the sum of the new state's magnitudes."""
    tree = model.init(
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        _inputs(0), model.initial_state(2),
    )
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) == params
    _, state = model.apply(
        tree, _inputs(1), model.initial_state(2), sample_action=False
    )
    out, new_state = model.apply(tree, _inputs(2), state, sample_action=False)
    np.testing.assert_allclose(
        out.policy_logits[-1, 0], logits, rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        out.baseline[-1, 1], baseline, rtol=1e-5, atol=1e-6
    )
    leaves = jax.tree_util.tree_leaves(new_state)
    assert [list(x.shape) for x in leaves[:4]] == leaf_shapes
    np.testing.assert_allclose(
        sum(float(jnp.sum(jnp.abs(x))) for x in leaves), state_sum, rtol=1e-5
    )
