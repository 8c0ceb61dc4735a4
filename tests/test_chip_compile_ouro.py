"""Compile for the chip, without the chip (tests/chip_fixtures.py): the
Ouro loop's weight-gradient memory, two compiles of one looped layer at
the published widths. A file of its own: tests/chip_fixtures.py says
why.
"""

import numpy as np

import jax
import jax.numpy as jnp

from tests.chip_fixtures import (  # noqa: F401 (fixtures)
    B,
    NUM_ACTIONS,
    T,
    on as _on,
    one_chip,
    topo,
)


def _ouro_loop_gradient_memory(chip):
    """Temp bytes of the gradient of ONE Ouro layer at the published
    widths run 4 times (the family's loop), rematerialised, over its 4
    caches of 255 slots, on the cell's [81, 32] tokens; the observation
    projection shrunk to an 8x8x1 frame."""
    from torchbeast_tpu.models import create_model

    model = create_model("ouro", num_actions=NUM_ACTIONS, num_layers=1,
                         remat=True)
    inputs = {
        "frame": np.zeros((T + 1, B, 8, 8, 1), np.uint8),
        "reward": np.zeros((T + 1, B), np.float32),
        "done": np.zeros((T + 1, B), bool),
        "last_action": np.zeros((T + 1, B), np.int32),
    }
    state = jax.eval_shape(lambda: model.initial_state(B))
    params = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
            jax.tree_util.tree_map(lambda x: x[:1], inputs), model.initial_state(B),
        )
    )

    def loss(params, inputs, state):
        out, _ = model.apply(params, inputs, state, sample_action=False)
        return jnp.sum(out.policy_logits) + jnp.sum(out.baseline)

    return jax.jit(jax.grad(loss)).lower(
        _on(chip, params), _on(chip, inputs), _on(chip, state)
    ).compile().memory_analysis().temp_size_in_bytes


def test_ouro_loop_sums_weight_gradients_pass_by_pass_on_v5e(
    one_chip, monkeypatch
):
    """A looped block's weight gradient is the sum over its passes.
    `OuroNet.make_block` ties the weights to the hidden state between
    two applications (an optimization barrier, whose transpose is one),
    so that the chip's compiler adds a pass's part to the running sum
    before it enters the pass before; without the tie the adds fuse
    into the gradients' consumer and every pass's part lives to the
    end: (passes - 1) x 196 MiB more here, 4.6 GiB at the cell's 8
    layers, which then does not fit beside the driver's copy."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tied = _ouro_loop_gradient_memory(one_chip)
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    untied = _ouro_loop_gradient_memory(one_chip)
    layer = 4 * (4 * 2048 * 2048 + 3 * 2048 * 5632)  # one layer, f32
    assert untied - tied > layer, (tied, untied, layer)
