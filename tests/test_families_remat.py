"""`--remat all` changes no value of any policy family: a case a
family of ONE parametrised test (tests/family_scaffold.py has the rule
for the next family). Apart from tests/test_families.py, whose longest
cases these were: under `--dist loadfile` a file is one worker's chain
(ISSUE 49)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib

_ENDS = [(4, 0), (7, 0), (8, 0), (10, 0), (0, 1), (5, 1)]
# family: the model's overrides, the batch's episode ends, how close the
# loss and the gradients stay (rtol, atol; an atol of None: 1e-5 (2e-5:
# nemotron3) of the largest gradient, the family's tolerance against
# its reference), the stats that are equal and those equal to 1e-6.
# ouro runs EAGERLY: its perturbed norm scales put keys and logits at
# 4-5, and two XLA programs (with and without remat) round 2e-5 apart
# where the same ops one by one agree to its 1e-6.
REMAT = {
    "mellum2": (
        dict(expert_share=(1, 4)), [(1, 1)], 1e-6, (1e-5, 1e-6),
        ["moe_held_assignments"], [],
    ),
    "ouro": ({}, [(1, 1)], 1e-6, (1e-5, 1e-6), [], ["loop_exit_p_last"]),
    "kanana2": (
        dict(expert_share=(1, 8)), [(1, 1)], 1e-5, (0, 1e-5),
        ["moe_held_assignments", "attention_latent_applications"], [],
    ),
    "nemotron3": (
        dict(expert_share=(1, 8), mixer_share=(1, 2)), _ENDS, 1e-5,
        (0, 2e-5),
        ["moe_held_assignments", "ssm_applications", "ssm_chunks",
         "ssm_resets_per_row", "moe_latent_applications"], [],
    ),
    "qwen3next": (
        dict(expert_share=(1, 4)), _ENDS, 1e-5, (0, 2e-5),
        ["moe_held_assignments", "delta_applications", "delta_chunks",
         "delta_resets_per_row", "attention_gated_applications"], [],
    ),
    "lfm2": (
        dict(expert_share=(1, 4)), [(2, 0), (3, 0), (0, 1), (4, 1)], 1e-5,
        (0, 2e-5),
        ["moe_held_assignments", "conv_layers", "conv_resets_per_row",
         "conv_state_bytes_per_row"], [],
    ),
    # The values handed on are inputs and outputs of the rematerialised
    # blocks; ends inside the unroll and on step 0.
    "phi4flash": (
        {}, [(2, 0), (4, 0), (0, 1), (3, 1)], 1e-5, (0, 2e-5),
        ["ssm_applications", "ssm_chunks", "ssm_resets_per_row",
         "shared_memory_readers", "shared_kv_readers",
         "shared_bytes_per_row", "attention_differential_applications"], [],
    ),
    # The streams [4, B, T, d] are a rematerialised block's input and
    # output; the maps' counters are computed inside it.
    "xing4": (
        dict(expert_share=(1, 8)), [(1, 1)], 1e-5, (0, 1e-5),
        ["moe_held_assignments", "attention_latent_applications",
         "hc_bytes_per_row"],
        ["hc_post_mean", "hc_res_row_error_max"],
    ),
    # The gate, the four norms and both kinds of cache inside the
    # rematerialised blocks; an end inside the unroll.
    "trinity": (
        dict(expert_share=(0, 8)), [(1, 1)], 1e-5, (0, 1e-5),
        ["moe_held_assignments", "attention_gated_applications",
         "attention_unrotated_applications", "moe_shared_applications"], [],
    ),
    # Mixer and SwiGLU, each behind its multiplier, inside ONE
    # rematerialised block a layer; ends inside a chunk and on step 0.
    "granite4": (
        {}, _ENDS, 1e-5, (0, 2e-5),
        ["ssm_applications", "ssm_chunks", "ssm_resets_per_row",
         "mlp_applications", "attention_unrotated_applications"], [],
    ),
    # A KDA mixer (its solve's result kept across the rematerialisation),
    # the latent mixer and a feed-forward part are each a rematerialised
    # block of their own; ends inside a chunk, a sub-block and on step 0.
    # 5e-5 of the largest gradient: a sub-block's columns are measured
    # from its first step (e^5 on a row's e^-5 at the toy's floor), and
    # two XLA programs round that product apart (one entry of 29,705 by
    # 2.4e-5).
    "ling3": (
        dict(expert_share=(1, 8)), _ENDS, 1e-5, (0, 5e-5),
        ["moe_held_assignments", "kda_applications", "kda_chunks",
         "kda_resets_per_row", "attention_latent_applications",
         "router_group_load_max_share"],
        ["kda_log_decay_mean", "kda_gate_at_floor_share"],
    ),
}


@pytest.mark.parametrize("family", list(REMAT))
def test_rematerialised_blocks_give_the_same_loss_gradients_and_steps(family):
    """`--remat all`: nn.remat around the family's blocks (one applied
    `passes` times: ouro) changes no value, no statistic and no sown
    step of a selection bias."""
    overrides, ends, loss_rel, (rtol, atol), equal, close = REMAT[family]
    model, params = scaffold.build(family, **overrides)
    state = scaffold.warm_state(model, params, seed=5)
    batch = scaffold.learner_batch(9, ends, t=scaffold.FAMILIES[family].t)
    jit = family != "ouro"
    loss, stats, grads = scaffold.loss_and_grads(model, jit)(
        params, batch, state
    )
    loss_r, stats_r, grads_r = scaffold.loss_and_grads(
        model.clone(remat=True), jit
    )(params, batch, state)
    assert float(loss) == pytest.approx(float(loss_r), rel=loss_rel)
    flat, flat_r = scaffold.flat(grads), scaffold.flat(grads_r)
    if rtol == 0:
        atol = atol * float(jnp.max(jnp.abs(flat)))
    np.testing.assert_allclose(flat, flat_r, rtol=rtol, atol=atol)
    for name in equal:
        assert float(stats[name]) == float(stats_r[name])
    for name in close:
        assert float(stats[name]) == pytest.approx(
            float(stats_r[name]), rel=1e-6
        )
    jax.tree_util.tree_map(
        np.testing.assert_array_equal,
        stats.get(learner_lib.PARAM_STEPS_KEY, {}),
        stats_r.get(learner_lib.PARAM_STEPS_KEY, {}),
    )
