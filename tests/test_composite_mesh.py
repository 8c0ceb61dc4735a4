"""Composite (data x expert) mesh: a data-parallel learner with
expert-sharded MoE layers in ONE update step must match the
single-device update numerically — XLA lays the gradient all-reduce on
`data` and the MoE dispatch/combine all-to-alls on `expert`."""

import jax
import numpy as np
import pytest

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import create_model
from torchbeast_tpu.parallel import (
    create_mesh,
    expert_param_shardings,
    make_parallel_update_step,
    shard_batch,
)

pytestmark = pytest.mark.slow

T, B, A = 4, 8, 5


def _batch(seed=0, t=T):
    rng = np.random.default_rng(seed)
    return {
        "frame": rng.integers(0, 256, (t + 1, B, 6, 6, 1), dtype=np.uint8),
        "reward": rng.standard_normal((t + 1, B)).astype(np.float32),
        "done": rng.random((t + 1, B)) < 0.15,
        "episode_return": rng.standard_normal((t + 1, B)).astype(
            np.float32
        ),
        "episode_step": rng.integers(0, 9, (t + 1, B)).astype(np.int32),
        "last_action": rng.integers(0, A, (t + 1, B)).astype(np.int32),
        "action": rng.integers(0, A, (t + 1, B)).astype(np.int32),
        "policy_logits": rng.standard_normal((t + 1, B, A)).astype(
            np.float32
        ),
        "baseline": rng.standard_normal((t + 1, B)).astype(np.float32),
    }


def test_create_mesh_axes():
    mesh = create_mesh(8, expert_parallelism=2)
    assert mesh.shape == {"data": 4, "model": 1, "expert": 2}
    plain = create_mesh(8)
    assert plain.shape == {"data": 8, "model": 1}


def test_dp_x_ep_update_matches_single_device():
    mesh = create_mesh(8, expert_parallelism=2)
    kwargs = dict(
        num_actions=A, num_layers=1, d_model=16, num_heads=2,
        memory_len=4, num_experts=4,
    )
    single = create_model("transformer", **kwargs)
    composite = create_model("transformer", moe_mesh=mesh, **kwargs)

    batch = _batch()
    state = single.initial_state(B)
    params = scaffold.init(
        single,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)

    step_single = learner_lib.make_update_step(
        single, optimizer, hp, donate=False
    )
    p_ref, _, stats_ref = step_single(
        params, optimizer.init(params), batch, state
    )

    shardings = expert_param_shardings(mesh, params)
    # 4 experts over a 2-wide axis: the expert kernels must shard.
    n_sharded = sum(
        not s.is_fully_replicated
        for s in jax.tree_util.tree_leaves(shardings)
    )
    assert n_sharded == 2  # w_in + w_out of the single block

    step_comp = make_parallel_update_step(
        composite, optimizer, hp, mesh, donate=False,
        param_shardings=shardings,
    )
    params_p = jax.tree_util.tree_map(jax.device_put, params, shardings)
    batch_p, state_p = shard_batch(mesh, batch, state)
    p_comp, _, stats_comp = step_comp(
        params_p, optimizer.init(params_p), batch_p, state_p
    )

    np.testing.assert_allclose(
        float(stats_comp["total_loss"]),
        float(stats_ref["total_loss"]),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        float(stats_comp["aux_loss"]),
        float(stats_ref["aux_loss"]),
        rtol=1e-5,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        ),
        p_comp,
        p_ref,
    )


def test_dp_x_sp_update_matches_single_device():
    """Composite (data x seq) mesh: data-parallel learner with the
    transformer's in-unroll attention sequence-sharded — both the
    zig-zag ring and the Ulysses strategy — must match the single-device
    update numerically."""
    mesh = create_mesh(8, seq_parallelism=2)
    assert mesh.shape == {"data": 4, "model": 1, "seq": 2}
    T_ = 7  # model sees T+1 = 8 steps: zigzag chunks of 2, ulysses 4
    kwargs = dict(
        num_actions=A, num_layers=1, d_model=16, num_heads=2,
        memory_len=4,
    )
    single = create_model("transformer", **kwargs)

    batch = _batch(seed=1, t=T_)
    state = single.initial_state(B)
    params = scaffold.init(
        single,
        {"params": jax.random.PRNGKey(2), "action": jax.random.PRNGKey(3)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T_)
    optimizer = learner_lib.make_optimizer(hp)
    step_single = learner_lib.make_update_step(
        single, optimizer, hp, donate=False
    )
    p_ref, _, stats_ref = step_single(
        params, optimizer.init(params), batch, state
    )

    for strategy, extra in (
        ("ring", {"ring_schedule": "zigzag"}),
        ("ulysses", {}),
    ):
        comp = create_model(
            "transformer", mesh=mesh, sp_strategy=strategy,
            batch_axis="data", **extra, **kwargs
        )
        step_comp = make_parallel_update_step(
            comp, optimizer, hp, mesh, donate=False
        )
        batch_p, state_p = shard_batch(mesh, batch, state)
        params_p = jax.device_put(
            params, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()
            )
        )
        p_comp, _, stats_comp = step_comp(
            params_p, optimizer.init(params_p), batch_p, state_p
        )
        np.testing.assert_allclose(
            float(stats_comp["total_loss"]),
            float(stats_ref["total_loss"]),
            rtol=1e-5,
            err_msg=strategy,
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5,
                err_msg=strategy,
            ),
            p_comp,
            p_ref,
        )


def test_dp_x_sp_x_ep_update_matches_single_device():
    """THREE-axis composite (data x seq x expert) mesh: data-parallel
    learner, sequence-sharded attention (zigzag ring AND ulysses), and
    expert-sharded MoE in ONE update step must match the single-device
    update numerically. Attention partitions over (data, seq) leaving
    `expert` unmentioned; the MoE constraints use `expert` — the two
    collective families coexist in one jitted program."""
    mesh = create_mesh(8, expert_parallelism=2, seq_parallelism=2)
    assert mesh.shape == {"data": 2, "model": 1, "seq": 2, "expert": 2}
    T_ = 7  # T+1 = 8: zigzag chunks of 2, ulysses T blocks of 4
    kwargs = dict(
        num_actions=A, num_layers=1, d_model=16, num_heads=2,
        memory_len=4, num_experts=4,
    )
    single = create_model("transformer", **kwargs)

    batch = _batch(seed=2, t=T_)
    state = single.initial_state(B)
    params = scaffold.init(
        single,
        {"params": jax.random.PRNGKey(4), "action": jax.random.PRNGKey(5)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T_)
    optimizer = learner_lib.make_optimizer(hp)
    step_single = learner_lib.make_update_step(
        single, optimizer, hp, donate=False
    )
    p_ref, _, stats_ref = step_single(
        params, optimizer.init(params), batch, state
    )

    shardings = expert_param_shardings(mesh, params)
    n_sharded = sum(
        not s.is_fully_replicated
        for s in jax.tree_util.tree_leaves(shardings)
    )
    assert n_sharded == 2  # w_in + w_out of the single block

    for strategy, extra in (
        ("ring", {"ring_schedule": "zigzag"}),
        ("ulysses", {}),
    ):
        comp = create_model(
            "transformer", mesh=mesh, sp_strategy=strategy,
            batch_axis="data", moe_mesh=mesh, **extra, **kwargs
        )
        step_comp = make_parallel_update_step(
            comp, optimizer, hp, mesh, donate=False,
            param_shardings=shardings,
        )
        params_p = jax.tree_util.tree_map(
            jax.device_put, params, shardings
        )
        batch_p, state_p = shard_batch(mesh, batch, state)
        p_comp, _, stats_comp = step_comp(
            params_p, optimizer.init(params_p), batch_p, state_p
        )
        np.testing.assert_allclose(
            float(stats_comp["total_loss"]),
            float(stats_ref["total_loss"]),
            rtol=1e-5,
            err_msg=strategy,
        )
        np.testing.assert_allclose(
            float(stats_comp["aux_loss"]),
            float(stats_ref["aux_loss"]),
            rtol=1e-5,
            err_msg=strategy,
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5,
                err_msg=strategy,
            ),
            p_comp,
            p_ref,
        )


def test_dp_x_tp_x_ep_update_matches_single_device():
    """(data x model x expert) mesh: Megatron-paired attention TP and
    expert-sharded MoE merged onto one param tree, data-parallel batch —
    the merged-rule update must match single-device numerically."""
    from torchbeast_tpu.parallel import (
        merge_param_shardings,
        transformer_tp_shardings,
    )

    mesh = create_mesh(8, model_parallelism=2, expert_parallelism=2)
    assert mesh.shape == {"data": 2, "model": 2, "expert": 2}
    kwargs = dict(
        num_actions=A, num_layers=1, d_model=16, num_heads=2,
        memory_len=4, num_experts=4,
    )
    single = create_model("transformer", **kwargs)
    batch = _batch(seed=3)
    state = single.initial_state(B)
    params = scaffold.init(
        single,
        {"params": jax.random.PRNGKey(6), "action": jax.random.PRNGKey(7)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)
    step_single = learner_lib.make_update_step(
        single, optimizer, hp, donate=False
    )
    p_ref, _, stats_ref = step_single(
        params, optimizer.init(params), batch, state
    )

    shardings = merge_param_shardings(
        expert_param_shardings(mesh, params),
        transformer_tp_shardings(mesh, params),
    )
    n_sharded = sum(
        not s.is_fully_replicated
        for s in jax.tree_util.tree_leaves(shardings)
    )
    # 2 expert kernels + 8 attention leaves (q/k/v kernel+bias, out
    # kernel, rel_bias); the MoE block has no dense FFN for TP to claim.
    assert n_sharded == 10, n_sharded

    comp = create_model("transformer", moe_mesh=mesh, **kwargs)
    step_comp = make_parallel_update_step(
        comp, optimizer, hp, mesh, donate=False,
        param_shardings=shardings,
    )
    params_p = jax.tree_util.tree_map(jax.device_put, params, shardings)
    batch_p, state_p = shard_batch(mesh, batch, state)
    p_comp, _, stats_comp = step_comp(
        params_p, optimizer.init(params_p), batch_p, state_p
    )
    np.testing.assert_allclose(
        float(stats_comp["total_loss"]), float(stats_ref["total_loss"]),
        rtol=1e-5,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        ),
        p_comp,
        p_ref,
    )


def test_merge_param_shardings_conflict_raises():
    from torchbeast_tpu.parallel import merge_param_shardings
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = create_mesh(8, expert_parallelism=2)
    a = {"w": NamedSharding(mesh, P("expert"))}
    b = {"w": NamedSharding(mesh, P("data"))}
    with pytest.raises(ValueError, match="conflicting"):
        merge_param_shardings(a, b)


def test_dp_x_pp_update_matches_single_device():
    """(data=2 x pipe=4) mesh: each data group runs its own GPipe while
    gradients all-reduce over `data` — the full update must match the
    single-device sequential tower for BOTH pipelined families."""
    mesh = create_mesh(8, pipe_parallelism=4)
    assert mesh.shape == {"data": 2, "model": 1, "pipe": 4}
    for family, kwargs, state_fn in (
        (
            "pipelined_mlp",
            dict(num_actions=A, num_stages=4, d_model=32),
            lambda m: (),
        ),
        (
            "pipelined_transformer",
            dict(
                num_actions=A, num_layers=4, d_model=32, num_heads=2,
                memory_len=8,
            ),
            lambda m: m.initial_state(B),
        ),
    ):
        single = create_model(family, **kwargs)
        comp = create_model(
            family, mesh=mesh, batch_axis="data", **kwargs
        )
        batch = _batch(seed=7)
        state = state_fn(single)
        params = scaffold.init(
            single,
            {
                "params": jax.random.PRNGKey(8),
                "action": jax.random.PRNGKey(9),
            },
            batch,
            state,
        )
        hp = learner_lib.HParams(batch_size=B, unroll_length=T)
        optimizer = learner_lib.make_optimizer(hp)
        step_single = learner_lib.make_update_step(
            single, optimizer, hp, donate=False
        )
        p_ref, _, stats_ref = step_single(
            params, optimizer.init(params), batch, state
        )
        step_comp = make_parallel_update_step(
            comp, optimizer, hp, mesh, donate=False
        )
        batch_p, state_p = shard_batch(mesh, batch, state)
        params_p = jax.device_put(
            params,
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        )
        p_comp, _, stats_comp = step_comp(
            params_p, optimizer.init(params_p), batch_p, state_p
        )
        np.testing.assert_allclose(
            float(stats_comp["total_loss"]),
            float(stats_ref["total_loss"]),
            rtol=1e-5,
            err_msg=family,
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5,
                err_msg=family,
            ),
            p_comp,
            p_ref,
        )
