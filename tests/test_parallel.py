"""Data-parallel learner over the 8-virtual-device CPU mesh: the sharded
update must produce numerically identical results to the single-device
update (grads all-reduce to the same global sum)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import create_model
from torchbeast_tpu.parallel import (
    create_mesh,
    make_parallel_update_step,
    replicate,
    shard_batch,
)

T, B, A = 4, 8, 4  # B divisible by the 8-device data axis


def make_batch(rng_seed=0, t=T, b=B):
    rng = np.random.default_rng(rng_seed)
    lead = (t + 1, b)
    return {
        "frame": rng.integers(0, 256, lead + (48, 48, 1), dtype=np.uint8),
        "reward": rng.standard_normal(lead).astype(np.float32),
        "done": rng.random(lead) < 0.2,
        "episode_return": rng.standard_normal(lead).astype(np.float32),
        "episode_step": rng.integers(0, 99, lead).astype(np.int32),
        "last_action": rng.integers(0, A, lead).astype(np.int32),
        "action": rng.integers(0, A, lead).astype(np.int32),
        "policy_logits": rng.standard_normal(lead + (A,)).astype(np.float32),
        "baseline": rng.standard_normal(lead).astype(np.float32),
    }


@pytest.fixture(scope="module")
def setup():
    model = create_model("shallow", num_actions=A, use_lstm=True)
    batch = make_batch()
    state = model.initial_state(B)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)
    return model, params, state, hp, optimizer


def test_mesh_shapes():
    mesh = create_mesh(8)
    assert mesh.devices.shape == (8, 1)
    assert mesh.axis_names == ("data", "model")
    mesh = create_mesh(8, model_parallelism=2)
    assert mesh.devices.shape == (4, 2)
    with pytest.raises(ValueError):
        create_mesh(8, model_parallelism=3)
    with pytest.raises(ValueError, match="visible"):
        create_mesh(64)  # more than the 8 virtual devices


@pytest.mark.slow
def test_parallel_update_matches_single_device(setup):
    model, params, state, hp, optimizer = setup
    batch = make_batch()

    # Single-device reference result.
    single = learner_lib.make_update_step(model, optimizer, hp)
    p1, _, stats1 = single(
        jax.tree_util.tree_map(jnp.copy, params),
        optimizer.init(params),
        batch,
        state,
    )

    # 8-way data-parallel result.
    mesh = create_mesh(8)
    par = make_parallel_update_step(model, optimizer, hp, mesh)
    params_r = replicate(mesh, jax.tree_util.tree_map(jnp.copy, params))
    opt_r = replicate(mesh, optimizer.init(params))
    batch_s, state_s = shard_batch(mesh, batch, state)
    p8, _, stats8 = par(params_r, opt_r, batch_s, state_s)

    np.testing.assert_allclose(
        float(stats1["total_loss"]), float(stats8["total_loss"]),
        rtol=2e-4,
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p8)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        )


def test_dp_plus_tp_update_matches_single_device(setup):
    """(data=4, model=2) mesh: dense kernels sharded over the model axis,
    batch over data — numerics must match the single-device update."""
    from torchbeast_tpu.models import create_model
    from torchbeast_tpu.parallel import (
        dense_kernel_shardings,
        place_params,
    )

    model = create_model("mlp", num_actions=A)
    batch = make_batch()
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch,
        (),
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)

    single = learner_lib.make_update_step(model, optimizer, hp, donate=False)
    p1, _, stats1 = single(params, optimizer.init(params), batch, ())

    mesh = create_mesh(8, model_parallelism=2)
    shardings = dense_kernel_shardings(mesh, params)
    # At least one kernel must actually shard for this test to mean much.
    assert any(
        not s.is_fully_replicated
        for s in jax.tree_util.tree_leaves(shardings)
    )
    par = make_parallel_update_step(
        model, optimizer, hp, mesh, param_shardings=shardings
    )
    params_s = place_params(
        mesh, jax.tree_util.tree_map(jnp.copy, params), shardings
    )
    opt_s = optimizer.init(params_s)
    batch_s, _ = shard_batch(mesh, batch, ())
    p2, _, stats2 = par(params_s, opt_s, batch_s, ())

    np.testing.assert_allclose(
        float(stats1["total_loss"]), float(stats2["total_loss"]), rtol=2e-4
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        )


def test_parallel_update_keeps_params_replicated(setup):
    model, params, state, hp, optimizer = setup
    mesh = create_mesh(8)
    par = make_parallel_update_step(model, optimizer, hp, mesh)
    # device_put may alias the source buffer as one replica shard, so hand
    # the donating call copies to keep the shared fixture alive.
    params_r = replicate(mesh, jax.tree_util.tree_map(jnp.copy, params))
    opt_r = replicate(mesh, optimizer.init(params))
    batch_s, state_s = shard_batch(mesh, make_batch(), state)
    p8, o8, _ = par(params_r, opt_r, batch_s, state_s)
    leaf = jax.tree_util.tree_leaves(p8)[0]
    assert leaf.sharding.is_fully_replicated

    # And the batch really was sharded over the data axis.
    frame = batch_s["frame"]
    assert not frame.sharding.is_fully_replicated
    assert len(frame.sharding.device_set) == 8


@pytest.mark.slow
def test_transformer_megatron_tp_matches_single_device():
    """Megatron column/row-paired TP for the transformer on a
    (data=4 x model=2) mesh: the update must match single-device, and
    the pairing must shard exactly the projection/FFN leaves (11 per
    block + their optimizer moments)."""
    from torchbeast_tpu.parallel import transformer_tp_shardings

    mesh = create_mesh(8, model_parallelism=2)
    kwargs = dict(
        num_actions=A, num_layers=1, d_model=16, num_heads=2,
        memory_len=4,
    )
    model = create_model("transformer", **kwargs)
    batch = make_batch(rng_seed=3)
    state = model.initial_state(B)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(6), "action": jax.random.PRNGKey(7)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)

    step_single = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    )
    p_ref, _, stats_ref = step_single(
        params, optimizer.init(params), batch, state
    )

    shardings = transformer_tp_shardings(mesh, params)
    flat, _ = jax.tree_util.tree_flatten_with_path(shardings)
    sharded = sorted(
        jax.tree_util.keystr(path)
        for path, s in flat
        if not s.is_fully_replicated
    )
    expected = sorted(
        f"['params']['block_0']{suffix}"
        for suffix in (
            "['q']['kernel']", "['q']['bias']",
            "['k']['kernel']", "['k']['bias']",
            "['v']['kernel']", "['v']['bias']",
            "['out']['kernel']", "['rel_bias']",
            "['Dense_0']['kernel']", "['Dense_0']['bias']",
            "['Dense_1']['kernel']",
        )
    )
    assert sharded == expected, sharded

    step_tp = make_parallel_update_step(
        model, optimizer, hp, mesh, donate=False,
        param_shardings=shardings,
    )
    params_p = jax.tree_util.tree_map(jax.device_put, params, shardings)
    opt_p = optimizer.init(params_p)
    batch_p, state_p = shard_batch(mesh, batch, state)
    p_tp, _, stats_tp = step_tp(params_p, opt_p, batch_p, state_p)

    np.testing.assert_allclose(
        float(stats_tp["total_loss"]), float(stats_ref["total_loss"]),
        rtol=1e-5,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        ),
        p_tp,
        p_ref,
    )
    # The new params must keep their TP placement (donation-stable).
    n_sharded_out = sum(
        not leaf.sharding.is_fully_replicated
        for leaf in jax.tree_util.tree_leaves(p_tp)
    )
    assert n_sharded_out == len(expected)


def test_transformer_tp_rejects_indivisible_heads():
    from torchbeast_tpu.parallel import transformer_tp_shardings

    mesh = create_mesh(8, model_parallelism=4)  # 4 does not divide H=2
    model = create_model(
        "transformer", num_actions=A, num_layers=1, d_model=16,
        num_heads=2, memory_len=4,
    )
    batch = make_batch(rng_seed=4)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(8), "action": jax.random.PRNGKey(9)},
        batch,
        model.initial_state(B),
    )
    with pytest.raises(ValueError, match="num_heads"):
        transformer_tp_shardings(mesh, params)


def test_parallel_update_applies_entropy_anneal(setup):
    """The mesh-sharded update must run the SAME loss-side schedule as
    the single-device one (they share learner.update_body): with an
    entropy anneal armed, the entropy_loss stat at the half-horizon
    count is half the count-0 value on identical params/batch."""
    import optax.tree_utils as otu

    model, params, state, hp, _ = setup
    hp = hp._replace(
        entropy_cost=1.0, entropy_cost_final=0.0,
        total_steps=10 * T * B,  # 10-update horizon
    )
    optimizer = learner_lib.make_optimizer(hp)
    mesh = create_mesh(8)
    step = make_parallel_update_step(
        model, optimizer, hp, mesh, donate=False
    )
    batch = make_batch()
    opt_state = optimizer.init(params)
    p = replicate(mesh, params)
    o = replicate(mesh, optimizer.init(params))
    b, s = shard_batch(mesh, batch, state)

    _, _, stats0 = step(p, o, b, s)
    o5 = replicate(
        mesh, otu.tree_set(opt_state, count=jnp.asarray(5, jnp.int32))
    )
    _, _, stats5 = step(p, o5, b, s)
    e0 = float(stats0["entropy_loss"])
    e5 = float(stats5["entropy_loss"])
    assert e0 != 0.0
    np.testing.assert_allclose(e5, 0.5 * e0, rtol=1e-5)


# Shapes of the partitioning test: no other dimension of these programs
# is 7, 12, 21 or 84 (frames 48x48x1; LSTM gates 1024 / 2068 / 532).
DIVIDE_T, DIVIDE_B, DIVIDE_CHIPS = 6, 12, 4


COLLECTIVES = (
    "all-gather", "all-reduce", "all-to-all", "collective-permute",
    "reduce-scatter",
)


def result_dims(hlo_text, *ops):
    """Result dims of every instruction of kind `ops` in a compiled
    module's text: [(op, (dims...)), ...]; a tuple result gives one
    entry per element. (tests/test_chip_compile.py reads the four-chip
    TPU program with it.)"""
    import re

    found = []
    pattern = r"= (\(.*?\)|\S+) (%s)(?:-start)?\(" % "|".join(ops)
    for match in re.finditer(pattern, hlo_text):
        for dims in re.findall(r"\w+\[([\d,]*)\]", match.group(1)):
            found.append(
                (match.group(2), tuple(int(d) for d in dims.split(",") if d))
            )
    return found


@pytest.mark.parametrize("family", ["deep", "shallow", "mlp"])
def test_data_parallel_update_divides_the_model(family):
    """Four chips each run a quarter of the rows, trunk included.

    The conv trunks merge [T+1, B] into one axis; merged time-major the
    partitioner cannot carry B's sharding through (a tiled sharding of a
    merged axis follows its major factor only), all-gathers the frames
    and every chip runs all (T+1) * B rows (0.95x of one chip on four,
    PERF.md §6, PR 28). Read from the compiled program: no all-gather of
    a [T+1, B, ...] array, and every convolution / matmul over frames
    has (T+1) * B / 4 rows.
    """
    steps, rows = DIVIDE_T + 1, DIVIDE_B
    lead = (steps, rows)
    model = create_model(family, num_actions=A, use_lstm=True)
    batch = make_batch(t=DIVIDE_T, b=rows)
    state = model.initial_state(rows)
    params = jax.eval_shape(
        model.init,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=rows, unroll_length=DIVIDE_T)
    optimizer = learner_lib.make_optimizer(hp)
    step = make_parallel_update_step(
        model, optimizer, hp, create_mesh(DIVIDE_CHIPS), donate=False
    )
    text = step.lower(
        params, jax.eval_shape(optimizer.init, params), batch, state
    ).compile().as_text()

    gathered = [
        dims for _, dims in result_dims(text, "all-gather")
        if dims[:2] == lead
    ]
    assert not gathered, f"all-gathers of [T+1, B, ...] arrays: {gathered}"
    # What is left between chips: the gradients' all-reduce (with the
    # stats' sums) and a scalar count.
    kinds = {op for op, _ in result_dims(text, *COLLECTIVES)}
    assert kinds == {"all-reduce"}, kinds

    whole, quarter = steps * rows, steps * rows // DIVIDE_CHIPS
    matmuls = result_dims(text, "convolution", "dot")
    over_all_rows = [m for m in matmuls if whole in m[1]]
    assert not over_all_rows, over_all_rows
    per_chip = [m for m in matmuls if m[1][:1] == (quarter,)]
    if family != "mlp":
        convs = [dims for op, dims in per_chip if op == "convolution"]
        # Forward and input-gradient convolutions of every conv layer
        # but the first, whose input is the frame.
        assert len(convs) >= 5, matmuls
    assert per_chip, matmuls
