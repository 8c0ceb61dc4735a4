"""Pipeline parallelism: the GPipe schedule in parallel/pp.py must be a
drop-in replacement for running the stages sequentially — identical
outputs, identical carried state, identical gradients (the bubbles'
masked computations must contribute zero grad)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from torchbeast_tpu.parallel.pp import (
    pipeline_apply,
    stack_stages,
    stage_param_shardings,
)

D = 16


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("pipe",))


def _make_stage_params(key, n_stages):
    keys = jax.random.split(key, n_stages)
    return stack_stages(
        [
            {
                "w": jax.random.normal(k, (D, D)) / np.sqrt(D),
                "b": jnp.zeros((D,)),
            }
            for k in keys
        ]
    )


def _stage_fn(params, x, carry, shared):
    """Residual MLP stage; consumes per-stage carry and a shared input so
    all three data paths are exercised."""
    h = jnp.tanh(x @ params["w"] + params["b"])
    if shared is not None:
        h = h * shared["scale"]
    if carry is None:
        return x + h, None
    new_carry = {"acc": carry["acc"] + h.sum(axis=-1)}
    return x + h + carry["acc"][:, None] * 0.01, new_carry


def _sequential(stage_params, x, carry=None, shared=None):
    n_stages = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    new_carries = []
    for s in range(n_stages):
        p = jax.tree_util.tree_map(lambda leaf: leaf[s], stage_params)
        c = (
            None
            if carry is None
            else jax.tree_util.tree_map(lambda leaf: leaf[s], carry)
        )
        x, nc = _stage_fn(p, x, c, shared)
        new_carries.append(nc)
    if carry is None:
        return x, None
    return x, jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *new_carries
    )


@pytest.mark.parametrize("n_microbatches", [None, 8])
def test_pipeline_matches_sequential(n_microbatches):
    n_stages, B = 4, 8
    mesh = _mesh(n_stages)
    params = _make_stage_params(jax.random.PRNGKey(0), n_stages)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    y_seq, _ = _sequential(params, x)
    y_pipe, _ = pipeline_apply(
        lambda p, xb, c, s: (_stage_fn(p, xb, None, None)[0], None),
        params,
        x,
        mesh=mesh,
        n_microbatches=n_microbatches,
    )
    np.testing.assert_allclose(y_pipe, y_seq, rtol=1e-6, atol=1e-6)


def test_pipeline_carry_and_shared():
    n_stages, B = 4, 8
    mesh = _mesh(n_stages)
    params = _make_stage_params(jax.random.PRNGKey(2), n_stages)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, D))
    carry = {
        "acc": jax.random.normal(jax.random.PRNGKey(4), (n_stages, B))
    }
    shared = {
        "scale": 1.0
        + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (B, 1))
    }

    y_seq, carry_seq = _sequential(params, x, carry, shared)
    y_pipe, carry_pipe = pipeline_apply(
        _stage_fn, params, x, mesh=mesh, stage_carry=carry, shared=shared
    )
    np.testing.assert_allclose(y_pipe, y_seq, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        carry_pipe["acc"], carry_seq["acc"], rtol=1e-6, atol=1e-6
    )


@pytest.mark.slow
def test_pipeline_gradients_match_sequential():
    """Backprop through the schedule == backprop through the stack; the
    fill/drain bubble computations must be gradient-invisible."""
    n_stages, B = 8, 8
    mesh = _mesh(n_stages)
    params = _make_stage_params(jax.random.PRNGKey(6), n_stages)
    x = jax.random.normal(jax.random.PRNGKey(7), (B, D))
    target = jax.random.normal(jax.random.PRNGKey(8), (B, D))

    def loss_seq(p):
        y, _ = _sequential(p, x)
        return jnp.mean((y - target) ** 2)

    def loss_pipe(p):
        y, _ = pipeline_apply(
            lambda pp_, xb, c, s: (_stage_fn(pp_, xb, None, None)[0], None),
            p,
            x,
            mesh=mesh,
        )
        return jnp.mean((y - target) ** 2)

    g_seq = jax.grad(loss_seq)(params)
    g_pipe = jax.grad(loss_pipe)(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g_seq,
        g_pipe,
    )


def test_pipeline_under_jit_with_shardings():
    """jit + explicitly placed stage params (the dryrun/driver path)."""
    n_stages, B = 4, 8
    mesh = _mesh(n_stages)
    params = _make_stage_params(jax.random.PRNGKey(9), n_stages)
    shardings = stage_param_shardings(mesh, params)
    params_placed = jax.tree_util.tree_map(
        jax.device_put, params, shardings
    )
    x = jax.random.normal(jax.random.PRNGKey(10), (B, D))

    @jax.jit
    def fwd(p, x):
        y, _ = pipeline_apply(
            lambda pp_, xb, c, s: (_stage_fn(pp_, xb, None, None)[0], None),
            p,
            x,
            mesh=mesh,
        )
        return y

    y_seq, _ = _sequential(params, x)
    np.testing.assert_allclose(
        fwd(params_placed, x), y_seq, rtol=1e-6, atol=1e-6
    )


def test_pipeline_rejects_bad_microbatching():
    mesh = _mesh(4)
    params = _make_stage_params(jax.random.PRNGKey(11), 4)
    x = jnp.zeros((6, D))
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_apply(
            lambda p, xb, c, s: (xb, None),
            params,
            x,
            mesh=mesh,
            n_microbatches=4,
        )


def test_multi_pass_pipeline_matches_sequential():
    """8 stages on 4 devices: the looped schedule (2 passes of the
    4-stage pipeline) must equal the sequential 8-stage tower, carries
    included."""
    from torchbeast_tpu.parallel.pp import pipeline_apply_multi

    n_stages, n_dev, B = 8, 4, 8
    mesh = _mesh(n_dev)
    params = _make_stage_params(jax.random.PRNGKey(20), n_stages)
    x = jax.random.normal(jax.random.PRNGKey(21), (B, D))
    carry = {
        "acc": jax.random.normal(jax.random.PRNGKey(22), (n_stages, B))
    }
    shared = {
        "scale": 1.0
        + 0.1 * jax.random.normal(jax.random.PRNGKey(23), (B, 1))
    }

    y_seq, carry_seq = _sequential(params, x, carry, shared)
    y_pipe, carry_pipe = pipeline_apply_multi(
        _stage_fn, params, x, mesh=mesh, stage_carry=carry, shared=shared
    )
    np.testing.assert_allclose(y_pipe, y_seq, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        carry_pipe["acc"], carry_seq["acc"], rtol=1e-6, atol=1e-6
    )


@pytest.mark.slow
def test_multi_pass_pipeline_gradients_match_sequential():
    from torchbeast_tpu.parallel.pp import pipeline_apply_multi

    n_stages, n_dev, B = 8, 4, 8
    mesh = _mesh(n_dev)
    params = _make_stage_params(jax.random.PRNGKey(24), n_stages)
    x = jax.random.normal(jax.random.PRNGKey(25), (B, D))
    target = jax.random.normal(jax.random.PRNGKey(26), (B, D))

    def loss_seq(p):
        y, _ = _sequential(p, x)
        return jnp.mean((y - target) ** 2)

    def loss_pipe(p):
        y, _ = pipeline_apply_multi(
            lambda pp_, xb, c, s: (_stage_fn(pp_, xb, None, None)[0], None),
            p,
            x,
            mesh=mesh,
        )
        return jnp.mean((y - target) ** 2)

    g_seq = jax.grad(loss_seq)(params)
    g_pipe = jax.grad(loss_pipe)(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g_seq,
        g_pipe,
    )


def test_multi_pass_rejects_non_multiple():
    from torchbeast_tpu.parallel.pp import pipeline_apply_multi

    mesh = _mesh(4)
    params = _make_stage_params(jax.random.PRNGKey(27), 6)
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_apply_multi(
            lambda p, xb, c, s: (xb, None),
            params,
            jnp.zeros((8, D)),
            mesh=mesh,
        )


def test_can_pipeline_gate():
    """The single divisibility gate the models' fallback and the
    drivers' validation share (parallel/pp.py can_pipeline)."""
    from torchbeast_tpu.parallel import create_mesh
    from torchbeast_tpu.parallel.pp import can_pipeline

    pipe_only = create_mesh(4, pipe_parallelism=4)  # data=1 x pipe=4
    assert can_pipeline(pipe_only, 8, "pipe")
    assert not can_pipeline(pipe_only, 6, "pipe")  # 6 % 4 != 0
    assert can_pipeline(pipe_only, 6, "pipe", n_microbatches=3)
    composite = create_mesh(8, pipe_parallelism=4)  # data=2 x pipe=4
    assert can_pipeline(composite, 8, "pipe", batch_axis="data")
    # 4 rows -> mb=1 per microbatch, not divisible by data=2.
    assert not can_pipeline(composite, 4, "pipe", batch_axis="data")
    # Custom M fixes it: mb=2 rows over data=2.
    assert can_pipeline(
        composite, 4, "pipe", n_microbatches=2, batch_axis="data"
    )
