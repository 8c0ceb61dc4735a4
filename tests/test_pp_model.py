"""PipelinedMLPNet: the pipeline-parallel torso must match the sequential
torso with identical parameters, and the FULL IMPALA learner step must
train it over a `pipe` mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import create_model
from torchbeast_tpu.parallel.pp import stage_param_shardings

pytestmark = pytest.mark.slow

T, B, A = 4, 8, 5


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "frame": rng.integers(0, 256, (T + 1, B, 6, 6, 1), dtype=np.uint8),
        "reward": rng.standard_normal((T + 1, B)).astype(np.float32),
        "done": rng.random((T + 1, B)) < 0.15,
        "episode_return": rng.standard_normal((T + 1, B)).astype(np.float32),
        "episode_step": rng.integers(0, 9, (T + 1, B)).astype(np.int32),
        "last_action": rng.integers(0, A, (T + 1, B)).astype(np.int32),
        "action": rng.integers(0, A, (T + 1, B)).astype(np.int32),
        "policy_logits": rng.standard_normal((T + 1, B, A)).astype(
            np.float32
        ),
        "baseline": rng.standard_normal((T + 1, B)).astype(np.float32),
    }


def _models(n_stages=4, use_lstm=False):
    mesh = Mesh(np.asarray(jax.devices()[:n_stages]), ("pipe",))
    kwargs = dict(
        num_actions=A, use_lstm=use_lstm, num_stages=n_stages, d_model=32
    )
    seq = create_model("pipelined_mlp", **kwargs)
    pipe = create_model("pipelined_mlp", mesh=mesh, **kwargs)
    return seq, pipe, mesh


def test_pipelined_model_matches_sequential():
    seq, pipe, _ = _models()
    batch = _batch()
    state = seq.initial_state(B)
    params = scaffold.init(
        seq,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch,
        state,
    )
    out_seq, _ = scaffold.forward(seq)(params, batch, state)
    out_pipe, _ = scaffold.forward(pipe)(params, batch, state)
    np.testing.assert_allclose(
        out_pipe.policy_logits, out_seq.policy_logits, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        out_pipe.baseline, out_seq.baseline, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(out_pipe.action, out_seq.action)


def test_pipelined_model_update_step_matches_sequential():
    """One full V-trace/RMSProp update: pipelined gradients == sequential
    gradients through the whole IMPALA loss."""
    seq, pipe, mesh = _models()
    batch = _batch(seed=1)
    state = seq.initial_state(B)
    params = scaffold.init(
        seq,
        {"params": jax.random.PRNGKey(2), "action": jax.random.PRNGKey(3)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)

    step_seq = learner_lib.make_update_step(seq, optimizer, hp, donate=False)
    step_pipe = learner_lib.make_update_step(
        pipe, optimizer, hp, donate=False
    )

    p_seq, _, stats_seq = step_seq(
        params, optimizer.init(params), batch, state
    )
    # The pipelined run places stage params sharded one-per-device (the
    # real deployment layout).
    shardings = stage_param_shardings(
        mesh, params["params"], axis="pipe"
    )
    from torchbeast_tpu.models import PipelinedMLPNet

    placed = {
        "params": {
            k: (
                jax.device_put(v, shardings[k])
                if k in PipelinedMLPNet.STAGE_PARAM_NAMES
                else v
            )
            for k, v in params["params"].items()
        }
    }
    p_pipe, _, stats_pipe = step_pipe(
        placed, optimizer.init(placed), batch, state
    )

    np.testing.assert_allclose(
        float(stats_pipe["total_loss"]),
        float(stats_seq["total_loss"]),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        float(stats_pipe["grad_norm"]),
        float(stats_seq["grad_norm"]),
        rtol=1e-4,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        p_pipe,
        p_seq,
    )


def test_pipelined_model_with_lstm_head():
    seq, pipe, _ = _models(use_lstm=True)
    batch = _batch(seed=2)
    state = seq.initial_state(B)
    assert len(state) == 2  # (h, c)
    params = scaffold.init(
        seq,
        {"params": jax.random.PRNGKey(4), "action": jax.random.PRNGKey(5)},
        batch,
        state,
    )
    out_seq, st_seq = scaffold.forward(seq)(params, batch, state)
    out_pipe, st_pipe = scaffold.forward(pipe)(params, batch, state)
    np.testing.assert_allclose(
        out_pipe.policy_logits, out_seq.policy_logits, rtol=1e-5, atol=1e-5
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        ),
        st_pipe,
        st_seq,
    )


def test_pipelined_model_microbatch_count():
    """T*B tokens split into more microbatches than stages still match."""
    n_stages = 4
    mesh = Mesh(np.asarray(jax.devices()[:n_stages]), ("pipe",))
    kwargs = dict(num_actions=A, num_stages=n_stages, d_model=32)
    seq = create_model("pipelined_mlp", **kwargs)
    pipe = create_model(
        "pipelined_mlp", mesh=mesh, n_microbatches=8, **kwargs
    )
    batch = _batch(seed=3)
    params = scaffold.init(
        seq,
        {"params": jax.random.PRNGKey(6), "action": jax.random.PRNGKey(7)},
        batch,
        (),
    )
    out_seq, _ = scaffold.forward(seq)(params, batch, ())
    out_pipe, _ = scaffold.forward(pipe)(params, batch, ())
    np.testing.assert_allclose(
        out_pipe.policy_logits, out_seq.policy_logits, rtol=1e-5, atol=1e-5
    )


def test_pipelined_model_more_stages_than_devices():
    """num_stages = 2x the pipe axis: the looped schedule must match the
    sequential 8-stage tower."""
    n_dev, n_stages = 4, 8
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("pipe",))
    kwargs = dict(num_actions=A, num_stages=n_stages, d_model=32)
    seq = create_model("pipelined_mlp", **kwargs)
    pipe = create_model("pipelined_mlp", mesh=mesh, **kwargs)
    batch = _batch(seed=9)
    params = scaffold.init(
        seq,
        {"params": jax.random.PRNGKey(30), "action": jax.random.PRNGKey(31)},
        batch,
        (),
    )
    out_seq, _ = scaffold.forward(seq)(params, batch, ())
    out_pipe, _ = scaffold.forward(pipe)(params, batch, ())
    np.testing.assert_allclose(
        out_pipe.policy_logits, out_seq.policy_logits, rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# PipelinedTransformerNet: the long-context family under the same schedule.
# ---------------------------------------------------------------------------

def _tf_models(n_dev=4, num_layers=4, n_microbatches=None):
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("pipe",))
    kwargs = dict(
        num_actions=A, num_layers=num_layers, d_model=32, num_heads=2,
        memory_len=8,
    )
    seq = create_model("pipelined_transformer", **kwargs)
    pipe = create_model(
        "pipelined_transformer", mesh=mesh,
        n_microbatches=n_microbatches, **kwargs
    )
    return seq, pipe, mesh


def test_pipelined_transformer_matches_sequential_with_cache():
    """Two chained unrolls: outputs AND the rolled KV-cache state must
    match the sequential stack bitwise-close (the cache rides the
    pipeline as resident stage carry)."""
    seq, pipe, _ = _tf_models()
    b1, b2 = _batch(seed=4), _batch(seed=5)
    state0 = seq.initial_state(B)
    params = scaffold.init(
        seq,
        {"params": jax.random.PRNGKey(8), "action": jax.random.PRNGKey(9)},
        b1,
        state0,
    )
    out_s1, st_s = scaffold.forward(seq)(params, b1, state0)
    out_p1, st_p = scaffold.forward(pipe)(params, b1, state0)
    np.testing.assert_allclose(
        out_p1.policy_logits, out_s1.policy_logits, rtol=1e-5, atol=1e-5
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        ),
        st_p,
        st_s,
    )
    # Second unroll from the carried (non-zero) cache.
    out_s2, _ = scaffold.forward(seq)(params, b2, st_s)
    out_p2, _ = scaffold.forward(pipe)(params, b2, st_p)
    np.testing.assert_allclose(
        out_p2.policy_logits, out_s2.policy_logits, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        out_p2.baseline, out_s2.baseline, rtol=1e-5, atol=1e-5
    )


def test_pipelined_transformer_update_step_matches_sequential():
    """Full V-trace/RMSProp update: pipelined gradients == sequential
    gradients, with stage params placed sharded over the pipe axis."""
    from torchbeast_tpu.models import PipelinedTransformerNet

    seq, pipe, mesh = _tf_models()
    batch = _batch(seed=6)
    state = seq.initial_state(B)
    params = scaffold.init(
        seq,
        {"params": jax.random.PRNGKey(10), "action": jax.random.PRNGKey(11)},
        batch,
        state,
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)
    step_seq = learner_lib.make_update_step(seq, optimizer, hp, donate=False)
    step_pipe = learner_lib.make_update_step(
        pipe, optimizer, hp, donate=False
    )
    p_seq, _, stats_seq = step_seq(
        params, optimizer.init(params), batch, state
    )
    shardings = stage_param_shardings(mesh, params["params"], axis="pipe")
    placed = {
        "params": {
            k: (
                jax.device_put(v, shardings[k])
                if k in PipelinedTransformerNet.STAGE_PARAM_NAMES
                else v
            )
            for k, v in params["params"].items()
        }
    }
    p_pipe, _, stats_pipe = step_pipe(
        placed, optimizer.init(placed), batch, state
    )
    np.testing.assert_allclose(
        float(stats_pipe["total_loss"]), float(stats_seq["total_loss"]),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        float(stats_pipe["grad_norm"]), float(stats_seq["grad_norm"]),
        rtol=1e-4,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        p_pipe,
        p_seq,
    )


def test_pipelined_transformer_looped_and_microbatched():
    """8 layers on 4 devices (looped schedule) with M=8 microbatches."""
    seq, pipe, _ = _tf_models(n_dev=4, num_layers=8, n_microbatches=8)
    batch = _batch(seed=7)
    state = seq.initial_state(B)
    params = scaffold.init(
        seq,
        {"params": jax.random.PRNGKey(12), "action": jax.random.PRNGKey(13)},
        batch,
        state,
    )
    out_seq, _ = scaffold.forward(seq)(params, batch, state)
    out_pipe, _ = scaffold.forward(pipe)(params, batch, state)
    np.testing.assert_allclose(
        out_pipe.policy_logits, out_seq.policy_logits, rtol=1e-5, atol=1e-5
    )


def test_pipelined_transformer_acting_fallback():
    """T=1, B=1 acting batch (indivisible by microbatches): the mesh
    model must fall back to the sequential loop, not crash, and agree
    with the no-mesh model."""
    seq, pipe, _ = _tf_models()
    rng = np.random.default_rng(8)
    inputs = {
        "frame": rng.integers(0, 256, (1, 1, 6, 6, 1), dtype=np.uint8),
        "reward": np.zeros((1, 1), np.float32),
        "done": np.zeros((1, 1), bool),
        "last_action": np.zeros((1, 1), np.int32),
    }
    state = seq.initial_state(1)
    batch = _batch(seed=9)
    params = scaffold.init(
        seq,
        {"params": jax.random.PRNGKey(14), "action": jax.random.PRNGKey(15)},
        batch,
        seq.initial_state(B),
    )
    out_s, st_s = scaffold.forward(seq)(params, inputs, state)
    out_p, st_p = scaffold.forward(pipe)(params, inputs, state)
    np.testing.assert_allclose(
        out_p.policy_logits, out_s.policy_logits, rtol=1e-5, atol=1e-5
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        ),
        st_p,
        st_s,
    )


def test_pipelined_transformer_remat_matches():
    """remat=True on the pipelined transformer: same outputs from both
    the pipelined and the sequential path (the jax.checkpoint wrapper
    applies to both, keeping the parity oracle exact)."""
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("pipe",))
    kwargs = dict(
        num_actions=A, num_layers=4, d_model=32, num_heads=2,
        memory_len=8,
    )
    plain = create_model("pipelined_transformer", **kwargs)
    remat_seq = create_model("pipelined_transformer", remat=True, **kwargs)
    remat_pipe = create_model(
        "pipelined_transformer", remat=True, mesh=mesh, **kwargs
    )
    batch = _batch(seed=11)
    state = plain.initial_state(B)
    params = scaffold.init(
        plain,
        {"params": jax.random.PRNGKey(42), "action": jax.random.PRNGKey(43)},
        batch,
        state,
    )
    out_plain, _ = scaffold.forward(plain)(params, batch, state)
    out_rs, _ = scaffold.forward(remat_seq)(params, batch, state)
    out_rp, _ = scaffold.forward(remat_pipe)(params, batch, state)
    np.testing.assert_allclose(
        out_rs.policy_logits, out_plain.policy_logits, rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        out_rp.policy_logits, out_plain.policy_logits, rtol=1e-5, atol=1e-5
    )
