"""Learner-step arithmetic: exact optimizer update vs a manual calculation,
weight change directionality, and stats plumbing (reference strategy:
tests/polybeast_learn_function_test.py — mock-driven exact-SGD checks)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import create_model

T, B, A = 4, 2, 3


def make_batch(rng_seed=0, t=T, b=B):
    rng = np.random.default_rng(rng_seed)
    return {
        # 48px: the smallest-ish frame the shallow conv stack still accepts.
        "frame": rng.integers(0, 256, (t + 1, b, 48, 48, 1), dtype=np.uint8),
        "reward": rng.standard_normal((t + 1, b)).astype(np.float32),
        "done": rng.random((t + 1, b)) < 0.2,
        "episode_return": rng.standard_normal((t + 1, b)).astype(np.float32),
        "episode_step": rng.integers(0, 100, (t + 1, b)).astype(np.int32),
        "last_action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "policy_logits": rng.standard_normal((t + 1, b, A)).astype(np.float32),
        "baseline": rng.standard_normal((t + 1, b)).astype(np.float32),
    }


@pytest.fixture(scope="module")
def model_and_params():
    model = create_model("shallow", num_actions=A)
    batch = make_batch()
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch,
        (),
    )
    return model, params


def test_update_step_matches_manual_sgd(model_and_params):
    """With plain SGD the update must be exactly params - lr * grad."""
    model, params = model_and_params
    hp = learner_lib.HParams()
    lr = 0.1
    optimizer = optax.sgd(lr)
    opt_state = optimizer.init(params)
    batch = make_batch()

    traced = jax.jit(jax.grad(
        lambda p: learner_lib.compute_loss(model, p, batch, (), hp),
        has_aux=True,
    ))
    grads, _ = traced(params)
    expected = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)

    update_step = learner_lib.make_update_step(model, optimizer, hp)
    # update_step donates params/opt_state; hand it copies so the shared
    # fixture stays alive.
    donated = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
    new_params, _, _ = update_step(*donated, batch, ())
    for e, n in zip(
        jax.tree_util.tree_leaves(expected),
        jax.tree_util.tree_leaves(new_params),
    ):
        np.testing.assert_allclose(e, n, rtol=1e-5, atol=1e-6)


def test_update_step_returns_stats(model_and_params):
    model, params = model_and_params
    hp = learner_lib.HParams()
    optimizer = learner_lib.make_optimizer(hp)
    opt_state = optimizer.init(params)
    update_step = learner_lib.make_update_step(model, optimizer, hp)
    donated = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
    _, _, stats = update_step(*donated, make_batch(), ())
    for key in (
        "total_loss", "pg_loss", "baseline_loss", "entropy_loss", "grad_norm",
        "episode_returns_sum", "episode_count",
    ):
        assert key in stats
        assert np.isfinite(jax.device_get(stats[key]))
    post = learner_lib.episode_stat_postprocess(jax.device_get(stats))
    assert "episodes_finished" in post


def test_episode_return_aggregation(model_and_params):
    model, params = model_and_params
    hp = learner_lib.HParams()
    batch = make_batch()
    _, stats = learner_lib.compute_loss(model, params, batch, (), hp)
    done = batch["done"][1:]
    expected_sum = batch["episode_return"][1:][done].sum()
    np.testing.assert_allclose(
        stats["episode_returns_sum"], expected_sum, rtol=1e-5
    )
    assert int(stats["episode_count"]) == int(done.sum())


def test_lr_schedule_decays_to_zero():
    hp = learner_lib.HParams(
        total_steps=1000, unroll_length=10, batch_size=10, learning_rate=1.0
    )
    frames_per_update = 100
    schedule = optax.linear_schedule(
        hp.learning_rate, 0.0, hp.total_steps // frames_per_update
    )
    assert schedule(0) == 1.0
    assert schedule(5) == 0.5
    assert schedule(10) == 0.0
    assert schedule(20) == 0.0  # stays at zero past the horizon


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_rmsprop_matches_torch_semantics(momentum):
    """Multi-step _rmsprop_torch (the learner's version-portable
    torch-RMSprop: upstream eps_in_sqrt=False where available, composed
    primitives on optax 0.2.3) vs torch.optim.RMSprop on the same
    tensors, with and without momentum."""
    torch = pytest.importorskip("torch")
    rng = np.random.default_rng(0)
    w = rng.standard_normal(5).astype(np.float32)
    lr, alpha, eps = 0.01, 0.99, 0.01

    tw = torch.nn.Parameter(torch.tensor(w))
    opt = torch.optim.RMSprop(
        [tw], lr=lr, alpha=alpha, eps=eps, momentum=momentum
    )
    ow = jnp.asarray(w)
    optax_opt = learner_lib._rmsprop_torch(
        lr, decay=alpha, eps=eps, momentum=momentum
    )
    state = optax_opt.init(ow)
    for step in range(3):  # multi-step: exercises nu/momentum carry
        g = rng.standard_normal(5).astype(np.float32)
        tw.grad = torch.tensor(g)
        opt.step()
        updates, state = optax_opt.update(jnp.asarray(g), state, ow)
        ow = optax.apply_updates(ow, updates)

    np.testing.assert_allclose(ow, tw.detach().numpy(), rtol=1e-5, atol=1e-6)


def test_entropy_schedule_anneal_and_constant():
    """entropy_schedule shares the LR decay's update clock: linear from
    entropy_cost to entropy_cost_final over total_steps frames, clamped
    past the horizon; None final = constant (returns None so
    compute_loss uses hp.entropy_cost untouched)."""
    import optax.tree_utils as otu

    from torchbeast_tpu import learner as learner_lib

    hp = learner_lib.HParams(
        entropy_cost=0.2, entropy_cost_final=0.0,
        total_steps=1000, unroll_length=10, batch_size=10,
    )  # 10 updates to anneal over
    opt = learner_lib.make_optimizer(hp)
    state = opt.init({"w": jnp.zeros(3)})
    at = learner_lib.entropy_schedule(hp)

    def with_count(n):
        return otu.tree_set(state, count=jnp.asarray(n, jnp.int32))

    np.testing.assert_allclose(float(at(with_count(0))), 0.2)
    np.testing.assert_allclose(float(at(with_count(5))), 0.1)
    np.testing.assert_allclose(float(at(with_count(10))), 0.0)
    np.testing.assert_allclose(float(at(with_count(20))), 0.0)  # clamped

    constant = learner_lib.entropy_schedule(
        hp._replace(entropy_cost_final=None)
    )
    assert constant(state) is None


def test_donate_argnums_policy_table():
    """Donation policy -> argnums for the (params, opt_state, batch,
    state) signature, incl. the donate_batch extension and the typo'd-
    policy guard (falling through to params donation would be unsafe
    for async drivers whose inference threads hold params refs)."""
    f = learner_lib.donate_argnums_for
    assert f(True) == (0, 1)
    assert f(False) == ()
    assert f("opt_only") == (1,)
    assert f(True, donate_batch=True) == (0, 1, 2, 3)
    assert f("opt_only", donate_batch=True) == (1, 2, 3)
    assert f(False, donate_batch=True) == (2, 3)
    with pytest.raises(ValueError, match="donation policy"):
        f("opt-only")
