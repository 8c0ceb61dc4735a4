"""Fused Pallas optimizer tail (--opt_impl pallas, ops/pallas_opt.py):
parity against the optax chain.

The parity matrix runs REAL update steps ({MLP, LSTM} x
{f32, bf16_train} x clip active/inactive x momentum) and compares the
full post-update state leaf-for-leaf: resident params, second moment,
momentum trace, schedule count, grad-norm stats, and — under
bf16_train — the master round-trip invariant (resident ==
bf16(master) exactly, the same contract learner._bf16_resident_params
pins). The kernel runs the identical f32 math in the identical order,
so tolerances are one-f32-rounding tight.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import precision as precision_lib
from torchbeast_tpu.models import create_model
from torchbeast_tpu.ops.pallas_opt import FusedTailState

T, B, A = 6, 4, 4
FRAME = (4, 4, 1)


def make_batch(seed=0, t=T, b=B):
    rng = np.random.default_rng(seed)
    return {
        "frame": rng.integers(0, 256, (t + 1, b) + FRAME, dtype=np.uint8),
        "reward": rng.standard_normal((t + 1, b)).astype(np.float32),
        "done": rng.random((t + 1, b)) < 0.1,
        "episode_return": rng.standard_normal((t + 1, b)).astype(
            np.float32
        ),
        "episode_step": rng.integers(0, 200, (t + 1, b)).astype(np.int32),
        "last_action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "action": rng.integers(0, A, (t + 1, b)).astype(np.int32),
        "policy_logits": rng.standard_normal((t + 1, b, A)).astype(
            np.float32
        ),
        "baseline": rng.standard_normal((t + 1, b)).astype(np.float32),
    }


def _setup(precision, use_lstm, clip, momentum=0.0):
    pol = precision_lib.get(precision)
    hp = learner_lib.HParams(
        unroll_length=T, batch_size=B, total_steps=100_000,
        opt_state_dtype=pol.opt_state_dtype,
        param_dtype=pol.param_dtype,
        grad_norm_clipping=clip,
        rmsprop_momentum=momentum,
    )
    model = create_model(
        "mlp", num_actions=A, use_lstm=use_lstm,
        dtype=pol.compute_dtype, head_dtype=pol.head_dtype,
    )
    batch = precision_lib.cast_batch(make_batch(), pol.batch_dtype)
    state = precision_lib.cast_batch(
        jax.tree_util.tree_map(
            np.asarray, model.initial_state(B)
        ),
        pol.batch_dtype,
    )
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        make_batch(t=0),
        model.initial_state(B),
    )
    params = precision_lib.cast_params(params, pol)
    return hp, model, params, batch, state


def _run_updates(hp, model, params, batch, state, n=3):
    optimizer = learner_lib.make_optimizer(hp)
    update = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    )
    p = jax.tree_util.tree_map(jnp.copy, params)
    o = optimizer.init(p)
    stats = None
    for _ in range(n):
        p, o, stats = update(p, o, batch, state)
    return p, o, stats


def _assert_trees_close(a, b, atol, rtol=1e-5):
    # rtol covers f32 reassociation drift on O(1)+ magnitudes (the
    # momentum trace accumulates across updates); atol the near-zero
    # leaves.
    for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            atol=atol, rtol=rtol,
        )


# clip=0.05 forces the rescale branch on every update (grad norms here
# are O(1)); clip=1e9 keeps it inactive — both sides of the kernel's
# global-norm select.
@pytest.mark.parametrize("clip", [0.05, 1e9])
@pytest.mark.parametrize("use_lstm", [False, True])
@pytest.mark.parametrize("precision", ["f32", "bf16_train"])
def test_fused_tail_matches_optax(precision, use_lstm, clip):
    hp, model, params, batch, state = _setup(precision, use_lstm, clip)
    p_x, o_x, s_x = _run_updates(
        hp._replace(opt_impl="xla"), model, params, batch, state
    )
    p_p, o_p, s_p = _run_updates(
        hp._replace(opt_impl="pallas"), model, params, batch, state
    )
    assert isinstance(o_p, FusedTailState)
    atol = 1e-6 if precision == "f32" else 0.0
    _assert_trees_close(p_x, p_p, atol=atol)
    # grad-norm stats: same grads both paths, exactly.
    np.testing.assert_allclose(
        float(s_x["grad_norm"]), float(s_p["grad_norm"]), rtol=1e-6
    )
    # Schedule clock ticked once per update on both paths.
    import optax

    assert int(o_p.count) == 3
    assert int(optax.tree_utils.tree_get(o_x, "count")) == 3
    # Second moment parity (storage dtype included).
    nu_x = optax.tree_utils.tree_get(o_x, "nu")
    for x, y in zip(
        jax.tree_util.tree_leaves(nu_x),
        jax.tree_util.tree_leaves(o_p.nu),
    ):
        assert x.dtype == y.dtype
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            atol=max(atol, 1e-6), rtol=1e-4,
        )


def test_fused_tail_momentum_matches_trace():
    hp, model, params, batch, state = _setup(
        "f32", use_lstm=False, clip=40.0, momentum=0.9
    )
    p_x, o_x, _ = _run_updates(
        hp._replace(opt_impl="xla"), model, params, batch, state
    )
    p_p, o_p, _ = _run_updates(
        hp._replace(opt_impl="pallas"), model, params, batch, state
    )
    _assert_trees_close(p_x, p_p, atol=1e-6)
    import optax

    trace_x = optax.tree_utils.tree_get(o_x, "trace")
    # The trace accumulates g/(sqrt(nu)+eps) terms: early-training nu
    # is tiny, so a one-ulp nu difference amplifies by ~1/eps into the
    # quotient and the momentum sum compounds it — hence the looser
    # rtol here while the params (scaled by lr=4.8e-4) stay tight.
    _assert_trees_close(trace_x, o_p.mom, atol=1e-5, rtol=1e-3)


def test_bf16_master_round_trip_exact():
    """The resident params ARE bf16(master) after every fused update —
    the kernel's narrowing cast is the one the bf16-resident contract
    pins (rounding never compounds)."""
    hp, model, params, batch, state = _setup(
        "bf16_train", use_lstm=True, clip=40.0
    )
    p, o, _ = _run_updates(
        hp._replace(opt_impl="pallas"), model, params, batch, state
    )
    assert o.master is not None
    for res, mst in zip(
        jax.tree_util.tree_leaves(p),
        jax.tree_util.tree_leaves(o.master),
    ):
        assert res.dtype == jnp.bfloat16
        assert mst.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(res, np.float32),
            np.asarray(mst.astype(jnp.bfloat16), np.float32),
        )


def test_fused_tail_rejects_factored_state():
    hp = learner_lib.HParams(opt_impl="pallas", opt_factored=True)
    with pytest.raises(ValueError, match="factored"):
        learner_lib.make_optimizer(hp)


def test_entropy_anneal_reads_fused_count():
    """entropy_schedule resolves its clock through the fused state's
    `count` field (same name as the optax chain's, by design)."""
    hp = learner_lib.HParams(
        opt_impl="pallas", entropy_cost=0.01, entropy_cost_final=0.0,
        total_steps=1000, unroll_length=T, batch_size=B,
    )
    optimizer = learner_lib.make_optimizer(hp)
    model = create_model("mlp", num_actions=A)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        make_batch(t=0),
        (),
    )
    opt_state = optimizer.init(params)
    cost_at = learner_lib.entropy_schedule(hp)
    assert float(cost_at(opt_state)) == pytest.approx(0.01)
