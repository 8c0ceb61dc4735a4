"""Transformer policy: shapes, the cache-consistency invariant (batch
forward == step-by-step forward with carried KV cache), and episode-
boundary isolation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu.models import TransformerNet, create_model

T, B, A = 6, 2, 4
FRAME = (8, 8, 1)


def make_inputs(seed=0, t=T, done=None):
    rng = np.random.default_rng(seed)
    if done is None:
        done = np.zeros((t, B), bool)
    return {
        "frame": jnp.asarray(
            rng.integers(0, 256, (t, B) + FRAME, dtype=np.uint8)
        ),
        "reward": jnp.asarray(rng.standard_normal((t, B)).astype(np.float32)),
        "done": jnp.asarray(done),
        "last_action": jnp.asarray(rng.integers(0, A, (t, B))),
    }


def init_model(**kwargs):
    model = TransformerNet(num_actions=A, **kwargs)
    inputs = make_inputs()
    state = model.initial_state(B)
    params = scaffold.init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        inputs,
        state,
    )
    return model, params


def test_shapes_and_state():
    model, params = init_model()
    inputs = make_inputs()
    state = model.initial_state(B)
    out, new_state = scaffold.forward(model)(params, inputs, state)
    assert out.policy_logits.shape == (T, B, A)
    assert out.baseline.shape == (T, B)
    assert len(new_state) == model.num_layers
    k, v, valid = new_state[0]
    assert k.shape == (model.memory_len, B, model.num_heads,
                       model.d_model // model.num_heads)
    assert valid.shape == (model.memory_len, B)
    # After a done-free unroll from empty cache, exactly T entries valid.
    assert float(np.asarray(valid).sum()) == T * B


def _stepwise_logits(model, params, inputs, state, t_total):
    logits = []
    for t in range(t_total):
        sub = {k: v[t : t + 1] for k, v in inputs.items()}
        out, state = scaffold.forward(model)(params, sub, state)
        logits.append(out.policy_logits[0])
    return np.stack(logits), state


def test_batch_forward_matches_stepwise_with_cache():
    """The defining invariant: running T steps at once equals running one
    step at a time carrying the KV cache."""
    model, params = init_model()
    inputs = make_inputs(seed=3)
    state = model.initial_state(B)
    full, _ = scaffold.forward(model)(params, inputs, state)
    logits, _ = _stepwise_logits(model, params, inputs, state, T)
    np.testing.assert_allclose(
        logits, np.asarray(full.policy_logits), rtol=2e-4, atol=2e-5
    )


def test_batch_matches_stepwise_with_small_memory_and_full_cache():
    """The hard regime: memory_len < T AND a pre-filled cache — the batch
    (learner) forward must model the stepwise eviction exactly, or the
    behavior/target logit pairing silently breaks in training."""
    model, params = init_model(memory_len=4)  # < T = 6
    warmup = make_inputs(seed=11)
    inputs = make_inputs(seed=12)

    state0 = model.initial_state(B)
    # Fill the cache with a warmup unroll (both paths identically).
    _, batch_state = scaffold.forward(model)(params, warmup, state0)
    full, _ = scaffold.forward(model)(params, inputs, batch_state)

    _, step_state = scaffold.forward(model)(params, warmup, state0)
    logits, _ = _stepwise_logits(model, params, inputs, step_state, T)
    np.testing.assert_allclose(
        logits, np.asarray(full.policy_logits), rtol=2e-4, atol=2e-5
    )


def test_stepwise_state_equals_batch_state():
    """The cache written by one batch forward must equal the cache from T
    stepwise forwards (it is the next rollout's initial_agent_state)."""
    model, params = init_model(memory_len=4)
    inputs = make_inputs(seed=13)
    state0 = model.initial_state(B)
    _, batch_state = scaffold.forward(model)(params, inputs, state0)
    s = state0
    for t in range(T):
        sub = {k: v[t : t + 1] for k, v in inputs.items()}
        _, s = scaffold.forward(model)(params, sub, s)
    for (bk, bv, bval), (sk, sv, sval) in zip(batch_state, s):
        np.testing.assert_allclose(
            np.asarray(bk), np.asarray(sk), rtol=2e-4, atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(bv), np.asarray(sv), rtol=2e-4, atol=2e-5
        )
        np.testing.assert_array_equal(np.asarray(bval), np.asarray(sval))


def test_episode_boundary_isolates_past():
    model, params = init_model()
    done = np.zeros((T, B), bool)
    d = 3
    done[d] = True
    inputs = make_inputs(seed=5, done=done)
    state = model.initial_state(B)
    out1, _ = scaffold.forward(model)(params, inputs, state)

    # Perturb pre-boundary frames: post-boundary outputs must not move.
    frames2 = np.asarray(inputs["frame"]).copy()
    frames2[0] = 0
    frames2[1] = 255
    inputs2 = {**inputs, "frame": jnp.asarray(frames2)}
    out2, _ = scaffold.forward(model)(params, inputs2, state)
    np.testing.assert_allclose(
        np.asarray(out1.policy_logits)[d:],
        np.asarray(out2.policy_logits)[d:],
        rtol=1e-5, atol=1e-6,
    )
    assert not np.allclose(
        np.asarray(out1.policy_logits)[:d],
        np.asarray(out2.policy_logits)[:d],
    )


def test_cache_invalidated_by_done():
    """A done in unroll k+1 must hide unroll k's cache from later steps."""
    model, params = init_model()
    state = model.initial_state(B)
    # Unroll 1 fills the cache (distinct content per variant).
    u1a = make_inputs(seed=7)
    u1b = make_inputs(seed=8)
    _, state_a = scaffold.forward(model)(params, u1a, state)
    _, state_b = scaffold.forward(model)(params, u1b, state)

    # Unroll 2 starts with done at slot 0: the old cache is invisible.
    done = np.zeros((T, B), bool)
    done[0] = True
    u2 = make_inputs(seed=9, done=done)
    out_a, _ = scaffold.forward(model)(params, u2, state_a)
    out_b, _ = scaffold.forward(model)(params, u2, state_b)
    np.testing.assert_allclose(
        np.asarray(out_a.policy_logits),
        np.asarray(out_b.policy_logits),
        rtol=1e-5, atol=1e-6,
    )


def test_registry():
    assert isinstance(create_model("transformer", A), TransformerNet)


# ---- sequence-parallel (ring attention) training path ----


def _seq_mesh(n):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:n]), ("seq",))


def _ring_model(dense_model):
    """Same architecture/params, ring path active over an 8-way seq mesh."""
    return TransformerNet(
        num_actions=dense_model.num_actions,
        num_layers=dense_model.num_layers,
        d_model=dense_model.d_model,
        num_heads=dense_model.num_heads,
        memory_len=dense_model.memory_len,
        mesh=_seq_mesh(8),
    )


@pytest.mark.slow
def test_ring_path_matches_dense_forward_and_state():
    """The ring formulation (band + segments + rel-bias + cache leg,
    online-merged) must reproduce the dense path bit-for-bit-ish — with a
    pre-filled cache, mid-unroll dones, and memory_len < T so the band
    actually clips."""
    t = 16  # divisible by the 8-way mesh
    model, params = init_model(memory_len=8)
    warm = make_inputs(seed=21, t=t)
    done = np.zeros((t, B), bool)
    done[5] = True
    done[11, 0] = True
    inputs = make_inputs(seed=22, t=t, done=done)

    state0 = model.initial_state(B)
    _, cache = scaffold.forward(model)(params, warm, state0)
    dense_out, dense_state = scaffold.forward(model)(params, inputs, cache)

    ring = _ring_model(model)
    ring_out, ring_state = scaffold.forward(ring)(params, inputs, cache)

    np.testing.assert_allclose(
        np.asarray(ring_out.policy_logits),
        np.asarray(dense_out.policy_logits),
        rtol=2e-4, atol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(ring_out.baseline),
        np.asarray(dense_out.baseline),
        rtol=2e-4, atol=2e-5,
    )
    for (dk, dv, dval), (rk, rv, rval) in zip(dense_state, ring_state):
        np.testing.assert_allclose(np.asarray(rk), np.asarray(dk),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(rv), np.asarray(dv),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(rval), np.asarray(dval))


@pytest.mark.slow
def test_ring_path_gradients_match_dense():
    t = 8
    model, params = init_model(memory_len=4)
    inputs = make_inputs(seed=31, t=t)
    state = model.initial_state(B)
    ring = _ring_model(model)

    def loss(m):
        def f(p):
            out, _ = m.apply(p, inputs, state, sample_action=False)
            return jnp.sum(out.policy_logits ** 2) + jnp.sum(
                out.baseline ** 2
            )
        return f

    g_dense_fn = jax.jit(jax.grad(loss(model)))
    g_dense = g_dense_fn(params)
    g_ring_fn = jax.jit(jax.grad(loss(ring)))
    g_ring = g_ring_fn(params)
    flat_d, _ = jax.tree_util.tree_flatten(g_dense)
    flat_r, _ = jax.tree_util.tree_flatten(g_ring)
    for gd, gr in zip(flat_d, flat_r):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gd), rtol=2e-3, atol=2e-4
        )


def test_ring_path_falls_back_to_dense_for_short_t():
    """Acting at T=1 must use the dense path (T not divisible by the mesh)
    with identical params — one model serves learner and actor."""
    model, params = init_model()
    ring = _ring_model(model)
    inputs = make_inputs(seed=41, t=1)
    state = model.initial_state(B)
    out_d, _ = scaffold.forward(model)(params, inputs, state)
    out_r, _ = scaffold.forward(ring)(params, inputs, state)
    np.testing.assert_allclose(
        np.asarray(out_r.policy_logits), np.asarray(out_d.policy_logits),
        rtol=1e-6,
    )


@pytest.mark.slow
def test_zigzag_ring_path_matches_dense():
    """Zig-zag-scheduled sequence-parallel training path: same numerics
    as dense, with cache + dones + band clipping (T=32 over the 8-way
    mesh -> 16 chunks of 2)."""
    t = 32
    model, params = init_model(memory_len=8)
    warm = make_inputs(seed=51, t=t)
    done = np.zeros((t, B), bool)
    done[9] = True
    done[23, 1] = True
    inputs = make_inputs(seed=52, t=t, done=done)

    state0 = model.initial_state(B)
    _, cache = scaffold.forward(model)(params, warm, state0)
    dense_out, dense_state = scaffold.forward(model)(params, inputs, cache)

    zig = TransformerNet(
        num_actions=model.num_actions,
        num_layers=model.num_layers,
        d_model=model.d_model,
        num_heads=model.num_heads,
        memory_len=model.memory_len,
        mesh=_seq_mesh(8),
        ring_schedule="zigzag",
    )
    zig_out, zig_state = scaffold.forward(zig)(params, inputs, cache)
    np.testing.assert_allclose(
        np.asarray(zig_out.policy_logits),
        np.asarray(dense_out.policy_logits),
        rtol=2e-4, atol=2e-5,
    )
    for (dk, dv, dval), (zk, zv, zval) in zip(dense_state, zig_state):
        np.testing.assert_allclose(np.asarray(zk), np.asarray(dk),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(zv), np.asarray(dv),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(zval), np.asarray(dval))


@pytest.mark.slow
def test_zigzag_ring_path_gradients_match_dense():
    t = 16
    model, params = init_model(memory_len=4)
    inputs = make_inputs(seed=61, t=t)
    state = model.initial_state(B)
    zig = TransformerNet(
        num_actions=model.num_actions,
        num_layers=model.num_layers,
        d_model=model.d_model,
        num_heads=model.num_heads,
        memory_len=model.memory_len,
        mesh=_seq_mesh(8),
        ring_schedule="zigzag",
    )

    def loss(m):
        def f(p):
            out, _ = m.apply(p, inputs, state, sample_action=False)
            return jnp.sum(out.policy_logits ** 2) + jnp.sum(
                out.baseline ** 2
            )
        return f

    g_dense_fn = jax.jit(jax.grad(loss(model)))
    g_dense = g_dense_fn(params)
    g_zig_fn = jax.jit(jax.grad(loss(zig)))
    g_zig = g_zig_fn(params)
    flat_d, _ = jax.tree_util.tree_flatten(g_dense)
    flat_z, _ = jax.tree_util.tree_flatten(g_zig)
    for gd, gz in zip(flat_d, flat_z):
        np.testing.assert_allclose(
            np.asarray(gz), np.asarray(gd), rtol=2e-3, atol=2e-4
        )


@pytest.mark.slow
def test_remat_update_matches_non_remat():
    """--transformer_remat: per-block rematerialization must be a pure
    memory/recompute trade — outputs and one full update identical to
    the non-remat model with the same params (incl. the MoE block whose
    sown aux loss must survive the lifted transform)."""
    import numpy as np

    from torchbeast_tpu import learner as learner_lib

    T, B, A = 4, 3, 5
    rng = np.random.default_rng(21)
    batch = {
        "frame": rng.integers(0, 256, (T + 1, B, 4, 4, 1), dtype=np.uint8),
        "reward": rng.standard_normal((T + 1, B)).astype(np.float32),
        "done": rng.random((T + 1, B)) < 0.2,
        "episode_return": rng.standard_normal((T + 1, B)).astype(
            np.float32
        ),
        "episode_step": rng.integers(0, 9, (T + 1, B)).astype(np.int32),
        "last_action": rng.integers(0, A, (T + 1, B)).astype(np.int32),
        "action": rng.integers(0, A, (T + 1, B)).astype(np.int32),
        "policy_logits": rng.standard_normal((T + 1, B, A)).astype(
            np.float32
        ),
        "baseline": rng.standard_normal((T + 1, B)).astype(np.float32),
    }
    kwargs = dict(
        num_actions=A, num_layers=2, d_model=16, num_heads=2,
        memory_len=4, num_experts=4,
    )
    plain = create_model("transformer", **kwargs)
    remat = create_model("transformer", remat=True, **kwargs)
    state = plain.initial_state(B)
    params = scaffold.init(
        plain,
        {"params": jax.random.PRNGKey(40), "action": jax.random.PRNGKey(41)},
        batch,
        state,
    )
    # Identical param trees: remat is a lifted transform, not a rewrite.
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        params,
        scaffold.init(
            remat,
            {"params": jax.random.PRNGKey(40),
             "action": jax.random.PRNGKey(41)},
            batch,
            state,
        ),
    )
    hp = learner_lib.HParams(batch_size=B, unroll_length=T)
    optimizer = learner_lib.make_optimizer(hp)
    step_p = learner_lib.make_update_step(plain, optimizer, hp, donate=False)
    step_r = learner_lib.make_update_step(remat, optimizer, hp, donate=False)
    p_p, _, s_p = step_p(params, optimizer.init(params), batch, state)
    p_r, _, s_r = step_r(params, optimizer.init(params), batch, state)
    np.testing.assert_allclose(
        float(s_r["total_loss"]), float(s_p["total_loss"]), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(s_r["aux_loss"]), float(s_p["aux_loss"]), rtol=1e-6
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        p_r,
        p_p,
    )
