"""Ulysses (all-to-all head-sharded) sequence parallelism: the op and the
transformer path must match the dense computations exactly — unlike the
ring, there is no online-softmax merging, so tolerances are tight."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tests import family_scaffold as scaffold
from torchbeast_tpu.models import create_model
from torchbeast_tpu.ops.attention import (
    causal_attention,
    segment_ids_from_done,
    ulysses_attention,
)

B, T, H, D = 2, 16, 8, 4

# The functions under test through `jax.jit`, traced once a shape.
_causal = jax.jit(causal_attention)
_ulysses = jax.jit(ulysses_attention, static_argnums=3)


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("seq",))


def _qkv(key):
    ks = jax.random.split(key, 3)
    return tuple(
        jax.random.normal(k, (B, T, H, D), jnp.float32) for k in ks
    )


@pytest.mark.parametrize("n_dev", [4, 8])
def test_ulysses_matches_dense(n_dev):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    dense = _causal(q, k, v)
    out = _ulysses(q, k, v, _mesh(n_dev))
    np.testing.assert_allclose(out, dense, rtol=1e-5, atol=1e-5)


def test_ulysses_with_segments_matches_dense():
    q, k, v = _qkv(jax.random.PRNGKey(1))
    done = jax.random.bernoulli(jax.random.PRNGKey(2), 0.2, (T, B))
    seg = segment_ids_from_done(done).T  # [B, T]
    dense = _causal(q, k, v, seg)
    out = _ulysses(q, k, v, _mesh(4), segment_ids=seg)
    np.testing.assert_allclose(out, dense, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_ulysses_gradients_match_dense():
    q, k, v = _qkv(jax.random.PRNGKey(3))
    mesh = _mesh(4)

    def loss_dense(q, k, v):
        return jnp.sum(_causal(q, k, v) ** 2)

    def loss_uly(q, k, v):
        return jnp.sum(_ulysses(q, k, v, mesh) ** 2)

    g_dense_fn = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))
    g_dense = g_dense_fn(q, k, v)
    g_uly_fn = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))
    g_uly = g_uly_fn(q, k, v)
    for a, b in zip(g_uly, g_dense):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_ulysses_rejects_bad_shapes():
    q, k, v = _qkv(jax.random.PRNGKey(4))
    with pytest.raises(ValueError, match=r"H \(6\) divisible"):
        # T=16 divides over 4 devices but H=6 does not.
        _ulysses(
            q[:, :, :6], k[:, :, :6], v[:, :, :6], _mesh(4)
        )


def _transformer_batch(T_, A, seed=5):
    rng = np.random.default_rng(seed)
    return {
        "frame": rng.integers(
            0, 256, (T_ + 1, B, 6, 6, 1), dtype=np.uint8
        ),
        "reward": rng.standard_normal((T_ + 1, B)).astype(np.float32),
        "done": rng.random((T_ + 1, B)) < 0.15,
        "episode_return": rng.standard_normal((T_ + 1, B)).astype(
            np.float32
        ),
        "episode_step": rng.integers(0, 9, (T_ + 1, B)).astype(np.int32),
        "last_action": rng.integers(0, A, (T_ + 1, B)).astype(np.int32),
        "action": rng.integers(0, A, (T_ + 1, B)).astype(np.int32),
        "policy_logits": rng.standard_normal((T_ + 1, B, A)).astype(
            np.float32
        ),
        "baseline": rng.standard_normal((T_ + 1, B)).astype(np.float32),
    }


@pytest.mark.slow
def test_ulysses_transformer_matches_dense():
    """Full model forward: ulysses path == dense path with identical
    params, including cache attention, band mask, segments, rel bias."""
    A, n_dev = 5, 4
    T_ = 7  # model sees T+1 = 8 steps, divisible by 4 devices
    mesh = _mesh(n_dev)
    kwargs = dict(
        num_actions=A, num_layers=2, d_model=32, num_heads=4,
        memory_len=6,
    )
    dense = create_model("transformer", **kwargs)
    uly = create_model(
        "transformer", mesh=mesh, sp_strategy="ulysses", **kwargs
    )
    batch = _transformer_batch(T_, A)
    state = dense.initial_state(B)
    # Non-trivial cache: run one unroll with the dense model first.
    params = scaffold.init(
        dense,
        {"params": jax.random.PRNGKey(6), "action": jax.random.PRNGKey(7)},
        batch,
        state,
    )
    _, state = scaffold.forward(dense)(params, batch, state)

    out_d, st_d = scaffold.forward(dense)(params, batch, state)
    out_u, st_u = scaffold.forward(uly)(params, batch, state)
    np.testing.assert_allclose(
        out_u.policy_logits, out_d.policy_logits, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        out_u.baseline, out_d.baseline, rtol=1e-5, atol=1e-5
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        ),
        st_u,
        st_d,
    )


def test_ulysses_transformer_acting_falls_back_to_dense():
    """T=1 acting can't be head-sharded (T % blocks != 0) — same params
    must still work through the dense branch."""
    A, n_dev = 5, 4
    mesh = _mesh(n_dev)
    kwargs = dict(
        num_actions=A, num_layers=1, d_model=32, num_heads=4,
        memory_len=6,
    )
    uly = create_model(
        "transformer", mesh=mesh, sp_strategy="ulysses", **kwargs
    )
    batch = _transformer_batch(0, A)
    state = uly.initial_state(B)
    params = scaffold.init(
        uly,
        {"params": jax.random.PRNGKey(8), "action": jax.random.PRNGKey(9)},
        batch,
        state,
    )
    out, _ = scaffold.apply(uly)(
        params,
        {k: batch[k][:1] for k in
         ("frame", "reward", "done", "last_action")},
        state,
        rngs={"action": jax.random.PRNGKey(10)},
    )
    assert out.action.shape == (1, B)
