"""Fused Pallas attention (interpret mode on CPU): op-level parity with
the jnp reference, model-level parity with the transformer's dense path,
and gradient flow through the custom VJP."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu.models import TransformerNet
from torchbeast_tpu.ops.pallas_attention import (
    _reference,
    transformer_attention,
)

B, T, H, D, M = 2, 12, 4, 16, 8


def make_op_inputs(seed=0, t=T, m=M):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, t, H, D)).astype(np.float32))
    k = jnp.asarray(
        rng.standard_normal((B, m + t, H, D)).astype(np.float32)
    )
    v = jnp.asarray(
        rng.standard_normal((B, m + t, H, D)).astype(np.float32)
    )
    done = rng.random((t, B)) < 0.15
    seg = jnp.asarray(np.cumsum(done, axis=0).T.astype(np.int32))
    cache_valid = jnp.asarray(
        (rng.random((B, m)) < 0.7).astype(np.float32)
    )
    no_done = jnp.asarray(np.cumsum(done, axis=0).T == 0)
    rel_bias = jnp.asarray(
        rng.standard_normal((H, m + 1)).astype(np.float32) * 0.1
    )
    return q, k, v, seg, cache_valid, no_done, rel_bias


@pytest.mark.parametrize("t,m", [(T, M), (1, M), (6, 3), (16, 0)])
def test_kernel_matches_reference(t, m):
    if m == 0:
        pytest.skip("memory_len 0 not a supported configuration")
    q, k, v, seg, valid, nodone, bias = make_op_inputs(seed=1, t=t, m=m)
    ours = transformer_attention(
        m, True, q, k, v, seg, valid, nodone, bias
    )
    ref = _reference(q, k, v, seg, valid, nodone, bias, m)
    np.testing.assert_allclose(
        np.asarray(ours), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


def test_gradients_flow_and_match_reference():
    q, k, v, seg, valid, nodone, bias = make_op_inputs(seed=2)

    def ours(q, k, v, bias):
        return jnp.sum(
            transformer_attention(M, True, q, k, v, seg, valid, nodone,
                                  bias) ** 2
        )

    def ref(q, k, v, bias):
        return jnp.sum(
            _reference(q, k, v, seg, valid, nodone, bias, M) ** 2
        )

    g_ours_fn = jax.jit(jax.grad(ours, argnums=(0, 1, 2, 3)))
    g_ours = g_ours_fn(q, k, v, bias)
    g_ref_fn = jax.jit(jax.grad(ref, argnums=(0, 1, 2, 3)))
    g_ref = g_ref_fn(q, k, v, bias)
    for a, b in zip(g_ours, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
        )


def test_vmem_guard_rejects_long_context():
    t = 4096
    q, k, v, seg, valid, nodone, bias = make_op_inputs(seed=3, t=t, m=M)
    with pytest.raises(ValueError, match="VMEM"):
        transformer_attention(M, True, q, k, v, seg, valid, nodone, bias)


# ---- model-level parity ----

A = 4
FRAME = (8, 8, 1)


def make_model_inputs(seed=0, t=6, done=None):
    rng = np.random.default_rng(seed)
    if done is None:
        done = np.zeros((t, B), bool)
    return {
        "frame": jnp.asarray(
            rng.integers(0, 256, (t, B) + FRAME, dtype=np.uint8)
        ),
        "reward": jnp.asarray(
            rng.standard_normal((t, B)).astype(np.float32)
        ),
        "done": jnp.asarray(done),
        "last_action": jnp.asarray(rng.integers(0, A, (t, B))),
    }


def test_model_pallas_matches_dense():
    t = 6
    dense = TransformerNet(num_actions=A, memory_len=4)
    palls = TransformerNet(num_actions=A, memory_len=4,
                           attention_impl="pallas")
    warm = make_model_inputs(seed=11, t=t)
    done = np.zeros((t, B), bool)
    done[2] = True
    inputs = make_model_inputs(seed=12, t=t, done=done)

    state0 = dense.initial_state(B)
    params = scaffold.init(
        dense,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        warm, state0,
    )
    _, cache = scaffold.forward(dense)(params, warm, state0)
    out_d, state_d = scaffold.forward(dense)(params, inputs, cache)
    out_p, state_p = scaffold.forward(palls)(params, inputs, cache)
    np.testing.assert_allclose(
        np.asarray(out_p.policy_logits), np.asarray(out_d.policy_logits),
        rtol=2e-4, atol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(out_p.baseline), np.asarray(out_d.baseline),
        rtol=2e-4, atol=2e-5,
    )
    for (dk, dv, dval), (pk, pv, pval) in zip(state_d, state_p):
        np.testing.assert_allclose(np.asarray(pk), np.asarray(dk),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(pv), np.asarray(dv),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(pval), np.asarray(dval))


def test_model_pallas_stepwise_T1():
    """The acting path (T=1) also runs through the kernel."""
    palls = TransformerNet(num_actions=A, attention_impl="pallas")
    dense = TransformerNet(num_actions=A)
    inputs = make_model_inputs(seed=21, t=1)
    state = dense.initial_state(B)
    params = scaffold.init(
        dense,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        inputs, state,
    )
    out_d, _ = scaffold.forward(dense)(params, inputs, state)
    out_p, _ = scaffold.forward(palls)(params, inputs, state)
    np.testing.assert_allclose(
        np.asarray(out_p.policy_logits), np.asarray(out_d.policy_logits),
        rtol=2e-4, atol=2e-5,
    )
