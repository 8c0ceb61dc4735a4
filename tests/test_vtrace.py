"""V-trace vs. a literal-math numpy ground truth.

Mirrors the reference's test strategy (tests/vtrace_test.py: an O(T^2)
explicit-sum implementation of the paper's Eq. 1 as ground truth), written
from the paper formula, not ported line-by-line.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchbeast_tpu.ops import vtrace


def ground_truth_vtrace(
    log_rhos,
    discounts,
    rewards,
    values,
    bootstrap_value,
    clip_rho_threshold,
    clip_pg_rho_threshold,
):
    """Literal implementation of IMPALA Eq. 1 with explicit python loops.

    vs = V(x_s) + sum_{t=s}^{T-1} (prod_{i=s}^{t-1} discount_i c_i) delta_t V
    """
    T = log_rhos.shape[0]
    rhos = np.exp(log_rhos)
    clipped_rhos = (
        np.minimum(rhos, clip_rho_threshold)
        if clip_rho_threshold is not None
        else rhos
    )
    cs = np.minimum(rhos, 1.0)
    values_tp1 = np.concatenate([values[1:], bootstrap_value[None]], axis=0)
    deltas = clipped_rhos * (rewards + discounts * values_tp1 - values)

    vs = np.array(values, dtype=np.float64)
    for s in range(T):
        for t in range(s, T):
            coeff = np.ones_like(bootstrap_value, dtype=np.float64)
            for i in range(s, t):
                coeff = coeff * discounts[i] * cs[i]
            vs[s] = vs[s] + coeff * deltas[t]

    vs_tp1 = np.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    clipped_pg_rhos = (
        np.minimum(rhos, clip_pg_rho_threshold)
        if clip_pg_rho_threshold is not None
        else rhos
    )
    pg_advantages = clipped_pg_rhos * (rewards + discounts * vs_tp1 - values)
    return vs, pg_advantages


def _random_inputs(rng, shape, log_rho_range=(-2.5, 2.5)):
    T = shape[0]
    log_rhos = rng.uniform(*log_rho_range, size=shape)
    discounts = (rng.random(shape) > 0.1) * 0.9  # some zeros: episode ends
    rewards = rng.standard_normal(shape)
    values = rng.standard_normal(shape) * 2
    bootstrap_value = rng.standard_normal(shape[1:]) * 2
    return dict(
        log_rhos=log_rhos,
        discounts=discounts,
        rewards=rewards,
        values=values,
        bootstrap_value=bootstrap_value,
    )


@pytest.mark.parametrize("scan_impl", ["sequential", "associative"])
@pytest.mark.parametrize("shape", [(5, 4), (8, 2), (1, 1)])
@pytest.mark.parametrize(
    "clip_rho,clip_pg_rho", [(1.0, 1.0), (3.7, 2.2), (None, None)]
)
def test_from_importance_weights_matches_ground_truth(
    shape, clip_rho, clip_pg_rho, scan_impl
):
    rng = np.random.default_rng(42)
    inputs = _random_inputs(rng, shape)
    gt_vs, gt_pg = ground_truth_vtrace(
        **inputs, clip_rho_threshold=clip_rho, clip_pg_rho_threshold=clip_pg_rho
    )
    out = vtrace.from_importance_weights(
        **{k: jnp.asarray(v) for k, v in inputs.items()},
        clip_rho_threshold=clip_rho,
        clip_pg_rho_threshold=clip_pg_rho,
        scan_impl=scan_impl,
    )
    np.testing.assert_allclose(out.vs, gt_vs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out.pg_advantages, gt_pg, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["associative"])
@pytest.mark.parametrize("t", [1, 80, 4000])
@pytest.mark.parametrize(
    "clip_rho,clip_pg_rho", [(1.0, 1.0), (3.7, 2.2), (None, None)]
)
def test_scan_impl_parity_matrix(impl, t, clip_rho, clip_pg_rho):
    """The default-path promotion contract (ISSUE 8): every scan impl
    agrees with the sequential reference across unroll lengths (T=1
    edge, the T=80 flagship, the 4000-shaped long-context case) and
    every clip setting. f32 inputs: float-reassociation tolerance only
    (1e-4 at T=4000 where products of thousands of terms reassociate;
    1e-5 below)."""
    rng = np.random.default_rng(11 + t)
    b = 2 if t == 4000 else 4
    inputs = _random_inputs(rng, (t, b))
    inputs = {k: jnp.asarray(v, jnp.float32) for k, v in inputs.items()}
    seq = vtrace.from_importance_weights(
        **inputs, clip_rho_threshold=clip_rho,
        clip_pg_rho_threshold=clip_pg_rho, scan_impl="sequential",
    )
    out = vtrace.from_importance_weights(
        **inputs, clip_rho_threshold=clip_rho,
        clip_pg_rho_threshold=clip_pg_rho, scan_impl=impl,
    )
    tol = 1e-4 if t == 4000 else 1e-5
    np.testing.assert_allclose(out.vs, seq.vs, rtol=tol, atol=tol)
    np.testing.assert_allclose(
        out.pg_advantages, seq.pg_advantages, rtol=tol, atol=tol
    )


@pytest.mark.parametrize("impl", ["sequential", "associative"])
def test_bf16_inputs_upcast_to_documented_tolerance(impl):
    """bf16-stored batch leaves reach V-trace half-width and are upcast
    on entry (the f32-accumulate contract): every impl must land within
    bf16's input-rounding tolerance (~2^-8 relative, documented in the
    README precision table) of the all-f32 sequential solve — and all
    impls must agree with each other far TIGHTER, since they share the
    same upcast inputs."""
    rng = np.random.default_rng(5)
    inputs = _random_inputs(rng, (40, 4))
    f32 = {k: jnp.asarray(v, jnp.float32) for k, v in inputs.items()}
    b16 = {k: v.astype(jnp.bfloat16) for k, v in f32.items()}
    ref = vtrace.from_importance_weights(**f32, scan_impl="sequential")
    out = vtrace.from_importance_weights(**b16, scan_impl=impl)
    assert out.vs.dtype == jnp.float32  # upcast-on-entry contract
    np.testing.assert_allclose(out.vs, ref.vs, rtol=2e-2, atol=5e-2)
    seq_b16 = vtrace.from_importance_weights(
        **b16, scan_impl="sequential"
    )
    np.testing.assert_allclose(
        out.vs, seq_b16.vs, rtol=1e-5, atol=1e-5
    )


def test_associative_scan_matches_sequential_long_t():
    """The log-depth associative solve must agree with the sequential
    scan well past the reference's unrolls (T=1024 — long-context
    shape) to float reassociation tolerance, under jit."""
    rng = np.random.default_rng(7)
    inputs = {
        k: jnp.asarray(v)
        for k, v in _random_inputs(rng, (1024, 2)).items()
    }
    seq_fn = jax.jit(
        lambda: vtrace.from_importance_weights(
            **inputs, scan_impl="sequential"
        )
    )
    seq = seq_fn()
    ass_fn = jax.jit(
        lambda: vtrace.from_importance_weights(
            **inputs, scan_impl="associative"
        )
    )
    ass = ass_fn()
    np.testing.assert_allclose(ass.vs, seq.vs, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        ass.pg_advantages, seq.pg_advantages, rtol=2e-5, atol=2e-5
    )


def test_bad_scan_impl_rejected():
    import pytest as _pytest

    rng = np.random.default_rng(0)
    inputs = {
        k: jnp.asarray(v) for k, v in _random_inputs(rng, (3, 2)).items()
    }
    with _pytest.raises(ValueError, match="scan_impl"):
        vtrace.from_importance_weights(**inputs, scan_impl="nope")


def test_higher_rank_inputs():
    # Reference supports arbitrary trailing dims (tests/vtrace_test.py:229-241).
    rng = np.random.default_rng(0)
    inputs = _random_inputs(rng, (6, 3, 2))
    gt_vs, gt_pg = ground_truth_vtrace(
        **inputs, clip_rho_threshold=1.0, clip_pg_rho_threshold=1.0
    )
    out = vtrace.from_importance_weights(
        **{k: jnp.asarray(v) for k, v in inputs.items()}
    )
    np.testing.assert_allclose(out.vs, gt_vs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out.pg_advantages, gt_pg, rtol=1e-4, atol=1e-4)


def test_action_log_probs_matches_log_softmax():
    rng = np.random.default_rng(1)
    logits = rng.standard_normal((5, 4, 7)).astype(np.float32)
    actions = rng.integers(0, 7, size=(5, 4))
    out = vtrace.action_log_probs(jnp.asarray(logits), jnp.asarray(actions))
    log_softmax = logits - np.log(
        np.exp(logits).sum(-1, keepdims=True)
    )
    expected = np.take_along_axis(log_softmax, actions[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-5)


def test_from_logits_log_rhos():
    rng = np.random.default_rng(2)
    T, B, A = 5, 3, 6
    behavior = jnp.asarray(rng.standard_normal((T, B, A)).astype(np.float32))
    target = jnp.asarray(rng.standard_normal((T, B, A)).astype(np.float32))
    actions = jnp.asarray(rng.integers(0, A, size=(T, B)))
    discounts = jnp.full((T, B), 0.9)
    rewards = jnp.asarray(rng.standard_normal((T, B)).astype(np.float32))
    values = jnp.asarray(rng.standard_normal((T, B)).astype(np.float32))
    bootstrap = jnp.asarray(rng.standard_normal((B,)).astype(np.float32))

    out = vtrace.from_logits(
        behavior, target, actions, discounts, rewards, values, bootstrap
    )
    expected_log_rhos = vtrace.action_log_probs(
        target, actions
    ) - vtrace.action_log_probs(behavior, actions)
    np.testing.assert_allclose(out.log_rhos, expected_log_rhos, rtol=1e-5)

    # Consistency with the from_importance_weights path.
    direct = vtrace.from_importance_weights(
        expected_log_rhos, discounts, rewards, values, bootstrap
    )
    np.testing.assert_allclose(out.vs, direct.vs, rtol=1e-6)


def test_outputs_carry_no_gradient():
    # Reference wraps everything in no_grad (vtrace.py:91-102); here the
    # outputs are stop_gradient'ed: grads w.r.t. values must come only from
    # direct use, not through vs.
    def fn(values):
        out = vtrace.from_importance_weights(
            log_rhos=jnp.zeros((4, 2)),
            discounts=jnp.full((4, 2), 0.9),
            rewards=jnp.ones((4, 2)),
            values=values,
            bootstrap_value=jnp.zeros((2,)),
        )
        return jnp.sum(out.vs) + jnp.sum(out.pg_advantages)

    grads = jax.grad(fn)(jnp.ones((4, 2)))
    np.testing.assert_allclose(grads, np.zeros((4, 2)))


def test_shape_mismatch_raises():
    # Reference parity (tests/vtrace_test.py:243-260): inconsistent
    # time/batch shapes must fail loudly, not broadcast silently.
    with pytest.raises((ValueError, TypeError), match="[Ss]hape|broadcast"):
        vtrace.from_importance_weights(
            log_rhos=jnp.zeros((5, 4)),
            discounts=jnp.zeros((5, 4)),
            rewards=jnp.zeros((7, 4)),  # wrong T
            values=jnp.zeros((5, 4)),
            bootstrap_value=jnp.zeros((4,)),
        )


def test_jit_and_scan_compile():
    jitted = jax.jit(vtrace.from_importance_weights)
    out = jitted(
        log_rhos=jnp.zeros((80, 8)),
        discounts=jnp.full((80, 8), 0.99),
        rewards=jnp.ones((80, 8)),
        values=jnp.zeros((80, 8)),
        bootstrap_value=jnp.zeros((8,)),
    )
    assert out.vs.shape == (80, 8)
