"""The `qwen3next` family's mechanism on its own (models/qwen3next.py):
the chunked delta rule against the step-by-step recurrence with episode
ends inside a chunk, the triangular solve against a plain inverse and
its closed-form gradient against autodiff's, and that a rematerialised
block solves once. Apart from tests/test_qwen3next.py, which holds the
family against its reference: under `--dist loadfile` a file is one
worker's chain (ISSUE 49)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from tests.test_qwen3next import ATOL, B, ENDS, RTOL, T
from torchbeast_tpu.models import qwen3next


def _recurrence(q, k, v, g, beta, state, done):
    """The gated delta rule a step at a time, by its definition."""
    per = v.shape[2] // q.shape[2]

    def step(S, inputs):
        q_t, k_t, v_t, g_t, beta_t, done_t = inputs
        q_t, k_t = (jnp.repeat(a, per, axis=1) for a in (q_t, k_t))
        S = jnp.where(
            done_t[:, None, None, None], 0.0,
            jnp.exp(g_t)[..., None, None] * S,
        )
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t, u)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S, o = jax.lax.scan(step, state, jax.tree_util.tree_map(
        lambda a: jnp.swapaxes(a, 0, 1), (q, k, v, g, beta, done)
    ))
    return jnp.swapaxes(o, 0, 1), S


def _scan_inputs(steps, ends, rows=2, Hk=2, Hv=4, Dk=6, Dv=5):
    keys = jax.random.split(jax.random.PRNGKey(steps), 6)
    q = qwen3next.l2_normalise(
        jax.random.normal(keys[0], (rows, steps, Hk, Dk))
    ) * Dk ** -0.5
    k = qwen3next.l2_normalise(jax.random.normal(keys[1], (rows, steps, Hk, Dk)))
    v = jax.random.normal(keys[2], (rows, steps, Hv, Dv))
    # Decays of 0.5-1 a step, so that a state crosses chunks.
    g = -0.3 * jax.nn.softplus(jax.random.normal(keys[3], (rows, steps, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (rows, steps, Hv)))
    state = jax.random.normal(keys[5], (rows, Hv, Dk, Dv))
    done = np.zeros((rows, steps), bool)
    for step, row in ends:
        if step < steps:
            done[row, step] = True
    return (q, k, v, g, beta, state), jnp.asarray(done)


# At the published chunk of 64: a chunk's first step (64), its last
# (127), two in a row (128, 129: the first of them a chunk's first), and
# step 0 of the other row.
CHUNK_ENDS = [(64, 0), (127, 0), (128, 0), (129, 0), (0, 1), (70, 1)]


@pytest.mark.parametrize("ends", [CHUNK_ENDS, []], ids=["ends", "none"])
@pytest.mark.parametrize("steps", [1, 63, 64, 65, 200])
def test_chunked_scan_equals_the_recurrence_with_ends_inside_a_chunk(
    steps, ends
):
    """Outputs, the state handed on and the gradients (with respect to
    every input and the state the unroll starts from), in chunks of 64:
    one step, a chunk short of whole, one whole chunk, a chunk and a
    step, three chunks and a padded one."""
    args, done = _scan_inputs(steps, ends)

    def chunked(*args):
        return qwen3next.delta_scan(*args, done, 64)

    def stepwise(*args):
        return _recurrence(*args, done)

    def total(f):
        def scalar(*args):
            o, last = f(*args)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(last))

        return jax.jit(jax.value_and_grad(scalar, argnums=range(6)))

    jitted = jax.jit(chunked)
    o, last = jitted(*args)
    jitted = jax.jit(stepwise)
    want_o, want_last = jitted(*args)
    np.testing.assert_allclose(o, want_o, RTOL, ATOL)
    np.testing.assert_allclose(last, want_last, RTOL, ATOL)
    value, grads = total(chunked)(*args)
    want_value, want_grads = total(stepwise)(*args)
    assert float(value) == pytest.approx(float(want_value), rel=1e-5)
    for got, want in zip(grads, want_grads):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    if ends and steps > 1:
        # The state the unroll starts from reaches row 0 (no end at its
        # first step) and not row 1 (`done` at step 0 drops it).
        assert np.any(grads[5][0]) and not np.any(grads[5][1])
    if ends and steps > 64:
        # A scan that did not reset is another function.
        free, _ = qwen3next.delta_scan(*args, jnp.zeros_like(done), 64)
        assert float(jnp.max(jnp.abs(free - want_o))) > 1e-2


@pytest.mark.parametrize("size", [1, 2, 5, 64])
def test_the_solve_is_the_inverse_and_keeps_exact_zeros(size):
    """`unit_lower_inverse` against numpy's inverse, at sizes that are
    and are not powers of two, entries up to 1 (aligned keys, beta 1);
    a system that is block diagonal (an episode end between its steps:
    exact zeros in L) has an inverse that is, to the bit."""
    rng = np.random.default_rng(size)
    L = np.tril(rng.uniform(-1, 1, (3, size, size)), -1).astype(np.float32)
    cut = size // 2
    L[0, cut:, :cut] = 0.0
    jitted = jax.jit(qwen3next.unit_lower_inverse)
    got = np.asarray(jitted(jnp.asarray(L)))
    want = np.linalg.inv(np.eye(size) + L.astype(np.float64))
    np.testing.assert_allclose(
        got, want, rtol=0, atol=2e-5 * max(1.0, np.abs(want).max())
    )
    assert not np.any(got[0, cut:, :cut])
    assert not np.any(np.triu(got, 1))
    np.testing.assert_array_equal(
        np.diagonal(got, axis1=-2, axis2=-1), np.ones((3, size), np.float32)
    )


# The solve as JAX differentiates it when left alone: the block doubling
# that `unit_lower_inverse` runs forward, every level's two products
# kept and transposed. What the closed form is held to.
_doubling_by_autodiff = qwen3next._block_doubling


def _products(jaxpr, both_shaped=None):
    """The `dot_general`s of a jaxpr and of every jaxpr inside it (a
    rematerialised block's, a loop's); with `both_shaped`, those whose
    two operands both end in that shape."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and (
            both_shaped is None or all(
                v.aval.shape[-len(both_shaped):] == both_shaped
                for v in eqn.invars
            )
        ):
            found += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += _products(inner, both_shaped)
    return found


@pytest.mark.parametrize("ended", [False, True], ids=["whole", "episode-end"])
@pytest.mark.parametrize("size", [1, 2, 8, 64])
def test_the_solves_closed_form_gradient_is_the_doublings_by_autodiff(
    size, ended
):
    """`unit_lower_inverse`'s backward (-T^T T_bar T^T below the
    diagonal, T the one residual) against JAX's own of the ten
    products, on the six axes `delta_scan` hands it ([B, c, Hk, per, Q,
    Q]), to 1e-5 of the gradient's scale; with a block of exact zeros
    below the diagonal (an episode end between its steps); and an input
    that has entries ON and ABOVE the diagonal, which the solve does
    not read: the value is the same and their gradient zeros."""
    rng = np.random.default_rng(size + ended)
    shape = (2, 3, 2, 2, size, size)
    L = np.tril(rng.uniform(-1, 1, shape), -1).astype(np.float32)
    cut = size // 2
    if ended:
        L[..., cut:, :cut] = 0.0
    weight = jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    def gradient(solve):
        return jax.jit(jax.value_and_grad(
            lambda L: jnp.sum(jnp.sin(solve(L)) * weight)
        ))

    closed_form = gradient(qwen3next.unit_lower_inverse)
    value, got = closed_form(L)
    want_value, want = gradient(_doubling_by_autodiff)(L)
    assert float(value) == float(want_value)  # the same forward
    scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
    assert got.shape == shape and (size == 1 or np.any(got))
    assert not np.any(np.triu(got))
    full = L + np.triu(rng.uniform(-1, 1, shape)).astype(np.float32)
    value_full, got_full = closed_form(full)
    assert float(value_full) == float(value)
    assert not np.any(np.triu(got_full))
    np.testing.assert_array_equal(got_full, got)


def test_the_solves_backward_is_two_products_and_one_residual():
    """What pins the mechanism: the gradient of a scalar of the solve
    on a [64, 64] system is 12 `dot_general`s (the ten of the forward
    and the closed form's two; JAX's of the doubling 30), all at the
    highest precision, and the forward alone the doubling's ten."""
    L = jnp.zeros((64, 64), jnp.float32)

    def gradient_of(solve):
        return jax.make_jaxpr(
            jax.grad(lambda L: jnp.sum(jnp.sin(solve(L))))
        )(L).jaxpr

    ours = gradient_of(qwen3next.unit_lower_inverse)
    assert _products(ours) == 12
    assert _products(gradient_of(_doubling_by_autodiff)) == 30
    assert _products(
        jax.make_jaxpr(qwen3next.unit_lower_inverse)(L).jaxpr
    ) == 10
    text = str(ours)
    assert text.count("Precision.HIGHEST") >= 12
    assert "Precision.HIGH," not in text and "DEFAULT" not in text


def test_a_rematerialised_deltanet_block_solves_once():
    """`--remat all` on the toy family (chunks of 4: one level of the
    doubling, two [4, 4] products a solve): the update's gradient holds
    the solve's forward products ONCE and the closed form's two, as the
    program without rematerialisation does: the block's second forward
    reads the inverse it kept (`delta_solved`, the one name the block's
    policy saves) and does not solve again, which would be two more;
    and the counter says what is kept, 4 bytes x rows x chunks x value
    heads x 4 x 4."""
    model, params = scaffold.build("qwen3next")
    batch = scaffold.learner_batch(1, ENDS, t=T)
    state = model.initial_state(B)

    def solves(model):
        jaxpr, (_, stats, _) = jax.make_jaxpr(
            scaffold.loss_and_grads.__wrapped__(model, jit=False),
            return_shape=True,
        )(params, batch, state)
        return _products(jaxpr.jaxpr, both_shaped=(4, 4)), stats

    plain, stats = solves(model)
    kept, _ = solves(model.clone(remat=True))
    assert plain == kept == 2 + 2
    assert "delta_solved_bytes_kept" not in stats
    stats_kept = scaffold.forward_stats(
        model.clone(remat=True), params, B, ENDS, T
    )
    assert float(stats_kept["delta_solved_bytes_kept"]) == (
        4 * B * 3 * 4 * 4 * 4
    )
