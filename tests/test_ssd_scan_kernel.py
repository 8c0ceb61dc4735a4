"""ops/ssd_scan.py: the Mamba-2 scan's chunks as kernels, interpreted on
the CPU at shapes the kernels take (heads of 64 on a state of 128
columns, chunks of 16), against the `jax.numpy` form of
models/nemotron3.py `ssd_scan` and against the step-at-a-time
recurrence in float64: y, the state handed on and every gradient, with
episode ends inside chunks and a non-zero entering state; which shapes
take the kernels; the passes a product is made of; the family's
counter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from tests.test_delta_rule_kernel import _dots
from tests.test_nemotron3 import _recurrence
from torchbeast_tpu.models import nemotron3, stats as model_stats
from torchbeast_tpu.ops import ssd_scan

ROWS, P, N, CHUNK = 2, 64, 128, 16

# (steps, heads, groups, chunk, (step, row) of each episode end).
CASES = {
    # Two whole chunks and a padded one on two groups; row 0: ends
    # inside chunks, two in consecutive steps; row 1: the unroll's first
    # step (the entering state dropped), a chunk's first and its last.
    "padded-two-groups": (
        40, 4, 2, CHUNK, [(5, 0), (21, 0), (22, 0), (0, 1), (16, 1), (31, 1)]
    ),
    # Granite's 64 heads on one group: four cells of eight lane tiles.
    "sixty-four-heads": (32, 64, 1, CHUNK, [(3, 0), (20, 1)]),
    # A chunk and a step (the second chunk 15 padded steps); an end at
    # every step of row 0.
    "every-step": (
        17, 2, 1, CHUNK, [(step, 0) for step in range(17)] + [(9, 1)]
    ),
    # One whole chunk: the backward kernel makes no state again.
    "one-chunk": (16, 2, 1, CHUNK, []),
    # Granite's chunk of 256, L made in its three lower blocks of a
    # lane tile; ends on both sides of the blocks' border.
    "chunk-of-256": (
        260, 2, 1, 256, [(100, 0), (127, 0), (128, 0), (0, 1), (200, 1)]
    ),
}


def _inputs(steps, H, G, ends, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    done = np.zeros((ROWS, steps), bool)
    for step, row in ends:
        done[row, step] = True
    return (
        jax.random.normal(keys[0], (ROWS, steps, H, P)),
        # A chunk's decay stays in float32's reach: 0.05..0.55 a step
        # over chunks of 16, the published 0.006..0.07 over one of 256.
        (0.05 + 0.5 * jax.random.uniform(keys[1], (ROWS, steps, H)))
        * min(1.0, 32 / steps),
        -jnp.exp(jax.random.normal(keys[2], (H,))),
        jax.random.normal(keys[3], (ROWS, steps, G, N)) / 4,
        jax.random.normal(keys[4], (ROWS, steps, G, N)) / 4,
        jax.random.normal(keys[5], (ROWS, H, P, N)),
    ), jnp.asarray(done)


def _total(scan, done):
    def scalar(*args):
        y, last = scan(*args, done)
        return jnp.sum(jnp.sin(y)) + jnp.sum(jnp.cos(last)), (y, last)

    return jax.jit(jax.value_and_grad(scalar, argnums=range(6), has_aux=True))


def _chunked(chunk):
    return lambda *args: nemotron3.ssd_scan(*args, chunk)


def _chunked_in_xla(chunk, monkeypatch):
    """`ssd_scan` as it runs where the kernels do not apply."""
    def scan(*args):
        with monkeypatch.context() as patched:
            patched.setattr(ssd_scan, "kernels_apply", lambda *shape: False)
            return nemotron3.ssd_scan(*args, chunk)

    return scan


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_equal_the_chunked_form_and_the_recurrence(case, monkeypatch):
    """At `highest` (three terms a side, six passes a product) the
    kernels and the `jax.numpy` form differ by the order of their sums;
    both are held to the recurrence in float64, at 1e-5 of each
    result's scale."""
    steps, H, G, chunk, ends = CASES[case]
    assert ssd_scan.kernels_apply(steps, chunk, H, P, G, N)
    args, done = _inputs(steps, H, G, ends)
    with jax.default_matmul_precision("highest"):
        (value, (y, last)), grads = _total(_chunked(chunk), done)(*args)
        (want_value, (want_y, want_last)), want_grads = _total(
            _chunked_in_xla(chunk, monkeypatch), done
        )(*args)
    with jax.enable_x64(True):
        wide = tuple(jnp.asarray(np.asarray(a), jnp.float64) for a in args)
        (_, (exact_y, exact_last)), exact_grads = _total(
            _recurrence, jnp.asarray(done)
        )(*wide)
    assert float(value) == pytest.approx(float(want_value), rel=1e-6)
    for got, want, exact in (
        (y, want_y, exact_y), (last, want_last, exact_last),
        *zip(grads, want_grads, exact_grads),
    ):
        exact = np.asarray(exact)
        scale = max(float(np.max(np.abs(exact))), 1e-30)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
        np.testing.assert_allclose(got, exact, rtol=0, atol=1e-5 * scale)
    # The state the unroll starts from reaches a row whose first step
    # ends no episode, and no row whose first step does.
    first = np.asarray(done)[:, 0]
    for row in range(ROWS):
        assert bool(np.any(np.asarray(grads[5][row]))) != bool(first[row])


@pytest.mark.parametrize(
    "precision, passes", [("high", 3), ("highest", 6), (None, 1)]
)
def test_a_product_is_the_passes_the_caller_states(precision, passes):
    """Forward: C B^T a group, and a lane tile's C S^T, (scores . L) x a
    head (two of 64 a tile) and (e . x)^T B, in the one rolled loop's
    body. Backward: (e . x)^T B where the states are made again; on the
    walk C B^T, C S^T, B dS^T, dy x^T and (scores . L)^T dy a head, (f .
    dy) S, (e . x) dS, (f . dy)^T C, and dscores B, dscores^T C a group.
    Each is the passes of the precision `ssd_scan` is traced under,
    every operand bfloat16 and every sum float32: the backward kernel,
    traced after the caller's context is left, makes the forward's."""
    args, done = _inputs(32, 2, 1, [])

    def loss(*args):
        y, last = nemotron3.ssd_scan(*args, done, CHUNK)
        return jnp.sum(y) + jnp.sum(last)

    def traced(*args):
        with jax.default_matmul_precision(precision):
            value, back = jax.vjp(loss, *args)
        return back(jnp.ones_like(value))  # outside the context

    calls = [
        eqn for eqn in jax.make_jaxpr(traced)(*args).jaxpr.eqns
        if eqn.primitive.name in ("jit", "pjit")
        and eqn.params["name"] in ("_forward", "_backward")
    ]
    assert [eqn.params["name"] for eqn in calls] == ["_forward", "_backward"]
    forward, backward = (_dots(eqn.params["jaxpr"].jaxpr) for eqn in calls)
    heads = 128 // P
    assert len(forward) == (3 + heads) * passes
    assert len(backward) == (1 + 8 + 2 * heads) * passes
    for eqn in forward + backward:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
        assert eqn.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("shape, applies", [
    ((512, 256, 64, 64, 1, 128), True),  # Granite's cell
    ((256, 128, 32, 64, 2, 128), True),  # Nemotron-3's, a quarter share
    ((256, 128, 128, 64, 8, 128), True),  # Nemotron-3's mixers whole
    ((17, 16, 2, 64, 1, 128), True),
    ((40, 16, 4, 128, 2, 256), True),
    ((1, 1, 64, 64, 1, 128), False),  # acting: a chunk of one step
    ((11, 4, 8, 8, 1, 6), False),  # tier-1's toy widths
    ((12, 4, 4, 3, 2, 5), False),
    ((512, 256, 64, 64, 1, 6), False),  # a state of no whole lane tiles
    ((512, 256, 64, 48, 1, 128), False),  # heads that fill no lane tile
    ((512, 256, 64, 256, 1, 128), False),
    ((256, 128, 8, 64, 8, 128), False),  # a group of half a lane tile
    ((40, 40, 64, 64, 1, 128), False),  # a chunk that is no whole tiles
    ((1024, 512, 64, 64, 1, 128), False),  # a chunk over 256 steps
    ((256 * 64, 256, 64, 64, 1, 128), False),  # more states than VMEM's
])
def test_which_shapes_take_the_kernels(shape, applies):
    """`kernels_apply` is a function of (steps, Q, H, P, G, N) alone."""
    assert ssd_scan.kernels_apply(*shape) is applies


def test_the_kernels_refuse_shapes_that_are_not_theirs():
    rows, steps, H, P_, G, N_ = 1, 12, 4, 3, 2, 5
    with pytest.raises(ValueError, match="kernels' shapes"):
        ssd_scan.scan(
            jnp.zeros((rows, steps, H, P_)), jnp.zeros((rows, steps, H)),
            jnp.zeros((H,)), jnp.zeros((rows, steps, G, N_)),
            jnp.zeros((rows, steps, G, N_)), jnp.zeros((rows, H, P_, N_)),
            jnp.zeros((rows, steps), bool), 4, 1,
        )


def test_the_family_counts_the_layers_its_kernels_ran():
    """`ssm_kernel_applications`: the toy period `M * M` with two heads
    of 64 on a state of 128 columns says 2 over an unroll of 17 steps in
    chunks of 16 (a whole chunk and a padded one) and 0 for a step of
    acting; at the toy widths 0 for an unroll too."""
    wide = dict(
        mamba_heads=2, mamba_head_dim=64, state_size=128, chunk_size=16
    )
    model, params = scaffold.build("granite4", **wide)
    stats = scaffold.forward_stats(model, params, scaffold.B, [(3, 0)], t=17)
    assert float(stats["ssm_applications"]) == 2
    assert float(stats["ssm_kernel_applications"]) == 2
    assert float(stats["ssm_chunks"]) == 2

    def acting(model, params):
        jitted = jax.jit(lambda p, x, s: model.apply(
            p, x, s, mutable=model_stats.COLLECTIONS, sample_action=False
        ))
        _, sown = jitted(
            params, scaffold.inputs(1, t=1), model.initial_state(scaffold.B)
        )
        return model_stats.folded(sown)

    stats = acting(model, params)
    assert float(stats["ssm_applications"]) == 2
    assert float(stats["ssm_kernel_applications"]) == 0
    toy, toy_params = scaffold.build("granite4")
    stats = scaffold.forward_stats(
        toy, toy_params, scaffold.B, [], t=scaffold.FAMILIES["granite4"].t
    )
    assert float(stats["ssm_applications"]) == 2
    assert float(stats["ssm_kernel_applications"]) == 0
