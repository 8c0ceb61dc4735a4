"""ops/delta_rule.py: the delta rule's chunk-to-chunk pass as kernels,
interpreted on the CPU at shapes the kernels take (Dk = Dv = 128, chunks
of 64), against the `jax.numpy` form of models/qwen3next.py
`delta_scan` and against the step-by-step recurrence in float64:
outputs, the state handed on and every gradient, with episode ends
inside chunks and a non-zero entering state; which shapes take the
kernels; the passes a product is made of; the family's counter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from tests.test_qwen3next_delta import _recurrence, _scan_inputs
from torchbeast_tpu.models import qwen3next, stats as model_stats
from torchbeast_tpu.ops import delta_rule

ROWS, HK, HV, D, CHUNK = 2, 1, 2, 128, 64


def _inputs(steps, ends):
    return _scan_inputs(steps, ends, ROWS, HK, HV, D, D)


# Row 0 / row 1: none; inside chunks; a chunk's first step and its last
# (and the unroll's first: the entering state dropped); every step.
ENDS = {
    "none": [],
    "inside": [(20, 0), (70, 0), (71, 0), (150, 1)],
    "first-and-last": [(64, 0), (127, 0), (0, 1), (63, 1), (128, 1)],
    "every-step": [(step, 0) for step in range(256)] + [(5, 1)],
}


def _total(scan, done):
    def scalar(*args):
        o, last = scan(*args, done)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(last)), (o, last)

    return jax.jit(jax.value_and_grad(scalar, argnums=range(6), has_aux=True))


def _chunked(*args):
    return qwen3next.delta_scan(*args, CHUNK)


def _chunked_in_hbm(monkeypatch):
    """`delta_scan` as it runs where the kernels do not apply."""
    def scan(*args):
        with monkeypatch.context() as patched:
            patched.setattr(delta_rule, "kernels_apply", lambda *shape: False)
            return qwen3next.delta_scan(*args, CHUNK)

    return scan


@pytest.mark.parametrize("ends", list(ENDS))
@pytest.mark.parametrize("steps", [64, 65, 200, 256])
def test_kernels_equal_the_chunked_form_and_the_recurrence(
    steps, ends, monkeypatch
):
    """One whole chunk, a chunk and a step (the second chunk 63 padded
    steps), three chunks and a padded one, four whole chunks. At
    `highest` (three terms a side, six passes a product) the kernels
    and the `jax.numpy` form differ by the order of their sums; both
    are held to the recurrence in float64."""
    assert delta_rule.kernels_apply(steps, CHUNK, D, D)
    args, done = _inputs(steps, ENDS[ends])
    with jax.default_matmul_precision("highest"):
        (value, (o, last)), grads = _total(_chunked, done)(*args)
        (want_value, (want_o, want_last)), want_grads = _total(
            _chunked_in_hbm(monkeypatch), done
        )(*args)
    with jax.enable_x64(True):
        wide = tuple(jnp.asarray(np.asarray(a), jnp.float64) for a in args)
        (_, (exact_o, exact_last)), exact_grads = _total(
            lambda *a: _recurrence(*a[:-1], a[-1]), jnp.asarray(done)
        )(*wide)
    assert float(value) == pytest.approx(float(want_value), rel=1e-6)
    for got, want, exact in (
        (o, want_o, exact_o), (last, want_last, exact_last),
        *zip(grads, want_grads, exact_grads),
    ):
        exact = np.asarray(exact)
        scale = max(float(np.max(np.abs(exact))), 1e-30)
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * scale)
        np.testing.assert_allclose(got, exact, rtol=0, atol=2e-5 * scale)
    # The state the unroll starts from reaches a row whose first step
    # ends no episode, and no row whose first step does.
    first = np.asarray(done)[:, 0]
    for row in range(ROWS):
        assert bool(np.any(np.asarray(grads[5][row]))) != bool(first[row])


def _dots(jaxpr):
    """The `dot_general`s of a jaxpr and of the jaxprs inside it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += _dots(inner)
    return found


@pytest.mark.parametrize(
    "precision, passes", [("high", 3), ("highest", 6), (None, 1)]
)
def test_a_product_is_the_passes_the_caller_states(precision, passes):
    """Three products a value head forward ([Kd; q] S, A V', Kl^T V');
    backward two where the states are made again and seven on the walk,
    each the passes of the precision `delta_scan` is traced under, every
    operand bfloat16 and every sum float32: no product at one pass
    under `high`, and the backward kernel, traced after the caller's
    context is left, makes the forward's."""
    args, done = _inputs(128, [])

    def loss(*args):
        o, last = qwen3next.delta_scan(*args, done, CHUNK)
        return jnp.sum(o) + jnp.sum(last)

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
    assert len(forward) == HV // HK * 3 * passes
    assert len(backward) == HV // HK * 9 * passes
    for eqn in forward + backward:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
        assert eqn.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("shape, applies", [
    ((256, 64, 128, 128), True),  # the cell's unroll
    ((65, 64, 128, 128), True),
    ((16, 16, 128, 256), True),
    ((1, 1, 128, 128), False),  # acting: a chunk of one step
    ((11, 4, 6, 5), False),  # tier-1's toy widths
    ((256, 64, 6, 128), False),
    ((256, 64, 128, 5), False),
    ((40, 40, 128, 128), False),  # a chunk that is no whole tiles
    ((64 * 1024, 64, 128, 128), False),  # more states than VMEM holds
])
def test_which_shapes_take_the_kernels(shape, applies):
    """`kernels_apply` is a function of (steps, Q, Dk, Dv) alone."""
    assert delta_rule.kernels_apply(*shape) is applies


def test_the_kernels_refuse_shapes_that_are_not_theirs():
    Q, Dk, Dv = 4, 6, 5
    with pytest.raises(ValueError, match="kernels' shapes"):
        delta_rule.chunk_pass(
            *(jnp.zeros(shape) for shape in (
                (1, 3, 1, Q, Dk), (1, 3, 1, Q, Dk), (1, 3, 1, 1, Q),
                (1, 3, 1, 1, Q), (1, 3, 1, 1, Q, Q), (1, 3, 1, 1, Q, Dv),
                (1, 3, 1, 1, Q, Dk), (1, 1, 1, Dk, Dv),
            )), 1,
        )


def test_the_family_counts_the_layers_its_kernels_ran():
    """`delta_kernel_applications`: one period `DDDA` at the published
    128 x 128 on one key head says 3 over an unroll of 17 steps in
    chunks of 16 (a whole chunk and a padded one) and 0 for a step of
    acting; at the toy widths 0 for an unroll too."""
    wide = dict(
        delta_key_heads=1, delta_value_heads=2, delta_key_dim=128,
        delta_value_dim=128, chunk_size=16, attention_interval=4,
        num_layers=4,
    )
    model, params = scaffold.build("qwen3next", **wide)
    stats = scaffold.forward_stats(model, params, scaffold.B, [(3, 0)], t=17)
    assert float(stats["delta_applications"]) == 3
    assert float(stats["delta_kernel_applications"]) == 3
    assert float(stats["delta_chunks"]) == 2

    def acting(model, params):
        jitted = jax.jit(lambda p, x, s: model.apply(
            p, x, s, mutable=model_stats.COLLECTIONS, sample_action=False
        ))
        _, sown = jitted(
            params, scaffold.inputs(1, t=1), model.initial_state(scaffold.B)
        )
        return model_stats.folded(sown)

    stats = acting(model, params)
    assert float(stats["delta_applications"]) == 3
    assert float(stats["delta_kernel_applications"]) == 0
    toy, toy_params = scaffold.build("qwen3next")
    stats = scaffold.forward_stats(
        toy, toy_params, scaffold.B, [], t=scaffold.FAMILIES["qwen3next"].t
    )
    assert float(stats["delta_applications"]) == 1
    assert float(stats["delta_kernel_applications"]) == 0
