"""ops/delta_rule.py: the delta rule's chunk-to-chunk pass as kernels,
interpreted on the CPU at shapes the kernels take (Dk = Dv = 128, chunks
of 64), against the `jax.numpy` form of models/qwen3next.py
`delta_scan` and against the step-by-step recurrence in float64:
outputs, the state handed on and every gradient, with episode ends
inside chunks and a non-zero entering state; which shapes take the
kernels; the passes a product is made of; the family's counter. And
what a chunk owes before its state enters it (W, U, Kd, A) as the
module's three cells: the op alone against the `jax.numpy` form and
float64, an episode end's exact zeros, what a rematerialised block
calls, the family's counter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from tests.test_qwen3next_delta import _recurrence, _scan_inputs
from torchbeast_tpu.models import nemotron3, qwen3next, stats as model_stats
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


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _dots(jaxpr):
    return [
        eqn for eqn in _equations(jaxpr)
        if eqn.primitive.name == "dot_general"
    ]


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


@pytest.mark.parametrize("chunk, t, cells", [(16, 17, None), (64, 65, 3)])
def test_the_family_counts_the_layers_its_kernels_ran(chunk, t, cells):
    """`delta_kernel_applications`: one period `DDDA` at the published
    128 x 128 on one key head says 3 over an unroll of 17 steps in
    chunks of 16 (a whole chunk and a padded one) and 0 for a step of
    acting; at the toy widths 0 for an unroll too. `delta_sides_in_
    kernel_applications` has no key in any of those (chunks of 16 take
    the pass's kernels alone) and says 3, a sum over the period's
    layers as the others are, over 65 steps in chunks of 64."""
    wide = dict(
        delta_key_heads=1, delta_value_heads=2, delta_key_dim=128,
        delta_value_dim=128, chunk_size=chunk, attention_interval=4,
        num_layers=4,
    )
    model, params = scaffold.build("qwen3next", **wide)
    stats = scaffold.forward_stats(model, params, scaffold.B, [(3, 0)], t=t)
    assert float(stats["delta_applications"]) == 3
    assert float(stats["delta_kernel_applications"]) == 3
    assert float(stats["delta_chunks"]) == 2
    if cells is None:
        assert "delta_sides_in_kernel_applications" not in stats
    else:
        assert float(stats["delta_sides_in_kernel_applications"]) == cells

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
    assert "delta_sides_in_kernel_applications" not in stats
    toy, toy_params = scaffold.build("qwen3next")
    stats = scaffold.forward_stats(
        toy, toy_params, scaffold.B, [], t=scaffold.FAMILIES["qwen3next"].t
    )
    assert float(stats["delta_applications"]) == 1
    assert float(stats["delta_kernel_applications"]) == 0
    assert "delta_sides_in_kernel_applications" not in stats


@pytest.mark.parametrize("shape, applies", [
    ((256, 64, 128, 128, 2), True),  # the cell's unroll
    ((65, 64, 128, 256, 2), True),
    ((256, 64, 128, 128, 1), False),  # a value head without a second
    ((256, 64, 128, 128, 4), False),
    ((32, 16, 128, 128, 2), False),  # two systems are no lane tile
    ((384, 128, 128, 128, 2), False),
    ((1, 1, 128, 128, 2), False),  # acting
    ((11, 4, 6, 5, 2), False),  # tier-1's toy widths
])
def test_which_shapes_take_the_cells(shape, applies):
    """`sides_apply` is `kernels_apply` of (steps, Q, Dk, Dv), chunks
    of half a lane tile and two value heads a key head."""
    assert delta_rule.sides_apply(*shape) is applies


SIDES_ROWS, SIDES_CHUNKS, SIDES_HEADS = 2, 2, 2
# The steps of a (row, chunk) at which an episode ends: none; inside a
# chunk, at its first step and on two steps running; the last 20 steps
# padded (beta, k, g zeros: steps that hand the state on as it is);
# every step of one chunk.
SIDES_ENDS = {
    "none": {},
    "inside-and-first": {(0, 0): [20, 21, 50], (1, 1): [0, 40]},
    "padded": {(0, 1): [7]},
    "every-step": {(1, 0): list(range(CHUNK)), (0, 1): [63]},
}


def _sides_case(ends, seed=0):
    """(q, k, v, beta, G) of two key heads with two value heads over
    two chunks, heads before steps, and the counts of ended episodes."""
    rng = np.random.default_rng(seed)
    Q, lead = CHUNK, (SIDES_ROWS, SIDES_CHUNKS, SIDES_HEADS)

    def unit(*shape):
        x = rng.standard_normal(shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    done = np.zeros((SIDES_ROWS, SIDES_CHUNKS, Q), bool)
    for (row, chunk), steps in SIDES_ENDS[ends].items():
        done[row, chunk, steps] = True
    live = np.ones((SIDES_CHUNKS, Q))
    if ends == "padded":
        live[-1, -20:] = 0.0
    g = -rng.uniform(0.0, 0.2, lead + (2, Q)) * live[:, None, None]
    return (
        unit(*lead, Q, D) * D ** -0.5,
        unit(*lead, Q, D) * live[:, None, :, None],
        rng.standard_normal(lead + (2, Q, D)),
        rng.uniform(0.05, 0.95, lead + (2, Q)) * live[:, None, None],
        np.cumsum(g, axis=-1),
    ), np.cumsum(done, axis=-1).astype(np.int32)


def _lower(k, beta, G, ends):
    """(L below the diagonal and zeros elsewhere, D) as `delta_scan`'s
    `jax.numpy` lines make them, [B, c, Hk, 2, Q, Q]."""
    decay = jnp.exp(jnp.where(
        nemotron3.reaches(ends)[:, :, None, None],
        G[..., :, None] - G[..., None, :], -jnp.inf,
    ))
    between_keys = jnp.einsum("bchid,bchjd->bchij", k, k)
    return jnp.where(
        np.tril(np.ones((CHUNK, CHUNK), bool), -1),
        beta[..., :, None] * between_keys[:, :, :, None] * decay, 0.0,
    ), decay


def _sides_in_numpy(ends):
    """The op as `delta_scan` makes it where the cells do not apply, in
    the operands' dtype."""
    def op(q, k, v, beta, G):
        lower, decay = _lower(k, beta, G, ends)
        from_start = jnp.where(
            ends[:, :, None, None] == 0, jnp.exp(G), 0.0
        )
        by_beta = qwen3next.unit_lower_inverse(lower) * beta[..., None, :]
        return (
            jnp.einsum("bchid,bchjd->bchij", q, k)[:, :, :, None] * decay,
            jnp.einsum("bchpij,bchpjv->bchpiv", by_beta, v),
            jnp.einsum(
                "bchpij,bchjd->bchpid", by_beta * from_start[..., None, :], k
            ),
        )

    return op


def _sides_in_cells(ends):
    def op(q, k, v, beta, G):
        # v as the mixer leaves it, steps before heads.
        return delta_rule.sides_before_the_state(
            q, k, v.transpose(0, 1, 4, 2, 3, 5), beta, G, ends, 3
        )

    return op


def _with_gradients(op, operands):
    """Every result and, under cotangents that are the results' sines,
    every gradient."""
    def scalar(*operands):
        results = op(*operands)
        return sum(jnp.sum(jnp.sin(3.0 * r)) for r in results), results

    run = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(len(operands))), has_aux=True
    ))
    (_, results), grads = run(*operands)
    return tuple(results) + tuple(grads)


@pytest.mark.parametrize("ends", list(SIDES_ENDS))
def test_the_cells_are_the_numpy_form_and_float64(ends):
    """The op alone, two key heads of two value heads over two chunks:
    A, U, Kd and the gradients of q, k, v, beta and G, the cells at
    three terms a side against the `jax.numpy` form at the highest and
    against the same form in float64, with ends inside a chunk, at its
    first step and at every step, and a chunk whose last steps are
    padding."""
    operands, counts = _sides_case(ends)
    counts = jnp.asarray(counts)
    single = tuple(jnp.asarray(a, jnp.float32) for a in operands)
    got = _with_gradients(_sides_in_cells(counts), single)
    with jax.default_matmul_precision("highest"):
        want = _with_gradients(_sides_in_numpy(counts), single)
    with jax.enable_x64(True):
        exact = _with_gradients(
            _sides_in_numpy(counts),
            tuple(jnp.asarray(a, jnp.float64) for a in operands),
        )
    assert len(got) == len(want) == 8
    for x, y, z in zip(got, want, exact):
        z = np.asarray(z)
        scale = float(np.max(np.abs(z)))
        assert scale > 0
        np.testing.assert_allclose(x, y, rtol=0, atol=2e-5 * scale)
        np.testing.assert_allclose(x, z, rtol=0, atol=2e-5 * scale)


def test_an_end_leaves_the_inverse_exactly_zero():
    """W of a key head's two value heads side by side, as the solve's
    cell writes it: where an episode ends between two steps L is
    exactly zero and so is W, at every level of the doubling (products
    of bfloat16 terms: sums of exact zeros); above the diagonal zeros,
    on it ones; and W (I + L) = I."""
    (_, k, _, beta, G), counts = _sides_case("inside-and-first")
    rows, chunks, Hk, Q = SIDES_ROWS, SIDES_CHUNKS, SIDES_HEADS, CHUNK
    side_by_side = (rows, chunks, Hk, 2 * Q)
    solved = np.asarray(delta_rule._solve(
        *(jnp.asarray(a, jnp.float32) for a in (
            k, np.stack(
                [beta.reshape(side_by_side), G.reshape(side_by_side)], axis=3
            ),
            np.tile(counts, (1, 1, 2))[:, :, None],
        )), terms=3, interpret=True,
    ))
    lower, _ = _lower(*(jnp.asarray(a) for a in (k, beta, G, counts)))
    strict = np.tril(np.ones((Q, Q), bool), -1)
    for row, chunk, head, side in np.ndindex(rows, chunks, Hk, 2):
        W = solved[row, chunk, head, :, side * Q : (side + 1) * Q]
        reach = counts[row, chunk][:, None] == counts[row, chunk][None, :]
        assert np.all(W[~reach] == 0.0)
        assert np.all(W[~strict & ~np.eye(Q, dtype=bool)] == 0.0)
        assert np.all(np.diag(W) == 1.0)
        np.testing.assert_allclose(
            W.astype(np.float64) @ (
                np.eye(Q) + np.asarray(lower[row, chunk, head, side])
            ), np.eye(Q), atol=1e-5,
        )
    assert not np.all(counts == 0)


def _calls(jaxpr, names):
    """The names, in order, of the jitted calls among `names`."""
    return [
        eqn.params["name"] for eqn in _equations(jaxpr)
        if eqn.primitive.name in ("jit", "pjit")
        and eqn.params["name"] in names
    ]


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "whole"])
def test_a_rematerialised_block_solves_once(kept):
    """Under the policy models/transformer.py `rematerialised` gives a
    DeltaNet block (it keeps what is named `SOLVED`), the gradient of a
    checkpointed scan calls the solve's cell ONCE: the second forward
    makes U, Kd and A again from the W that was kept (the apply's cell
    alone), and the backward is one call. Rematerialised whole, it
    solves again."""
    args, done = _inputs(128, ENDS["inside"])
    policy = (
        jax.checkpoint_policies.save_only_these_names(qwen3next.SOLVED)
        if kept else None
    )

    def loss(*args):
        o, last = jax.checkpoint(
            lambda *a: qwen3next.delta_scan(*a, done, CHUNK), policy=policy
        )(*args)
        return jnp.sum(o) + jnp.sum(last)

    calls = _calls(
        jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(6))))(*args).jaxpr,
        ("_solve", "_apply", "_sides_backward", "_forward", "_backward"),
    )
    assert calls.count("_solve") == (1 if kept else 2), calls
    assert calls.count("_apply") == 2, calls
    assert calls.count("_sides_backward") == 1, calls
    assert calls.count("_backward") == 1, calls
