"""The window of a share of the experts (models/moe.py `dropless_
experts`, `_window_dispatch` / `_window_combine`, `window_rungs`,
`window_sweeps`): with fewer experts held than a token chooses the rows
move tokens x held at a time; the window is swept a rung at a time as
far as its live rows reach, where fewer are held than chosen, where as
many are, and at a quarter share; what each program moves, in the text
XLA compiles. The layers themselves: tests/test_moe.py."""

import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import family_scaffold as scaffold
from tests.test_moe import D, E, FF, _latent_by_hand
from torchbeast_tpu.models import stats


def _routed_to(kernel, x, first, column, sign):
    """The router's kernel with held expert `first + column` moved to
    every token's top (sign +1) or bottom (-1): x is positive there."""
    assert float(jnp.min(x)) > 0
    return kernel.at[:, first + column].set(sign * 3.0)


@pytest.mark.parametrize(
    "first, routing",
    [(0, None), (3, None), (6, None), (3, "both"), (6, "never")],
    ids=["first", "middle", "last", "window-full", "one-never-chosen"],
)
def test_fewer_experts_held_than_chosen_see_a_window_of_the_rows(
    first, routing
):
    """Two of eight experts held under five a token: a token lands on
    each held expert at most once, so the grouped matmuls see a window
    of tokens x 2 of the tokens x 5 sorted rows (PR 42: 8 of 512 held
    under 22 a token would else push 90,112 rows of which 1,408 are
    the chip's through every kernel). Same values and gradients as the
    experts by hand, wherever the window lies, the sorted rows' end
    among it; with every token on BOTH held experts (the window full)
    and with a held expert that no token chooses (no row in its column
    of the slots). The rows the kernels are handed are the window's,
    and (PR 43) so is every row that is moved: no array of tokens x 5
    rows, nor of those and the window's, forward or backward, and no
    scatter-add of rows."""
    from torchbeast_tpu.models.moe import DroplessMoE

    tokens, experts, top_k = 24, 8, 5
    x = jax.random.normal(jax.random.PRNGKey(first), (tokens, D))
    if routing:
        x = jnp.abs(x) + 0.5
    layer = DroplessMoE(
        d_ff=FF, num_experts=experts, top_k=top_k, aux_loss_weight=0.0,
        renormalise=True, scoring="sigmoid", routed_scaling=5.0,
        shared_width=6, gated=False, activation="relu2", latent_width=5,
        held=(first, 2),
    )
    params = scaffold.init(layer, jax.random.PRNGKey(1), x)
    if routing:
        kernel = params["params"]["router"]["kernel"]
        sign = -1 if routing == "never" else 1
        kernel = _routed_to(kernel, x, first, 1, sign)
        if routing == "both":
            kernel = _routed_to(kernel, x, first, 0, 1)
        params = {"params": dict(
            params["params"], router={"kernel": kernel}
        )}
    y, sown = scaffold.apply(layer, mutable=stats.COLLECTIONS)(params, x)
    want = _latent_by_hand(x, params["params"], top_k, 5.0, held=(first, 2))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    assert float(stats.folded(sown)["moe_assignments"]) == tokens * top_k
    held_rows = float(stats.folded(sown)["moe_held_assignments"])
    assert 0 < held_rows <= tokens * 2
    _, chosen = jax.lax.top_k(
        jax.nn.sigmoid(x @ params["params"]["router"]["kernel"]), top_k
    )
    if routing == "both":
        assert held_rows == tokens * 2
    if routing == "never":
        assert not np.any(np.asarray(chosen) == first + 1)
        assert held_rows == np.sum(np.asarray(chosen) == first)

    def by_rows(params, x):
        return jnp.sum(jnp.sin(layer.apply(params, x)))

    def by_hand(params, x):
        p = params["params"]
        scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
        _, chosen = jax.lax.top_k(scores, top_k)
        mask = jax.nn.one_hot(chosen, experts).sum(axis=1)
        gates = 5.0 * scores * mask / jnp.sum(
            scores * mask, axis=-1, keepdims=True
        )
        latent = x @ p["latent_down"]["kernel"]
        routed = sum(
            gates[:, first + e : first + e + 1] * (
                jnp.square(jax.nn.relu(latent @ p["w_up"][e])) @ p["w_down"][e]
            )
            for e in range(2)
        )
        return jnp.sum(jnp.sin(
            routed @ p["latent_up"]["kernel"] + jnp.square(
                jax.nn.relu(x @ p["shared_up"]["kernel"])
            ) @ p["shared_down"]["kernel"]
        ))

    got_fn = jax.jit(jax.grad(by_rows, argnums=(0, 1)))
    got = got_fn(params, x)
    ref_fn = jax.jit(jax.grad(by_hand, argnums=(0, 1)))
    ref = ref_fn(params, x)
    for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # The rows the kernels are handed, at 64 tokens: the window's 128,
    # not the 320 sorted rows padded to the kernels' tile.
    wide = jax.random.normal(jax.random.PRNGKey(2), (64, D))
    text = str(jax.make_jaxpr(lambda x: layer.apply(params, x))(wide))
    assert "f32[128,5]" in text and "f32[512,5]" not in text
    # Nor is any array of the latent's width 64 x 5 = 320 rows long, or
    # 320 + the window's 128, forward or backward; and no gradient of a
    # gather is left to JAX, whose scatter-add the chip serialises.
    backward = str(jax.make_jaxpr(jax.grad(by_rows, argnums=(0, 1)))(
        params, wide
    ))
    for program in (text, backward):
        assert "f32[320,5]" not in program and "f32[448,5]" not in program
        assert not re.search(r"f32\[\d+,5\] = scatter-add", program)
    assert re.search(r"f32\[\d+,8\] = scatter-add", backward)  # the router's
    whole = layer.clone(held=None)
    text = str(jax.make_jaxpr(lambda x: whole.apply(
        whole.init(jax.random.PRNGKey(1), wide), x
    ))(wide))
    assert "f32[512,5]" in text and "f32[128,5]" not in text
    assert "f32[320,5]" in text


def _window_by_hand(idx, first, held):
    """The window's geometry in numpy: (order, live, slot)."""
    tokens, K = idx.shape
    flat = np.asarray(idx).reshape(-1)
    order = np.argsort(flat, kind="stable")
    start = int(np.sum(flat < first))
    live = int(np.sum((flat >= first) & (flat < first + held)))
    window = tokens * held
    order_w = np.concatenate([order, np.zeros(window, order.dtype)])[
        start : start + window
    ]
    slot = np.full((tokens, held), window)
    for row in range(live):
        slot[order_w[row] // K, flat[order_w[row]] - first] = row
    return order_w, live, slot


def _sorted_indices(idx, experts):
    """(order, inverse, sizes) of idx [tokens, K] as `dropless_experts`
    makes them: the assignments sorted by expert, the permutation back,
    every expert's count."""
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(flat.shape[0], dtype=order.dtype)
    )
    sizes = jnp.bincount(flat, length=experts).astype(jnp.int32)
    return order, inverse, sizes


@pytest.mark.parametrize(
    "tokens, K, E, held, first",
    [(16, 3, 8, 2, 0), (16, 3, 8, 2, 3), (16, 3, 8, 2, 6),
     (12, 6, 12, 2, 5), (12, 6, 12, 1, 11), (8, 9, 12, 4, 8),
     (20, 4, 6, 3, 3)],
    ids=["start", "middle", "past-the-end", "K-far-over-held",
         "one-held-last", "top-9-of-12-past-the-end", "half-the-experts"],
)
def test_window_dispatch_and_combine_against_plain_gathers(
    tokens, K, E, held, first
):
    """`_window_dispatch` and `_window_combine` on their own, against
    the obvious formulation left to JAX's autodiff: `x[order // K]`,
    whose gradient is the scatter-add the helper avoids, and a plain
    weighted sum of the kernels' rows; values, and the gradients with
    respect to x, the kernels' output and the gates."""
    from torchbeast_tpu.models import moe

    width = 7
    keys = jax.random.split(jax.random.PRNGKey(tokens * K + first), 5)
    x = jax.random.normal(keys[0], (tokens, width))
    gate, idx = jax.lax.top_k(jax.random.uniform(keys[1], (tokens, E)), K)
    window = tokens * held
    out = jax.random.normal(keys[2], (window, width))
    weights = jax.random.normal(keys[3], (window, width))
    tangent = jax.random.normal(keys[4], (tokens, width))
    order_w, live, slot = _window_by_hand(idx, first, held)
    assert 0 < live <= window
    if first + held == E:
        assert int(np.sum(np.asarray(idx) < first)) + window > tokens * K

    def dispatch(x):
        return moe._window_dispatch(
            x, idx, *_sorted_indices(idx, E), first, held
        )

    dispatched = jax.jit(dispatch)
    rows, groups, at = dispatched(x)
    np.testing.assert_array_equal(at.order[:live], order_w[:live])
    np.testing.assert_array_equal(at.token, order_w // K)
    np.testing.assert_array_equal(at.slot, slot)
    assert int(at.live) == live
    assert int(jnp.sum(groups)) == window
    np.testing.assert_array_equal(
        groups[:held], np.bincount(np.asarray(idx).reshape(-1), minlength=E)[
            first : first + held
        ],
    )
    np.testing.assert_array_equal(rows, np.asarray(x)[order_w // K])
    # The kernels visit the held experts' rows alone: so does the loss.
    weights = weights * (jnp.arange(window) < live)[:, None]
    got_fn = jax.jit(jax.grad(lambda x: jnp.sum(dispatch(x)[0] * weights)))
    got = got_fn(x)
    want_fn = jax.jit(
        jax.grad(lambda x: jnp.sum(x[order_w // K] * weights))
    )
    want = want_fn(x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    hit = slot < window
    chose = np.asarray(idx)[:, :, None] == first + np.arange(held)

    def plain(out, gate):
        gate_held = jnp.sum(jnp.where(chose, gate[:, :, None], 0.0), axis=1)
        picked = jnp.where(
            hit[:, :, None], out[np.minimum(slot, window - 1)], 0.0
        )
        return jnp.sum(picked * gate_held[:, :, None], axis=1)

    def combine(out, gate):
        return moe._window_combine(out, gate, at)

    def traced(f, argument=None):
        """f, or its gradient against `tangent`, as one program."""
        if argument is None:
            return jax.jit(f)
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(f(*a) * tangent), argnums=argument
        ))

    np.testing.assert_allclose(
        traced(combine)(out, gate), traced(plain)(out, gate),
        rtol=1e-6, atol=1e-6,
    )
    for argument in (0, 1):
        got, want = (
            traced(f, argument)(out, gate) for f in (combine, plain)
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # Backward too, rows move by gathers alone.
    program = str(jax.make_jaxpr(jax.grad(
        lambda x, out, gate: jnp.sum(
            dispatch(x)[0] * weights
        ) + jnp.sum(combine(out, gate) * tangent),
        argnums=(0, 1, 2),
    ))(x, out, gate))
    assert "scatter-add" in program  # the bincount's, of integers
    assert not re.search(r"f32\[[\d,]*\] = scatter-add", program)


# --- a window as long as its live rows: the rungs ---------------------------

RUNG_TOKENS, RUNG_E, RUNG_FIRST = 512, 64, 7
# (K, held): fewer held than chosen (Nemotron-3's 8 under 22), and as
# many or more (Qwen3-Next's 32 under 10).
FEWER_HELD, MORE_HELD = (5, 2), (3, 4)


def _routed_with(live, seed, top_k, held, whole=0, tokens=RUNG_TOKENS,
                 experts=RUNG_E, first=RUNG_FIRST):
    """idx [tokens, K], distinct experts a token, `live` of the
    assignments on the `held` experts from `first` on; `whole`
    tokens have every one of their min(K, held) on them."""
    rng = np.random.default_rng(seed)
    others = [e for e in range(experts) if not first <= e < first + held]
    idx = np.stack([
        rng.choice(others, top_k, replace=False) for _ in range(tokens)
    ])
    slots = min(top_k, held)
    cells = [(t, c) for t in range(whole, tokens) for c in range(slots)]
    rng.shuffle(cells)
    cells = [(t, c) for t in range(whole) for c in range(slots)] + cells
    turn = rng.integers(held, size=tokens)  # which expert a rank meets
    for t, c in cells[:live]:
        idx[t, c] = first + (c + turn[t]) % held
    return jnp.asarray(idx, jnp.int32)


@functools.lru_cache(maxsize=None)
def _rung_programs(gated, held, activation):
    """(ours, plain): jitted value and gradients of the held experts'
    part of the sum by `dropless_experts` and written out, an expert at
    a time over ALL the tokens; traced once for every case."""
    from torchbeast_tpu.models import moe

    act = moe._ACTIVATIONS[activation]

    def ours(x, gate, w_gate, w_up, w_down, idx):
        return moe.dropless_experts(
            x, idx, gate, w_gate if gated else None, w_up, w_down,
            first_of=(RUNG_FIRST, RUNG_E), activation=activation,
        )[0]

    def plain(x, gate, w_gate, w_up, w_down, idx):
        y = 0.0
        for c in range(held):
            mine = jnp.sum(
                jnp.where(idx == RUNG_FIRST + c, gate, 0.0), axis=1,
                keepdims=True,
            )
            hidden = act(x @ (w_gate if gated else w_up)[c])
            if gated:
                hidden = hidden * (x @ w_up[c])
            y = y + mine * (hidden @ w_down[c])
        return y

    def program(f):
        return jax.jit(jax.value_and_grad(
            lambda tangent, idx, *a: jnp.sum(f(*a, idx) * tangent),
            argnums=(2, 3, 4, 5, 6),
        ))

    return program(ours), program(plain)


def _expert_operands(seed, tokens, top_k, held, d=8, f=16):
    """(tangent, x, gate, w_gate, w_up, w_down) of `held` seeded
    experts d -> f -> d, as `_rung_programs`' callables take them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    gate = jax.random.uniform(keys[1], (tokens, top_k))
    w_gate, w_up = (
        jax.random.normal(k, (held, d, f)) / 3 for k in keys[2:4]
    )
    w_down = jax.random.normal(keys[4], (held, f, d)) / 4
    tangent = jax.random.normal(keys[5], (tokens, d))
    return tangent, x, gate, w_gate, w_up, w_down


def _swept_against_the_experts_written_out(
    shape, live, gated, activation, whole=0
):
    """Values and the gradients of x, the gates and every weight, by
    the sweep and by the sum over the held experts written out; the
    rungs taken are those the live rows fill."""
    from torchbeast_tpu.models import moe

    top_k, held = shape
    rungs = moe.window_rungs(RUNG_TOKENS, top_k, held, RUNG_E)
    assert rungs == (256, RUNG_TOKENS * min(top_k, held))
    tangent, x, gate, w_gate, w_up, w_down = _expert_operands(
        live, RUNG_TOKENS, top_k, held
    )
    idx = _routed_with(live, live, top_k, held, whole)
    mine = jnp.bincount(idx.reshape(-1), length=RUNG_E)[
        RUNG_FIRST : RUNG_FIRST + held
    ]
    assert int(jnp.sum(mine)) == live
    assert int(moe.window_sweeps(rungs, mine)) == -(-live // 256)
    ours, plain = _rung_programs(gated, held, activation)
    (got, got_grads), (want, want_grads) = (
        program(tangent, idx, x, gate, w_gate, w_up, w_down)
        for program in (ours, plain)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip(
        ("x", "gate", "w_gate", "w_up", "w_down"), got_grads, want_grads
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    if live:
        assert np.any(got_grads[3]) and np.any(got_grads[0])
    else:
        assert not np.any(got) and not np.any(got_grads[4])
    return idx


@pytest.mark.parametrize(
    "live, gated",
    [(100, False), (256, True), (257, False), (1024, False), (0, False)],
    ids=["under-a-rung", "exactly-a-rung-gated", "one-row-over-a-rung",
         "collapsed-onto-the-held-experts", "no-row"],
)
def test_window_is_swept_as_far_as_its_live_rows_reach(live, gated):
    """Two of 64 experts held under five a token, 512 tokens: the
    window of tokens x 2 = 1,024 sorted rows is swept 256 rows at a
    time (twice an even load's 80, in row tiles), as many rungs as the
    step's own sizes fill, counted on the device; every assignment to a
    held expert is computed however many that is. Values and the
    gradients of x, the gates and every weight against the sum over the
    held experts written out, with the rows under one rung, exactly
    filling it (SwiGLU experts there), one over it (two rungs), all
    1,024 (every token on both held experts: four) and none."""
    _swept_against_the_experts_written_out(FEWER_HELD, live, gated, "relu2")


@pytest.mark.parametrize(
    "live, gated, whole",
    [(100, False, 0), (256, True, 0), (257, False, 0), (300, True, 60),
     (1536, True, 512), (0, False, 0)],
    ids=["under-a-rung", "exactly-a-rung-gated", "one-row-over-a-rung",
         "tokens-on-K-held-experts-at-once",
         "collapsed-onto-the-held-experts", "no-row"],
)
def test_window_is_swept_where_as_many_are_held_as_chosen(live, gated, whole):
    """Four of 64 experts held under THREE a token (`held >= K`:
    Qwen3-Next's 32 of 512 under 10), 512 tokens: a token may land on
    three held experts at once, so the window is all tokens x 3 = 1,536
    sorted rows and a token reads its rows back by RANK, three slots,
    not four; the sweep is the same loop, 256 rows a rung (twice an
    even load's 96). Against the held experts written out (silu, and
    SwiGLUs where gated): under a rung, exactly one, one row over, 60
    tokens with all three ranks on held experts (what `held < K` cannot
    have), every assignment on them (all six rungs: nothing is dropped
    at any load) and none."""
    idx = _swept_against_the_experts_written_out(
        MORE_HELD, live, gated, "silu", whole
    )
    on_held = (idx >= RUNG_FIRST) & (idx < RUNG_FIRST + MORE_HELD[1])
    assert int(jnp.sum(jnp.all(on_held, axis=1))) >= whole


# A quarter of the experts held, as many as a token chooses or more
# (LFM2's 8 of 32 under 4 at an eighth of its cell's tokens): twice the
# even load is half the window, 1.25 times it in whole row tiles a rung
# of 768 of 2,048.
QUARTER_TOKENS, QUARTER_K, QUARTER_HELD, QUARTER_E, QUARTER_FIRST = (
    512, 4, 8, 32, 16
)
QUARTER_RUNG = 768


@functools.lru_cache(maxsize=None)
def _quarter_programs(gated):
    """(swept, permuted): jitted value and gradients of `dropless_
    experts` at the quarter share as `window_rungs` has it, and the
    program it traced before PR 56: with no window (`window_rungs`
    answering () while THAT one is traced), every one of the tokens x K
    sorted rows permuted and the other experts' rows skipped by the
    kernels' `group_offset`."""
    from torchbeast_tpu.models import moe

    def program(rungs_rule):
        def experts(x, gate, w_gate, w_up, w_down, idx):
            with mock.patch.object(moe, "window_rungs", rungs_rule):
                return moe.dropless_experts(
                    x, idx, gate, w_gate if gated else None, w_up, w_down,
                    first_of=(QUARTER_FIRST, QUARTER_E),
                )[0]

        return jax.jit(jax.value_and_grad(
            lambda tangent, idx, *a: jnp.sum(experts(*a, idx) * tangent),
            argnums=(2, 3, 4, 5, 6),
        ))

    return program(moe.window_rungs), program(lambda *shape: ())


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "two-matrix"])
@pytest.mark.parametrize(
    "live, rungs_swept",
    [(None, 1), (768, 1), (769, 2), (1400, 2), (2048, 3), (0, 0)],
    ids=["near-even-routing", "exactly-a-rung", "one-row-over-a-rung",
         "pushed-onto-the-held-two-rungs", "every-rung", "no-row"],
)
def test_a_quarter_share_is_swept_and_is_the_full_permute(
    live, rungs_swept, gated
):
    """PR 56: 8 of 32 experts held under 4 a token, 512 tokens. The
    window is all 2,048 sorted rows and is swept 768 at a time; value
    and the gradients of x, the gates and every weight (five with the
    SwiGLU's `w_gate`, four without) equal the full permute's, the
    program these shapes traced before: at a routing drawn as a router
    draws it (top 4 of uniform scores: about 512 rows, one rung), at
    exactly a rung and one row over, pushed onto the held experts (two
    rungs), with EVERY assignment on them (all three rungs: nothing is
    dropped at any load) and with none."""
    from torchbeast_tpu.models import moe

    tokens, top_k, held = QUARTER_TOKENS, QUARTER_K, QUARTER_HELD
    rungs = moe.window_rungs(tokens, top_k, held, QUARTER_E)
    assert rungs == (QUARTER_RUNG, tokens * top_k)
    tangent, x, gate, w_gate, w_up, w_down = _expert_operands(
        live or 7, tokens, top_k, held
    )
    if live is None:
        _, idx = jax.lax.top_k(jax.random.uniform(
            jax.random.PRNGKey(56), (tokens, QUARTER_E)
        ), top_k)
    else:
        idx = _routed_with(
            live, live, top_k, held, tokens=tokens, experts=QUARTER_E,
            first=QUARTER_FIRST,
        )
    mine = jnp.bincount(idx.reshape(-1), length=QUARTER_E)[
        QUARTER_FIRST : QUARTER_FIRST + held
    ]
    if live is not None:
        assert int(jnp.sum(mine)) == live
    assert int(moe.window_sweeps(rungs, mine)) == rungs_swept
    swept, permuted = _quarter_programs(gated)
    (got, got_grads), (want, want_grads) = (
        program(tangent, idx, x, gate, w_gate, w_up, w_down)
        for program in (swept, permuted)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    names = ("x", "gate", "w_gate", "w_up", "w_down")
    for name, a, b in zip(names, got_grads, want_grads):
        if name == "w_gate" and not gated:
            assert not np.any(a) and not np.any(b)
            continue
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
        assert np.any(b) == bool(rungs_swept), name


def test_a_quarter_share_moves_a_rung_of_rows_and_the_permute_all():
    """What each of the two programs moves at the quarter share, in
    the text XLA compiles (a `custom_vjp`'s unused forward value is
    still in the jaxpr): swept, the kernels and the activation see the
    rung's [768, .] and no array of all 2,048 sorted rows at the
    experts' hidden width exists, forward or backward; the gathers as
    long as all the sorted rows are TWO (the forward's sum and the
    dispatch's gradient; the gates' gradient reads [2048] scalars
    back), the rung's three (dispatch forward, again backward, and
    `grad[token]`), each once in the first rung and once in the loop's
    body. The full permute gathers all the rows four times and holds
    the hidden width at 2,048 rows."""
    tokens, top_k, held = QUARTER_TOKENS, QUARTER_K, QUARTER_HELD
    d, f = 8, 16
    rows, rung = tokens * top_k, QUARTER_RUNG
    operands = (
        jnp.zeros((tokens, d)), jnp.zeros((tokens, d)),
        jnp.zeros((tokens, top_k)), jnp.zeros((held, d, f)),
        jnp.zeros((held, d, f)), jnp.zeros((held, f, d)),
    )
    idx = jnp.zeros((tokens, top_k), jnp.int32)
    swept, permuted = (
        program.lower(operands[0], idx, *operands[1:]).compile().as_text()
        for program in _quarter_programs(True)
    )

    def gathered(text):
        """Elements of every f32 gather's result, most first."""
        return sorted((
            int(np.prod([int(n) for n in dims.split(",")]))
            for dims in re.findall(r"f32\[([\d,]+)\][^=\n]* gather\(", text)
        ), reverse=True)

    assert "moe_sweep)/while" in swept and "moe_sweep" not in permuted
    assert f"f32[{rung},{f}]" in swept and f"f32[{rows},{f}]" not in swept
    assert f"f32[{rows},{f}]" in permuted
    # PR 58: a rung is traced twice, the first outside the loop and the
    # loop's body (2 + 3 + 1 before it). The first rung's dispatch is ONE
    # gather for the forward pass and the backward's: outside a loop the
    # compiler sees they are the same.
    assert gathered(swept)[:11] == (
        [rows * d] * 4 + [rung * d] * 5 + [rows] * 2
    )
    assert gathered(permuted)[:4] == [rows * d] * 4
    assert gathered(permuted)[4] < rows
    for program in (swept, permuted):
        # No rows are scatter-added (the kernels' own group metadata is
        # a vector of a few floats).
        assert not re.search(r"f32\[\d+,[\d,]+\][^=\n]* scatter\(", program)


@pytest.mark.parametrize(
    "live", [14, 0, 20], ids=["some-slots-fill", "all-fill", "none-fill"]
)
def test_window_sum_gradients_against_the_einsum_form(live):
    """`_window_sum`'s backward (PR 56: one gather `g = grad[token]`,
    the gates' gradient `sum(out * g, -1)` read back by `slot` as
    scalars) against what it replaces, `einsum("tcd,td->tc", out[slot],
    grad)` on a second gather of [tokens, slots, d], and against JAX's
    own gradient of the sum written plainly (a scatter-add into `out`);
    with slots that name no row (the fill: a token that chose fewer
    held experts than it has slots), with none that does, and with
    all."""
    from torchbeast_tpu.models import moe

    tokens, slots, window, d = 12, 3, 20, 7
    rng = np.random.default_rng(live)
    cells = [(t, c) for t in range(tokens) for c in range(slots)]
    rng.shuffle(cells)
    slot = np.full((tokens, slots), window)
    token = np.zeros(window, np.int32)
    for row, (t, c) in enumerate(cells[:live]):
        slot[t, c], token[row] = row, t
    keys = jax.random.split(jax.random.PRNGKey(live), 3)
    out = jax.random.normal(keys[0], (window, d))
    gate_held = jax.random.uniform(keys[1], (tokens, slots))
    grad = jax.random.normal(keys[2], (tokens, d))
    hit = slot < window
    gate_rows = np.zeros(window, np.float32)
    gate_rows[slot[hit]] = np.asarray(gate_held)[hit]
    slot, token = jnp.asarray(slot), jnp.asarray(token)

    def ours(out, gate_held):
        return moe._window_sum(out, gate_held, token, slot, gate_rows)

    def plain(out, gate_held):
        picked = jnp.where(
            hit[:, :, None], out[jnp.minimum(slot, window - 1)], 0.0
        )
        return jnp.sum(picked * gate_held[:, :, None], axis=1)

    def pulled_back(f):
        return jax.jit(lambda out, gate_held: jax.vjp(f, out, gate_held)[1](
            grad
        ))

    got_out, got_gate = pulled_back(ours)(out, gate_held)
    want_out, want_gate = pulled_back(plain)(out, gate_held)
    replaced = jnp.einsum("tcd,td->tc", moe._rows_at(out, slot), grad)
    np.testing.assert_allclose(got_gate, replaced, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_gate, want_gate, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_out, want_out, rtol=1e-6, atol=1e-6)
    assert not np.any(np.asarray(got_gate)[~hit])
    assert not np.any(np.asarray(got_out)[live:])
    assert np.any(got_gate) == bool(live)
    # The backward gathers the window's rows of `grad` once and reads
    # [window] scalars back: no array of tokens x slots x d.
    text = str(jax.make_jaxpr(moe._window_sum_bwd)(
        (out, token, slot, gate_rows), grad
    ))
    assert f"f32[{tokens},{slots},{d}]" not in text
    assert f"f32[{tokens},{slots}] = gather" in text
    assert text.count(" = gather") == 2


@pytest.mark.parametrize(
    "tokens, top_k, held, experts, want",
    [(4096, 22, 8, 512, (2816, 32768)),  # the Nemotron-3 cell's layer
     (1024, 22, 8, 512, (768, 8192)),  # and its check's four rows
     (24, 5, 2, 8, (48, 48)),  # no room under half: one rung, as it was
     (512, 5, 2, 8, (1024, 1024)),  # an even load fills more than half
     (4096, 10, 32, 512, (5120, 40960)),  # the Qwen3-Next cell's layer
     (1024, 10, 32, 512, (1280, 10240)),  # and its check's four rows
     # A quarter of the experts, as many held as chosen: twice the
     # even load is half the window, 1.25 times it is a rung (PR 56).
     (2592, 8, 16, 64, (6656, 20736)),  # the Mellum2 cell's layer
     (2592, 6, 16, 128, (4096, 15552)), (64, 5, 5, 8, ()),
     (4096, 4, 8, 32, (5120, 16384)),  # the LFM2 cell's layer
     (1024, 4, 8, 32, (1280, 4096)),  # and its check's four rows
     (4096, 4, 16, 32, ()),  # half the experts: no room for either rung
     (4096, 8, 64, 64, ())],  # as many rows as OLMoE's, were they a share
    ids=["nemotron3-cell", "nemotron3-check", "toy", "dense-routing",
         "qwen3next-cell", "qwen3next-check", "mellum2", "kanana2",
         "held-equals-chosen", "lfm2-cell", "lfm2-check", "half-held",
         "all-held"],
)
def test_window_rungs_follow_from_shapes_alone(
    tokens, top_k, held, experts, want
):
    """The rung of every cell that holds a share, by the one rule: the
    tuples of the Qwen3-Next, Kanana-2 and Nemotron-3 cells as they
    were; since PR 56 LFM2's and Mellum2's quarter shares take a rung
    of 1.25 times the even load, where twice it left no room and all
    tokens x K rows were permuted."""
    from torchbeast_tpu.models import moe

    assert moe.window_rungs(tokens, top_k, held, experts) == want
    if want:
        rung, window = want

        swept = jax.jit(functools.partial(moe.window_sweeps, want))

        def sweeps(*mine):
            return int(swept(jnp.asarray(mine)))

        assert sweeps(rung, 0) == 1
        # Every token on as many held experts as it can choose.
        assert sweeps(window - 1, 1) == -(-window // rung)
        if rung < window:
            assert sweeps(0, 0) == 0 and sweeps(rung, 1) == 2


def test_as_many_held_as_chosen_trace_the_program_they_traced():
    """Five of eight experts held under five a token (`held >= K`) at
    64 tokens, where a rung of 256 rows is not under half the 320
    sorted rows (as Mellum2's 16 of 64 under 8 at its cell's shapes,
    and OLMoE's all): no window, no rung, no loop; the t x K sorted
    rows permuted as before PRs 44 and 47, whose jaxpr of value and
    gradients this is letter for letter (3,217 lines, 29 arrays of the
    320 sorted rows at the experts' two widths; the parent commit's
    text hashed the same)."""
    from torchbeast_tpu.models import moe

    tokens, top_k, experts, held, d, f = 64, 5, 8, 5, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    gate, idx = jax.lax.top_k(
        jax.random.uniform(keys[1], (tokens, experts)), top_k
    )
    w_up = jax.random.normal(keys[3], (held, d, f))
    w_down = jax.random.normal(keys[4], (held, f, d))

    def loss(x, gate, w_up, w_down, first_of):
        y, _ = moe.dropless_experts(
            x, idx, gate, None, w_up, w_down, first_of=first_of,
            activation="relu2",
        )
        return jnp.sum(jnp.sin(y))

    def program(first_of, w_up, w_down):
        return str(jax.make_jaxpr(jax.value_and_grad(
            functools.partial(loss, first_of=first_of), argnums=(0, 1, 2, 3)
        ))(x, gate, w_up, w_down))

    text = program((1, experts), w_up, w_down)
    assert moe.window_rungs(tokens, top_k, held, experts) == ()
    assert "while[" not in text
    assert len(text.splitlines()) == 3217
    rows = tokens * top_k
    assert text.count(f"f32[{rows},{d}]") + text.count(
        f"f32[{rows},{f}]"
    ) == 29
    # Four held under five chosen: a window, of one rung at 64 tokens.
    windowed = program((1, experts), w_up[:4], w_down[:4])
    assert f"f32[{rows},{d}]" not in windowed
    assert f"f32[{tokens * 4},{d}]" in windowed and "while[" not in windowed
