"""The `qwen3next` family (models/qwen3next.py; a matrix-valued carried
state beside a window in models/transformer.py's walk; a shared expert
scaled by a token's gate in models/moe.py DroplessMoE): against the
plain reference on seeded weights (loss, gradients, new states), the
chunked delta rule against the step-by-step recurrence with episode
ends inside a chunk, the triangular solve against a plain inverse,
batch forward against stepwise acting through the carried states and
through the state table, and the shares of the routed experts adding up
to the uncut layer."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench.reference import qwen3next_policy as reference
from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import Qwen3NextNet, moe, qwen3next
from torchbeast_tpu.models.transformer import Recurrent

T, B, A = scaffold.FAMILIES["qwen3next"].t, scaffold.B, scaffold.A
# The shrunken `PUBLISHED` (tests/family_scaffold.py): one Gated DeltaNet
# layer scanned in chunks of 4 steps (the 11 steps of an unroll are two
# whole chunks and one padded) and one gated attention layer over a
# cache of 5 slots, which the unroll evicts on the way.
SMALL = scaffold.FAMILIES["qwen3next"].small
M = SMALL["memory_len"]
# On the CPU both sides compute in float32 at full precision and differ
# by the order of their sums: the chunked form's solve and its [Dk, Dk]
# matrix a chunk against 11 rank-one steps. One bfloat16 pass in the
# scan would read 1e-3 to 1e-2 here.
RTOL = ATOL = 2e-5

# Episode ends at a chunk's first step (4), at its last (7), and twice in
# a row (8, 9), in one row; the other row ends one on step 0, where the
# state the unroll starts from is dropped whole.
ENDS = [(4, 0), (7, 0), (8, 0), (9, 0), (0, 1), (5, 1)]


@pytest.mark.parametrize("ends", [ENDS[:4] + [(5, 1)], []], ids=["ends", "none"])
@pytest.mark.parametrize(
    "expert_share", [(0, 1), (1, 4), (1, 8)],
    ids=["everything-held", "experts-1-of-4", "experts-1-of-8"],
)
def test_family_agrees_with_the_reference(expert_share, ends):
    """Logits, baseline, the states handed on, the loss and every
    gradient, from states an actor carried, with and without episode
    ends in the batch; with 4 of 16 experts held (as many as a token
    chooses, or more) 22 tokens leave no room for a rung and all the
    sorted rows are permuted, 2 of 16 see a window of one rung."""
    model, params = scaffold.build("qwen3next", expert_share=expert_share)
    state = scaffold.warm_state(model, params, seed=5)
    assert all(np.any(leaf) for leaf in jax.tree_util.tree_leaves(state))
    assert len(jax.tree_util.tree_leaves(state)) == 5
    batch = scaffold.learner_batch(7, ends, t=T)
    stats, grads, _, aux = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    # Every parameter of both layers (a mixer and a MoE block each)
    # takes a gradient.
    for block in ("block_0", "block_1", "block_2", "block_3"):
        for name, leaf in grads["params"][block].items():
            assert np.any(jax.tree_util.tree_leaves(leaf)[0]), (block, name)
    # The softmax load-balance term of both layers is in the loss.
    assert float(stats["aux_loss"]) == pytest.approx(float(aux), rel=1e-5)
    assert float(aux) > 0.001 * 2 * 0.99
    # What the layers say of themselves.
    assert float(stats["delta_applications"]) == 1
    assert float(stats["delta_chunks"]) == 3  # 11 steps in chunks of 4
    assert float(stats["delta_resets_per_row"]) == len(ends) / 2
    assert float(stats["delta_state_bytes_per_row"]) == 4 * (
        4 * 6 * 5 + 3 * (2 * 2 * 6 + 4 * 5)
    )
    assert float(stats["attention_gated_applications"]) == 1
    assert float(stats["moe_shared_applications"]) == 2
    assert float(stats["moe_assignments"]) == 2 * 3 * T * B
    assert "attention_fused_applications" not in stats  # toy widths
    if expert_share == (0, 1):
        assert "moe_held_assignments" not in stats
    else:
        assert 0 < float(stats["moe_held_assignments"]) < 2 * 3 * T * B
        # Four held are no fewer than the three chosen, and 66 sorted
        # rows are under a rung: no window.
        assert ("moe_window_rows" in stats) == (expert_share == (1, 8))


@pytest.mark.parametrize(
    "expert_share, even_router, sweeps",
    [((1, 16), False, 1), ((1, 16), True, 0), ((0, 16), True, 5)],
    ids=["as-routed", "no-row", "every-token-on-three-held-experts"],
)
def test_update_stats_say_how_far_the_window_was_swept(
    expert_share, even_router, sweeps
):
    """Four of 64 experts held under three a token (`held >= K`, the
    cell's side of `moe.window_rungs`), 352 tokens: the window is all
    1,056 sorted rows, its rungs 256, and the update's stats carry the
    rows the kernels swept and the layers that needed one rung alone,
    as the held experts' sizes imply, summed over the two MoE parts:
    one rung each as initialised; with a router of zeros every token's
    three are experts 0, 1, 2 (ties go to the first), so none with
    experts 4-7 held, and with 0-3 held all five rungs, every one of
    the 1,056 assignments computed (`moe_window_short_applications`
    0)."""
    rows = 32
    model, params = scaffold.build(
        "qwen3next", expert_share=expert_share, num_experts=64
    )
    assert moe.window_rungs(T * rows, 3, 4, 64) == (256, 3 * T * rows)
    if even_router:
        inner = dict(params["params"])
        for name in ("block_1", "block_3"):
            block = dict(inner[name])
            router = jax.tree_util.tree_map(
                jnp.zeros_like, block["moe"]["router"]
            )
            block["moe"] = dict(block["moe"], router=router)
            inner[name] = block
        params = {"params": inner}
    stats = scaffold.forward_stats(model, params, rows, ENDS, T)
    held = float(stats["moe_held_assignments"]) / 2  # a layer
    if even_router:
        assert held == (3 * T * rows if sweeps else 0)
    assert sweeps == -(-held // 256)
    assert float(stats["moe_window_rows"]) == 2 * 256 * sweeps
    assert float(stats["moe_window_short_applications"]) == 2 * (sweeps <= 1)


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


def _scan_inputs(steps, ends):
    rows, Hk, Hv, Dk, Dv = 2, 2, 4, 6, 5
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


@pytest.mark.parametrize("unrolls", [0, 2], ids=["empty", "warm"])
def test_batch_forward_equals_stepwise_acting_through_the_carried_states(
    unrolls
):
    """The learner's [T, B] forward (the delta rule in chunks of 4, the
    convolution as shifted adds over the unroll, attention over [cache;
    unroll] with RoPE on a head's first 4 columns) and the actor's T=1
    forwards through the matrix state, the conv tail and the rolling
    cache of un-rotated keys (5 slots: the 11 steps evict on the way)
    give the same logits and leave the same states, across episode ends
    inside a chunk."""
    model, params = scaffold.build("qwen3next")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, ENDS, t=T)
    )


@pytest.mark.parametrize("via", ["reset", "rebuild"])
def test_stepwise_acting_through_the_state_table_equals_the_batch_forward(
    via
):
    """Three actors' slots in a `DeviceStateTable` whose rows hold BOTH
    kinds of state: the DeltaNet layer's matrix state and conv tail (S
    [4, 1, 6, 5], tail [3, 1, 44]) and the attention layer's window (k,
    v [M, 1, 2, 16], valid [M, 1]). The rows arrive in another order
    every step and episodes end on the way; every step's logits equal
    the batch forward's and the table ends with what that forward
    leaves; reset and rebuild bring back zeros of every shape."""
    model, params = scaffold.build("qwen3next")
    shapes = [
        [(4, 1, 6, 5), (3, 1, 44)], [(M, 1, 2, 16), (M, 1, 2, 16), (M, 1)],
    ]
    table = scaffold.assert_state_table_acting_equals_the_batch_forward(
        model, params,
        scaffold.inputs(4, [(3, 2), (4, 2), (1, 0)], t=6, rows=3),
        shapes=shapes,
    )
    if via == "reset":
        table.reset([1])
        assert all(
            np.any(leaf) for item in table.read_slot(0) for leaf in item
        )
    else:
        table.poison()
        table.rebuild()
    held = table.read_slot(1)
    assert [[np.shape(leaf) for leaf in item] for item in held] == shapes
    assert not any(np.any(leaf) for item in held for leaf in item)


def test_rope_turns_a_heads_first_columns_alone():
    """The attention block on one query and a cache: the same keys one
    slot OLDER give another output (their positions enter the scores),
    and with the query zero on a head's first `rotary_dim` columns they
    give the same: the other 12 columns of a head are not turned. (That
    scores depend on time differences alone is the stepwise case above,
    whose cache of un-rotated keys rolls.)"""
    block = qwen3next._GatedAttentionBlock(
        d_model=32, num_heads=4, kv_heads=2, head_dim=16, rotary_dim=4,
        rope_theta=1e7, memory_len=M, rms_norm_eps=1e-6,
    )
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (B, 1, 32))
    cache = tuple(jax.random.normal(key, (M, B, 2, 16)) for key in keys[1:3])
    older = tuple(jnp.roll(c, -1, axis=0) for c in cache)
    slots = jnp.arange(M)[None, None, :]
    seq_mask = jnp.ones((B, 1, 1), bool)
    params = block.init(
        keys[3], x, cache, jnp.ones((B, 1, M), bool), seq_mask
    )
    apply = jax.jit(block.apply)

    def both(params):
        return (
            apply(params, x, cache, jnp.broadcast_to(slots >= 1, (B, 1, M)),
                  seq_mask)[0],
            apply(params, x, older,
                  jnp.broadcast_to(slots < M - 1, (B, 1, M)), seq_mask)[0],
        )

    here, there = both(params)
    assert float(jnp.max(jnp.abs(here - there))) > 1e-4
    # The query's part of `q` (a head's [query 16 | gate 16]) zero on
    # its first 4 columns; the norm keeps zeros.
    inner = dict(params["params"])
    kernel = inner["q"]["kernel"].reshape(32, 4, 2, 16)
    kernel = kernel.at[:, :, 0, :4].set(0.0).reshape(32, -1)
    here, there = both({"params": dict(inner, q={"kernel": kernel})})
    np.testing.assert_allclose(here, there, RTOL, ATOL)


def _layer(held=None, tokens=40, seed=0, E=16, K=3):
    layer = moe.DroplessMoE(
        d_ff=8, num_experts=E, top_k=K, aux_loss_weight=0.001,
        renormalise=True, held=held, shared_width=12,
        shared_token_gate=True,
    )
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, 16))
    return layer, x, layer.init(jax.random.PRNGKey(seed + 1), x)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_expert_shares_add_up_to_the_uncut_layer(side):
    """The test that ties the share to the model: the routed parts of
    four shares of 16 experts (four held, no fewer than the three a
    token chooses: the cell's path), each with its own quarter of the
    uncut layer's expert weights, plus the shared expert UNDER ITS
    TOKEN GATE, COUNTED ONCE, add up to the uncut layer's output.
    Program (values and the gradient with respect to x) and reference."""
    E, K, tokens, shares = 16, 3, 40, 4
    _, x, params = _layer(tokens=tokens, seed=4)
    p = params["params"]
    assert sorted(p) == [
        "router", "shared_down", "shared_expert_gate", "shared_gate",
        "shared_up", "w_down", "w_gate", "w_up",
    ]
    assert p["shared_expert_gate"]["kernel"].shape == (16, 1)

    def shared(x):
        hidden = jax.nn.silu(x @ p["shared_gate"]["kernel"]) * (
            x @ p["shared_up"]["kernel"]
        )
        return jax.nn.sigmoid(x @ p["shared_expert_gate"]["kernel"]) * (
            hidden @ p["shared_down"]["kernel"]
        )

    def run(first, count, x):
        cut = dict(p, **{
            k: p[k][first : first + count]
            for k in ("w_gate", "w_up", "w_down")
        })
        if side == "program":
            held = None if count == E else (first, count)
            return _layer(held, tokens=tokens)[0].apply({"params": cut}, x)
        return reference._experts(x, cut, {
            "published_num_experts": E, "num_experts": count,
            "expert_share": [first // count, E // count],
            "num_experts_per_tok": K, "norm_topk_prob": True,
            "hidden_act": "silu", "router_aux_loss_coef": 0.001,
        })[0]

    firsts = range(0, E, E // shares)
    whole = run(0, E, x)
    parts = [run(first, E // shares, x) - shared(x) for first in firsts]
    assert all(float(jnp.max(jnp.abs(part))) > 0 for part in parts)
    np.testing.assert_allclose(sum(parts) + shared(x), whole, RTOL, ATOL)
    assert float(jnp.max(jnp.abs(parts[0] + shared(x) - whole))) > 1e-3
    assert float(
        jnp.max(jnp.abs(sum(parts) + shares * shared(x) - whole))
    ) > 1e-3
    if side == "reference":
        return
    grad_whole = jax.grad(lambda x: jnp.sum(jnp.sin(run(0, E, x))))(x)
    weight = jnp.cos(whole)
    grad_parts = sum(
        jax.grad(lambda x, f=first: jnp.sum(
            weight * (run(f, E // shares, x) - shared(x))
        ))(x)
        for first in firsts
    ) + jax.grad(lambda x: jnp.sum(weight * shared(x)))(x)
    np.testing.assert_allclose(grad_parts, grad_whole, rtol=1e-4, atol=1e-5)


def test_the_gates_sum_to_one_and_the_shared_expert_has_a_gate_a_token():
    """One token, by hand: 10-of-512's rule at 3 of 16. The gates are
    the chosen softmax probabilities over their sum; the shared SwiGLU
    is scaled by sigmoid(w_g . u), one number a token."""
    layer, x, params = _layer(tokens=1, seed=3)
    p = params["params"]
    u = x[0]
    probs = jax.nn.softmax(u @ p["router"]["kernel"])
    chosen = np.argsort(-np.asarray(probs))[:3]
    gates = probs[chosen] / jnp.sum(probs[chosen])
    assert float(jnp.sum(gates)) == pytest.approx(1.0, rel=1e-6)

    def swiglu(gate, up, down):
        return (jax.nn.silu(u @ gate) * (u @ up)) @ down

    routed = sum(
        g * swiglu(p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        for g, e in zip(gates, chosen)
    )
    token_gate = jax.nn.sigmoid(u @ p["shared_expert_gate"]["kernel"])
    assert token_gate.shape == (1,) and 0.05 < float(token_gate[0]) < 0.95
    want = routed + token_gate * swiglu(
        p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"],
    )
    np.testing.assert_allclose(layer.apply(params, x)[0], want, RTOL, ATOL)


def test_layers_follow_the_interval_and_the_state_holds_what_they_carry():
    model, params = scaffold.build("qwen3next")
    carried = Recurrent(((4, 6, 5), (3, 2 * 2 * 6 + 4 * 5)))
    # A layer is its mixer's entry, then its MoE part's (nothing).
    assert model.layer_caches() == (carried, None, (M, 2, 16), None)
    state = model.initial_state(3)
    assert [[leaf.shape for leaf in item] for item in state] == [
        [(4, 3, 6, 5), (3, 3, 44)], [(M, 3, 2, 16), (M, 3, 2, 16), (M, 3)],
    ]
    blocks = params["params"]
    assert sorted(blocks["block_0"]) == [
        "A_log", "conv_kernel", "dt_bias", "gate_norm", "in_proj_ba",
        "in_proj_qkvz", "norm", "out_proj",
    ]
    assert sorted(blocks["block_2"]) == [
        "k", "k_norm", "norm", "o", "q", "q_norm", "v",
    ]
    assert sorted(blocks["block_1"]) == sorted(blocks["block_3"]) == [
        "moe", "norm",
    ]
    assert sorted(blocks["block_1"]["moe"]) == [
        "router", "shared_down", "shared_expert_gate", "shared_gate",
        "shared_up", "w_down", "w_gate", "w_up",
    ]
    # q, k for 2 key heads of 6; v, z for 4 value heads of 5; b, a.
    assert blocks["block_0"]["in_proj_qkvz"]["kernel"].shape == (
        32, 2 * 2 * 6 + 2 * 4 * 5
    )
    assert blocks["block_0"]["in_proj_ba"]["kernel"].shape == (32, 8)
    assert blocks["block_0"]["conv_kernel"].shape == (4, 44)  # no bias
    assert blocks["block_0"]["gate_norm"].shape == (5,)  # one for all heads
    # The query and its gate side by side, a head.
    assert blocks["block_2"]["q"]["kernel"].shape == (32, 4 * 2 * 16)
    assert blocks["block_2"]["q_norm"]["scale"].shape == (16,)
    # As initialised (the scaffold perturbs the norms): zero-centred
    # scales at zero, the gated norm at one; A in (0, 16), softplus(dt_
    # bias) in [0.001, 0.1].
    fresh = scaffold.init_params(model, scaffold.inputs(0, t=T))["params"]
    assert not np.any(fresh["block_0"]["norm"]["scale"])
    assert not np.any(fresh["final_norm"]["scale"])
    assert np.all(np.asarray(fresh["block_0"]["gate_norm"]) == 1)
    assert np.all(np.exp(fresh["block_0"]["A_log"]) <= 16)
    step = jax.nn.softplus(fresh["block_0"]["dt_bias"])
    assert np.all(step >= 0.001 - 1e-6) and np.all(step <= 0.1 + 1e-6)
    # The published interval: three DeltaNet layers, then attention.
    whole = Qwen3NextNet(
        num_actions=A, **dict(SMALL, attention_interval=4, num_layers=8)
    )
    assert [type(entry) for entry in whole.layer_caches()[::2]] == [
        Recurrent, Recurrent, Recurrent, tuple,
    ] * 2
    assert whole.layer_caches()[1::2] == (None,) * 8
    assert len(whole.initial_state(1)) == 8
    with pytest.raises(ValueError, match="whole periods of 4"):
        Qwen3NextNet(
            num_actions=A, **dict(SMALL, attention_interval=4, num_layers=6)
        )


def test_the_new_scopes_are_in_the_lowered_update():
    model, params = scaffold.build("qwen3next")
    batch = scaffold.learner_batch(1, ENDS, t=T)
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    text = jax.jit(jax.grad(
        lambda p: learner_lib.compute_loss(
            model, p, batch, model.initial_state(B), hp
        )[0]
    )).lower(params).as_text(debug_info=True)
    for scope in (
        "deltanet_in_proj", "deltanet_conv", "delta_scan/delta_intra",
        "delta_scan/delta_intra/delta_solve", "delta_scan/delta_states",
        "delta_scan/delta_inter", "deltanet_gate_norm", "deltanet_out_proj",
        "attention_full", "attention_full/attention_gate", "moe_route",
        "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
        "moe_shared/moe_shared_gate",
    ):
        assert scope in text, scope
