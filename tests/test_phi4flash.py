"""The `phi4flash` family (models/phi4flash.py; values that one block
hands on and later blocks receive in models/transformer.py's walk): against the
plain reference on seeded weights (loss, gradients, new states), batch
forward against stepwise acting through the carried states and through
the state table, the selective scan's backward pass against autodiff of
the recurrence a step at a time, planted faults that the reference must
see, and what is the family's own."""

import contextlib

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import Phi4FlashNet, phi4flash
from torchbeast_tpu.models.transformer import Recurrent

T, B, A = scaffold.FAMILIES["phi4flash"].t, scaffold.B, scaffold.A
# The shrunken `PUBLISHED` (tests/family_scaffold.py): published layers
# 14-19, a sliding window of 4 keys (3 slots) and a full cache of 5,
# which the 6 steps of an unroll evict on the way; the scan one chunk of
# the `lax.scan` (its chunks' edges: `test_selective_scan_is_the_
# recurrence_forward_and_backward`).
SMALL = scaffold.FAMILIES["phi4flash"].small
M, D = SMALL["memory_len"], SMALL["d_model"]
N, INNER = SMALL["d_state"], 2 * D
RTOL = ATOL = 2e-5

# Two episode ends inside the unroll in one row; the other row ends one
# on step 0, where everything carried is dropped whole, and one on the
# last step but two.
ENDS = [(2, 0), (4, 0), (0, 1), (3, 1)]


@pytest.mark.parametrize(
    "ends", [ENDS, [(5, 0), (1, 1), (2, 1)], []],
    ids=["inside-and-zero", "last-and-adjacent", "none"],
)
@pytest.mark.parametrize("unrolls", [0, 2], ids=["empty", "warm"])
def test_family_agrees_with_the_reference(unrolls, ends):
    """Logits, baseline, the scans' states and tails and both windows
    handed on, the loss and every gradient, from empty states (the
    benchmark's check) and from states an actor carried (live scan
    states, both caches filled), with and without episode ends.
    Tolerance 2e-5: both sides compute in float32 on the CPU and differ
    in the order of their sums (a chunked scan, one grouped attention of
    wide heads against two softmaxes a pair)."""
    model, params = scaffold.build("phi4flash")
    state = scaffold.warm_state(model, params, seed=5, unrolls=unrolls)
    assert [len(item) for item in state] == [2, 3, 2, 3]
    assert bool(unrolls) == all(
        np.any(leaf) for leaf in jax.tree_util.tree_leaves(state)
    )
    batch = scaffold.learner_batch(7, ends, t=T)
    stats, grads, _, aux = scaffold.assert_agrees_with_the_reference(
        model, params, state, batch, RTOL, ATOL
    )
    assert float(aux) == 0.0 == float(stats["aux_loss"])
    # Every parameter of the six layers takes a gradient: the memory and
    # the keys and values reach their readers, and the readers' reach
    # the layers that made them.
    for block in range(6):
        for name, leaf in grads["params"][f"block_{block}"].items():
            for value in jax.tree_util.tree_leaves(leaf):
                assert np.any(value), (block, name)
    # What the layers say of themselves.
    assert float(stats["ssm_applications"]) == 2
    assert float(stats["ssm_state_bytes_per_row"]) == 2 * 4 * (N + 3) * INNER
    assert float(stats["ssm_chunks"]) == 1
    assert float(stats["ssm_resets_per_row"]) == len(ends) / 2
    assert float(stats["shared_memory_readers"]) == 1
    assert float(stats["shared_kv_readers"]) == 1
    assert float(stats["attention_differential_applications"]) == 3
    # A row hands on T steps of the scan's output and M + T keys and
    # values of 4 heads of 4.
    assert float(stats["shared_bytes_per_row"]) == 4 * (
        T * INNER + 2 * (M + T) * 4 * 4
    )
    assert "attention_fused_applications" not in stats  # toy widths


@pytest.mark.parametrize("unrolls", [0, 2], ids=["empty", "warm"])
def test_batch_forward_equals_stepwise_acting_through_the_carried_states(
    unrolls
):
    """The learner's [T, B] forward (the scan in chunks, attention over
    [cache; unroll], the memory and the keys and values handed on for
    the whole unroll) and the actor's T=1 forwards (the recurrence
    itself, one step's memory and the rolling caches handed on) give the
    same logits and leave the same states, from empty and from live
    states, across episode ends."""
    model, params = scaffold.build("phi4flash")
    state = scaffold.warm_state(model, params, seed=2, unrolls=unrolls)
    scaffold.assert_stepwise_acting_equals_the_batch_forward(
        model, params, state, scaffold.inputs(3, ENDS, t=T)
    )


@pytest.mark.parametrize("via", ["reset", "rebuild"])
def test_stepwise_acting_through_the_state_table_equals_the_batch_forward(
    via
):
    """Three actors' slots in a `DeviceStateTable` whose rows hold two
    Mamba states with their tails and two windows of different lengths,
    and NOTHING for the two layers that read another layer's values: the
    T=1 act step hands those on inside the step. The rows arrive in
    another order every step and episodes end on the way; every step's
    logits equal the batch forward's and the table ends with what that
    forward leaves; reset and rebuild bring back zeros of every shape."""
    model, params = scaffold.build("phi4flash")
    mamba = [(N, 1, INNER), (3, 1, INNER)]

    def window(slots):
        return [(slots, 1, 4, 4), (slots, 1, 4, 4), (slots, 1)]

    shapes = [mamba, window(3), mamba, window(M)]
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


def _recurrence(a, dt, A, B_in, C_in, state, done):
    """Mamba-1 a step at a time, by a Python loop over an explicit
    state: what `selective_scan` is held to, forward and backward."""
    ys = []
    for t in range(a.shape[1]):
        keep = 1.0 - done[:, t].astype(jnp.float32)
        state = (
            jnp.exp(dt[:, t, None, :] * A) * keep[:, None, None] * state
            + (dt[:, t] * a[:, t])[:, None, :] * B_in[:, t, :, None]
        )
        ys.append(jnp.einsum("bnd,bn->bd", state, C_in[:, t]))
    return jnp.stack(ys, axis=1), state


def _scan_case(steps, seed=0, rows=3, channels=8, columns=4):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(keys[0], (rows, steps, channels)),
        jax.nn.softplus(jax.random.normal(keys[1], (rows, steps, channels))),
        -jnp.exp(jax.random.normal(keys[2], (columns, channels))),
        jax.random.normal(keys[3], (rows, steps, columns)),
        jax.random.normal(keys[4], (rows, steps, columns)),
        jax.random.normal(keys[5], (rows, columns, channels)),
    )


@pytest.mark.parametrize("steps,chunk,ends", [
    (11, 4, [(0, 0), (2, 0), (4, 1), (7, 1), (8, 1), (10, 2)]),
    (8, 4, [(3, 0), (4, 0)]),
    (1, 16, []),
    (1, 16, [(0, 1)]),
    (6, 16, [(2, 0)]),
], ids=["ragged", "whole-chunks", "one-step", "one-step-ended", "one-chunk"])
def test_selective_scan_is_the_recurrence_forward_and_backward(
    monkeypatch, steps, chunk, ends
):
    """`selective_scan` (chunks rematerialised, the last padded) against
    the recurrence a step at a time: the output, the state handed on and
    the gradients of all six operands by autodiff of the loop, with
    episode ends inside a chunk, at a chunk's edge (step 4, 8), on step
    0 and on the last step; T=1 is one chunk of one step."""
    monkeypatch.setattr(phi4flash, "SCAN_CHUNK", chunk)
    operands = _scan_case(steps)
    done = np.zeros((3, steps), bool)
    for step, row in ends:
        done[row, step] = True
    done = jnp.asarray(done)

    def summed(scan):
        def loss(*operands):
            y, last = scan(*operands)
            return jnp.sum(jnp.sin(y)) + jnp.sum(last ** 2), (y, last)

        return jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True
        ))

    ((_, (y, last)), grads) = summed(
        lambda *o: phi4flash.selective_scan(*o, done)[:2]
    )(*operands)
    ((_, (want_y, want_last)), want_grads) = summed(
        lambda *o: _recurrence(*o, done)
    )(*operands)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, want_last, rtol=1e-5, atol=1e-5)
    for name, got, want in zip(
        ("a", "dt", "A", "B", "C", "state"), grads, want_grads
    ):
        np.testing.assert_allclose(
            got, want, rtol=1e-4,
            atol=1e-5 * float(jnp.max(jnp.abs(want))) + 1e-6, err_msg=name,
        )
    # A row whose unroll starts with an end reads nothing of the state.
    if (0, 0) in ends:
        assert not np.any(grads[5][0]) and np.any(grads[5][1])


def test_which_shapes_the_scans_kernels_take():
    from torchbeast_tpu.ops import selective_scan as kernels

    # Any unroll at whole blocks of 512 channels and tiles of 8 columns:
    # the cell's, and one that is no whole step blocks (padded).
    for shape in ((256, 5120, 16), (255, 5120, 16), (20, 512, 8)):
        assert kernels.kernels_apply(*shape)
    for other in ((1, 5120, 16), (256, 64, 4), (256, 512, 4)):
        assert not kernels.kernels_apply(*other)
    with pytest.raises(ValueError, match="no unroll over whole blocks"):
        kernels.selective_scan_kernels(*_scan_case(6), jnp.zeros((3, 6), bool))


@pytest.mark.parametrize("steps,pieces", [(256, (2, 16)), (130, (2, 9))])
def test_the_scans_kernels_are_the_lax_scan_forward_and_backward(
    monkeypatch, steps, pieces
):
    """Over whole blocks of 512 channels an unroll's `selective_scan` is
    ops/selective_scan.py's two kernels (interpreted here): the output,
    the state handed on and the gradients of all six operands equal the
    `lax.scan`'s, over two step blocks (the state and its cotangent
    cross the boundary in scratch) with episode ends on step 0, inside a
    block, on a block's first step (128) and on the last; 130 steps are
    padded to the two blocks with steps that pass the state on. Either
    regime says how many pieces it walked the steps in (`ssm_chunks`):
    step blocks of 128, chunks of 16."""
    from torchbeast_tpu.ops import selective_scan as kernels

    rows, channels, columns = 2, 512, 8
    a, dt, A, B_in, C_in, state = _scan_case(
        steps, seed=3, rows=rows, channels=channels, columns=columns
    )
    dt = 0.1 * dt  # decays that leave something after 128 steps
    done = np.array(
        jax.random.bernoulli(jax.random.PRNGKey(9), 0.05, (rows, steps))
    )
    done[0, 0] = done[1, 128] = done[0, steps - 1] = True
    done[1, 0] = False
    done = jnp.asarray(done)

    walked = []

    def summed(*operands):
        y, last, count = phi4flash.selective_scan(*operands, done)
        walked.append(count)
        return jnp.sum(jnp.sin(y)) + jnp.sum(last ** 2), (y, last)

    def run():  # a FRESH function: traces are cached by function
        fresh = jax.jit(jax.value_and_grad(
            lambda *operands: summed(*operands),
            argnums=tuple(range(6)), has_aux=True,
        ))
        return fresh(a, dt, A, B_in, C_in, state)

    calls = []
    by_kernels = kernels.selective_scan_kernels
    monkeypatch.setattr(
        phi4flash, "selective_scan_kernels",
        lambda *operands: calls.append(1) or by_kernels(*operands),
    )
    (_, (y, last)), grads = run()
    assert calls == [1]
    monkeypatch.setattr(phi4flash, "kernels_apply", lambda *shape: False)
    (_, (want_y, want_last)), want_grads = run()
    assert calls == [1]
    assert tuple(walked) == pieces
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, want_last, rtol=1e-5, atol=1e-5)
    for name, got, want in zip(
        ("a", "dt", "A", "B", "C", "state"), grads, want_grads
    ):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            got, want, rtol=1e-4,
            atol=1e-5 * float(jnp.max(jnp.abs(want))), err_msg=name,
        )
    # Row 0 ends an episode on step 0: nothing of its state is read.
    assert not np.any(grads[5][0]) and np.any(grads[5][1])


def test_the_scan_keeps_the_chunks_boundaries_and_not_the_unrolls_states(
    monkeypatch
):
    """Differentiated, the scan's residuals are the states at the
    chunks' boundaries ([chunks, B, N, D]) and its streamed operands; no
    value of the jaxpr is [T, B, N, D], in any order of those axes."""
    steps, chunk = 32, 4
    monkeypatch.setattr(phi4flash, "SCAN_CHUNK", chunk)
    operands = _scan_case(steps, rows=2, channels=8, columns=4)
    done = jnp.zeros((2, steps), bool)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *o: jnp.sum(phi4flash.selective_scan(*o, done)[0]),
        argnums=(0, 1, 2, 3, 4, 5),
    ))(*operands)

    def shapes(jaxpr, found):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                found.add(tuple(var.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                shapes(sub, found)
        return found

    found = shapes(jaxpr.jaxpr, set())
    whole = steps * 2 * 4 * 8
    assert not {s for s in found if int(np.prod(s)) >= whole}, found
    assert (steps // chunk, 2, 4, 8) in found  # the boundaries
    assert (chunk, 2, 4, 8) in found  # a chunk's inside, made again


# --- planted faults -----------------------------------------------------------

FAULTS = [
    None, "memory_taken_after_the_gate", "cross_layer_reads_the_sliding_keys",
    "lambda_init_by_the_cuts_index",
]


@contextlib.contextmanager
def planted(fault, monkeypatch):
    """The program with `fault` in it, for whatever is TRACED inside:
    the memory units read layer 16's scan output AFTER its gate (m
    silu(z), what `out_proj` multiplies); the cross layer reads the
    SLIDING layer's keys, values and masks (layer 15's, for layer
    17's: the windows must be equally long for the shapes to agree);
    `lambda_init` is computed from a layer's index in the CUT (0-5) and
    not from its published index (14-19)."""
    if fault == "lambda_init_by_the_cuts_index":
        right = phi4flash.lambda_init
        monkeypatch.setattr(
            phi4flash, "lambda_init", lambda index: right(index - 14)
        )
    elif fault == "cross_layer_reads_the_sliding_keys":
        right_shares = Phi4FlashNet.layer_shares

        def shares(self):
            kinds = self.kinds()
            return tuple(
                ((phi4flash.SHARED_KV,), takes) if kind == phi4flash.SLIDING
                else ((), takes) if kind == phi4flash.FULL
                else (gives, takes)
                for kind, (gives, takes) in zip(kinds, right_shares(self))
            )

        monkeypatch.setattr(Phi4FlashNet, "layer_shares", shares)
    if fault != "memory_taken_after_the_gate":
        yield
        return
    gated = []

    def gate_first(next_fun, args, kwargs, context):
        module = context.module
        if (
            module.name == "out_proj"
            and isinstance(module.parent, phi4flash._MambaBlock)
            and module.parent.hands_on
        ):
            gated.append(args[0])
        if (
            isinstance(module, phi4flash._MemoryBlock)
            and context.method_name == "__call__"
        ):
            kwargs = dict(kwargs, memory=gated[-1])
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(gate_first):
        yield


def with_louder_readers(params):
    """After some training, not as seeded: the two layers that read
    another layer's values (blocks 4 and 5 of the cut) carry a larger
    part of the residual stream than lecun-normal weights give them."""
    inner = dict(params["params"])
    for name in ("block_4", "block_5"):
        out_proj = inner[name]["out_proj"]
        inner[name] = dict(inner[name], out_proj=dict(
            out_proj, kernel=4.0 * out_proj["kernel"]
        ))
    return {"params": inner}


@pytest.mark.parametrize("fault", FAULTS)
def test_the_reference_sees_a_planted_fault(fault, monkeypatch):
    """The program as it is agrees with the reference to 2e-5 of the
    loss's scale; with each fault planted it differs by more than the
    benchmark's 5e-3 (perfbench/drivers/learner.py REFERENCE_RTOL), on
    a batch with episode ends from states an actor carried. Windows of
    equal length (5 slots both), so that the second fault has shapes."""
    model, params = scaffold.build("phi4flash", sliding_window=M + 1)
    params = with_louder_readers(params)
    state = scaffold.warm_state(model, params, seed=5, unrolls=2)
    batch = scaffold.learner_batch(7, [(2, 0), (4, 1)], t=T)
    ref_loss, scale, _ = scaffold.reference_loss_and_grads(model)(
        params, batch, state
    )
    with planted(fault, monkeypatch):
        # A trace of its own: the fault is read when the model is traced.
        loss, _, _ = scaffold.loss_and_grads.__wrapped__(model)(
            params, batch, state
        )
    rel = abs(float(loss) - float(ref_loss)) / float(scale)
    if fault is None:
        assert rel < 2e-5
    else:
        assert rel > 5e-3, rel


# --- the family's own ---------------------------------------------------------

def test_lambda_init_is_by_the_published_index():
    assert phi4flash.lambda_init(0) == pytest.approx(0.2)
    assert phi4flash.lambda_init(17) == pytest.approx(
        0.8 - 0.6 * np.exp(-5.1)
    )
    model, _ = scaffold.build("phi4flash")
    inits = {
        layer: model.make_block(f"block_{layer}", layer).lambda_init
        for layer in (1, 3, 5)
    }
    assert inits == {
        layer: phi4flash.lambda_init(14 + layer) for layer in (1, 3, 5)
    }


@pytest.mark.parametrize("num_layers,first,kinds", [
    (6, 14, "msmfgc"),
    (8, 12, "msmsmfgc"),
    (10, 12, "msmsmfgcgc"),
    (32, 0, "ms" * 8 + "mf" + "gc" * 7),
])
def test_a_cut_is_whole_pairs_around_the_stage_boundary(
    num_layers, first, kinds
):
    """`--num_layers n`: published layers 16 and 17, the pair that hands
    its values on, with ceil of half the other pairs before it and the
    rest after; 32 is the published model, 9 Mamba layers (m), 8 sliding
    (s) and one full (f) attention layer, 7 gated memory units (g) and 7
    cross layers (c)."""
    letters = {
        phi4flash.MAMBA: "m", phi4flash.SLIDING: "s", phi4flash.FULL: "f",
        phi4flash.MEMORY: "g", phi4flash.CROSS: "c",
    }
    model = Phi4FlashNet(
        num_actions=A, **dict(SMALL, num_layers=num_layers)
    )
    assert model.published_indices() == tuple(
        range(first, first + num_layers)
    )
    assert "".join(letters[kind] for kind in model.kinds()) == kinds
    carried = Recurrent(((N, INNER), (3, INNER)))
    entry = {
        "m": carried, "s": (3, 4, 4), "f": (M, 4, 4), "g": None, "c": None,
    }
    assert model.layer_caches() == tuple(entry[kind] for kind in kinds)
    shares = model.layer_shares()
    assert len(shares) == num_layers
    gives = {i: s[0] for i, s in enumerate(shares) if s[0]}
    boundary = 16 - first
    assert gives == {
        boundary: (phi4flash.SHARED_MEMORY,),
        boundary + 1: (phi4flash.SHARED_KV,),
    }
    for kind, (_, takes) in zip(kinds, shares):
        assert takes == {
            "g": (phi4flash.SHARED_MEMORY,), "c": (phi4flash.SHARED_KV,),
        }.get(kind, ())
    # The state has an item for every entry that carries.
    assert len(model.initial_state(1)) == sum(k in "msf" for k in kinds)


@pytest.mark.parametrize("num_layers", [2, 4, 5, 7, 34])
def test_a_depth_that_is_no_cut_is_refused(num_layers):
    with pytest.raises(ValueError, match="whole pairs of layers around"):
        Phi4FlashNet(num_actions=A, **dict(SMALL, num_layers=num_layers))


def test_the_published_layers_parameters_by_hand():
    """The four kinds of mixer at the published widths, counted from the
    shapes `init` would make (nothing is allocated): 41,241,600 a
    Mamba-1 mixer, 19,668,864 an attention with its own keys and values,
    26,214,400 a gated memory unit, 13,112,704 a cross attention; with
    the SwiGLU's 78,643,200 and two LayerNorms the six layers of the cut
    are 633,068,672."""
    from torchbeast_tpu.models import create_model

    model = create_model("phi4flash", num_actions=6, num_layers=6)
    rows = 1
    batch = scaffold.inputs(0, t=1, rows=rows)
    shapes = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0),
             "action": jax.random.PRNGKey(1)},
            batch, model.initial_state(rows),
        )
    )["params"]

    def count(tree):
        return sum(
            int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)
        )

    mlp = ("gate_proj", "up_proj", "down_proj", "mlp_norm", "mixer_norm")
    mixers = [
        count({k: v for k, v in shapes[f"block_{layer}"].items()
               if k not in mlp})
        for layer in range(6)
    ]
    assert mixers == [
        41_241_600, 19_668_864, 41_241_600, 19_668_864, 26_214_400,
        13_112_704,
    ]
    layers = sum(count(shapes[f"block_{layer}"]) for layer in range(6))
    assert layers == sum(mixers) + 6 * (78_643_200 + 4 * 2560)
    assert layers == 633_068_672
    # The two layers that read another's values have no key or value
    # weights and no state.
    assert "Wqkv" not in shapes["block_5"] and "Wq" in shapes["block_5"]
    assert set(shapes["block_4"]) == set(mlp) | {"in_proj", "out_proj"}
    state = jax.eval_shape(lambda: model.initial_state(2))
    assert [[leaf.shape for leaf in item] for item in state] == [
        [(16, 2, 5120), (3, 2, 5120)],
        [(511, 2, 20, 64), (511, 2, 20, 64), (511, 2)],
        [(16, 2, 5120), (3, 2, 5120)],
        [(4095, 2, 20, 64), (4095, 2, 20, 64), (4095, 2)],
    ]


def test_a_row_of_the_full_cache_and_of_a_scan_state_in_bytes():
    """What ISSUE 55 states of the cell's state: 41.9 MB a row in the
    full layer's keys and values, 389,120 bytes a row and Mamba layer."""
    assert 2 * 4095 * 20 * 64 * 4 == 41_932_800
    assert 4 * (16 * 5120 + 3 * 5120) == 389_120


def test_the_new_scopes_are_in_the_lowered_update():
    """Every scope the family enters is in the update's lowered text
    (tests/test_learner_scopes.py has them in the compiled program, the
    difference inside each of the three attentions')."""
    import optax

    model, params = scaffold.build("phi4flash")
    batch = scaffold.learner_batch(1, [(2, 0)], t=T)
    hp = learner_lib.HParams(batch_size=B, unroll_length=T - 1)
    optimizer = optax.sgd(0.1)
    text = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    ).lower(
        params, optimizer.init(params), batch, model.initial_state(B)
    ).as_text(debug_info=True)
    for scope in (
        "mamba1_in_proj", "mamba1_conv", "mamba1_x_proj", "selective_scan",
        "mamba1_out_proj", "attention_sliding", "attention_full",
        "attention_cross", "attention_difference", "memory_unit", "mlp",
    ):
        assert f"/{scope}/" in text, scope
