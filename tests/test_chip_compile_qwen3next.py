"""Compile for the chip, without the chip (tests/chip_fixtures.py):
`qwen3next_policy.learner`'s whole update, one AOT compile of the real
cell, and the delta rule's two kernels alone at the cell's shapes and
at others they take. A file of its own: tests/chip_fixtures.py says
why.
"""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.chip_fixtures import (  # noqa: F401 (fixtures)
    NUM_ACTIONS,
    assert_conv_kernels,
    on as _on,
    one_chip,
    struct as _struct,
    topo,
)
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import qwen3next
from torchbeast_tpu.ops import delta_rule


def _shapes(text, under=""):
    """The f32 shapes a compiled program names, on lines that hold
    `under`."""
    return {
        tuple(int(d) for d in dims.split(","))
        for line in text.splitlines() if under in line
        for dims in re.findall(r"f32\[([0-9,]+)\]", line)
    }


def _states_of_every_cell(shapes, cells):
    """Those of `shapes` that are a [128, 128] matrix a (row, chunk,
    value head) cell: `left`, `handed_on`, `entering` of the `jax.numpy`
    form, and their cotangents."""
    return {
        s for s in shapes
        if s[-2:] == (128, 128) and int(np.prod(s[:-2])) >= cells
    }


@pytest.mark.parametrize(
    "rows, steps, Hk, per, chunk, Dk, Dv, precision",
    [
        (16, 256, 16, 2, 64, 128, 128, "high"),  # the cell's
        (2, 200, 16, 2, 64, 128, 128, "highest"),  # six passes, padded
        (2, 64, 2, 2, 64, 128, 128, "high"),  # one chunk: no turn remakes
        (2, 32, 1, 2, 16, 128, 256, "high"),
        (2, 384, 3, 1, 128, 256, 128, None),
    ],
)
def test_delta_rule_kernels_lower_for_v5e(
    one_chip, monkeypatch, rows, steps, Hk, per, chunk, Dk, Dv, precision
):
    """The check interpret mode cannot make: `delta_scan` with its
    chunk-to-chunk pass in ops/delta_rule.py's kernels, value and every
    gradient, compiles for the chip's compiler at the cell's shapes
    (16 rows x 256 steps in 4 chunks of 64, 16 key heads x 2 value
    heads of 128 x 128, three passes) and at others `kernels_apply`
    admits (six passes and one; one chunk; one key head a cell; chunks
    of 16 and 128; widths of 256); each kernel is one Mosaic call and
    the program around them holds no [128, 128] matrix a (row, chunk,
    value head)."""
    Hv = Hk * per
    assert delta_rule.kernels_apply(steps, min(chunk, steps), Dk, Dv)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(q, k, v, g, beta, state, done):
        with jax.default_matmul_precision(precision):
            o, last = qwen3next.delta_scan(
                q, k, v, g, beta, state, done, chunk
            )
        return jnp.sum(o * o) + jnp.sum(last)

    traced = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6))))
    text = traced.lower(
        _struct(one_chip, (rows, steps, Hk, Dk)),
        _struct(one_chip, (rows, steps, Hk, Dk)),
        _struct(one_chip, (rows, steps, Hv, Dv)),
        _struct(one_chip, (rows, steps, Hv)),
        _struct(one_chip, (rows, steps, Hv)),
        _struct(one_chip, (rows, Hv, Dk, Dv)),
        _struct(one_chip, (rows, steps), jnp.bool_),
    ).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    # Where two systems fill a lane tile (chunks of 64, a key head's two
    # value heads) what a chunk owes before its state enters it is three
    # cells more (PR 69: W; U, Kd, A; their backward), and no product
    # of the scan is XLA's.
    sides = delta_rule.sides_apply(steps, min(chunk, steps), Dk, Dv, per)
    assert sides is (chunk == 64)
    assert len(calls) == 2 + 3 * sides, len(calls)
    for kernel, count in (
        ("delta_rule_forward", 1), ("delta_rule_backward", 1),
        ("delta_sides_solve", sides), ("delta_sides_apply", sides),
        ("delta_sides_backward", sides),
    ):
        assert sum(kernel in call for call in calls) == count, kernel
    assert (" convolution(" in text) is not sides
    chunks = -(-steps // chunk)
    if (Dk, Dv) == (128, 128) and chunks > 1:
        assert not _states_of_every_cell(
            _shapes(text), rows * chunks * Hv
        )


def test_qwen3next_cell_update_compiles_for_v5e(one_chip, monkeypatch):
    """`qwen3next_policy.learner`'s update as the benchmark builds it
    (the configuration's own argv and sizes: one period `DDDA`, experts
    0/16, blocks rematerialised, a [256, B] batch), whole, for a
    described v5e: it fits beside the driver's copy of the weights
    (under 15.0 GiB with it) and fills the chip; the attention layer's
    scores over 4,351 keys of heads of 256 live in `fused_attend`'s
    VMEM (no f32 array over the keys is in the program: the fused pass
    compiles at a head size it had never run); the three DeltaNet
    layers' matrix states [16, 32, 128, 128] are in it, and no array of
    the chunked scan is a [128, 128] matrix a chunk and head (PR 61:
    the state goes from chunk to chunk in ops/delta_rule.py's kernels,
    six forward calls and three backward). The
    experts' kernels see one rung at a time of the sorted rows (PR 47:
    5,120 rows, twice an even load's, of the 40,960 that 32 held under
    10 chosen can draw): no f32 array of 40,960 rows is left at the
    experts' hidden width, at the model's only the three gathers by
    `slot` a layer from a rung's table (the combine's sum, its gates'
    gradient, the dispatch's backward), and the sweep's loops are in
    the program. The triangular solve is 36 products at the highest
    in the whole update (PR 48): a layer's ten forward and the two of
    its closed-form backward, the rematerialised block solving no
    second time (120 when JAX differentiated the doubling). By
    `memory_analysis` 9.888 GiB, 12.144 with the copy (11.078 / 13.335
    with the solve's levels kept for autodiff; 10.347 / 12.604 before
    the sweep): it counts the backward loops' carried gradients, 3 x
    [32, 2048, 512] f32 a layer, apart from the heap they share with
    everything else by the TPU backend's own account ("Total hbm usage"
    9.95G; 10.58G before PR 48, 10.62G before the sweep), which is
    what the chip reads."""
    from perfbench import manifest
    from perfbench.drivers import learner as learner_driver
    from torchbeast_tpu import monobeast

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
        manifest.HERE, "configs", "qwen3next_80b_policy.json"
    )) as f:
        config = json.load(f)
    steps, rows = config["unroll_length"], config["batch_size"]
    flags = monobeast.make_parser().parse_args(
        config["program_argv"]
        + ["--unroll_length", str(steps), "--batch_size", str(rows)]
    )
    hp = monobeast.hparams_from_flags(flags)
    frame = tuple(config["frame_shape"])
    model, _ = monobeast._init_model_and_params(
        flags, NUM_ACTIONS, rows, frame, init_params=False
    )
    optimizer = learner_lib.make_optimizer(hp)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        monobeast.dummy_env_outputs(1, rows, frame, np.uint8),
        model.initial_state(rows),
    ))
    batch, state = jax.eval_shape(lambda: (
        learner_driver._make_batch(
            jax.random.PRNGKey(0), steps + 1, rows, NUM_ACTIONS, frame
        ),
        model.initial_state(rows),
    ))
    compiled = learner_lib.make_update_step(model, optimizer, hp).lower(
        _on(one_chip, params),
        _on(one_chip, jax.eval_shape(optimizer.init, params)),
        _on(one_chip, batch), _on(one_chip, state),
    ).compile()
    memory = compiled.memory_analysis()
    total = (
        memory.temp_size_in_bytes + memory.argument_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    # `temp_size_in_bytes` 6,472,551,424 with the sweep's loops started from
    # zeros, 6,439,727,616 with each first rung before its loop (PR 58: the
    # zeros of a part's five sums are gone), 5,954,033,664 with the delta
    # rule's states in VMEM (PR 61: 10.514 GiB in all where it was 10.967).
    weights = 4 * sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    assert weights == 4 * config["param_count"] == 4 * 605_711_431
    print("memory", memory, "total GiB", total / 2**30,
          "with the copy", (total + weights) / 2**30)
    assert total + weights < 15.0 * 2**30, memory
    assert total > 4 * 2**30, memory  # the cell fills the chip
    text = compiled.as_text()
    shapes = _shapes(text)
    scores = {
        s for s in shapes if len(s) >= 3 and s[-1] in (4095, 4351, 4352)
    }
    assert not scores, scores
    assert text.count("fused_attend_forward") >= 1
    assert text.count("fused_attend_backward") >= 1
    # The frames enter `Dense_0` as the bfloat16 integers they are (PR
    # 52: models/transformer.py `frame_projection`): no float32 copy of
    # the batch's frames anywhere in the program (the parent wrote three,
    # 462 MB each), and under `obs_embed` nothing 28,224 wide but the
    # kernel [28224, d] (its gradient, its two bf16 terms) and the bf16
    # operand: ONE cast of the uint8 frames (116 MB in, 231 out), which
    # both products read.
    frames = (steps + 1) * rows * int(np.prod(frame))
    assert not {s for s in shapes if int(np.prod(s)) >= frames}
    wide = {
        (kind, tuple(int(d) for d in dims.split(",")))
        for line in text.splitlines() if "obs_embed" in line
        for kind, dims in re.findall(
            r"= \(?(\w+)\[([0-9,]+)\]", line.split(" metadata=")[0]
        )
        if "28224" in dims.split(",")
    }
    kernel = {(28224, 2048), (28224, 2048, 1)}
    assert {s for kind, s in wide if kind == "f32"} <= kernel, wide
    assert {kind for kind, _ in wide} <= {"f32", "bf16"}, wide
    operands = [
        line for line in text[text.index("\nENTRY "):].splitlines()
        if "obs_embed" in line and re.search(
            r"= bf16\[%d,%d,84,84,4\]\S* fusion\(" % (rows, steps + 1), line
        )
    ]
    assert len(operands) == 1, operands
    # The carried matrix states are the program's arguments.
    assert (32, rows, 128, 128) in shapes
    # The sorted rows of all the assignments are never an operand of a
    # kernel, a cut or the activation: they see a rung of them.
    from torchbeast_tpu.models import moe

    tokens = (steps + 1) * rows
    rung, window = moe.window_rungs(tokens, 10, 32, 512)
    assert (rung, window) == (5120, 10 * tokens)
    assert not {s for s in shapes if s[0] == window and s[-1] == 512}
    assert {(rung, 2048), (rung, 512)} <= shapes
    long_rows = re.findall(
        r"^\s+%%\S+ = \(?f32\[%d,2048\][^\n]*" % window, text, re.M
    )
    # Two a MoE part (PR 56), in the first rung and in the loop's body
    # (PR 58: 8 while one copy of a rung stood in the program).
    assert 0 < len(long_rows) <= 16, len(long_rows)
    assert all('/gather"' in line for line in long_rows)
    # A DeltaNet layer solves once, in ops/delta_rule.py's cell (PR 69;
    # ten products forward and two backward at the highest were XLA's,
    # 36 in the update): no product is left under `delta_solve` or
    # `delta_intra`, and the only [64, 64] matrices a (row, chunk,
    # value head) are A and its cotangent, the kernels' own: L, K K^T,
    # D and the doubling's levels exist in VMEM alone.
    assert not [
        line for line in text.splitlines()
        if " convolution(" in line
        and ("/delta_solve/" in line or "/delta_intra/" in line)
    ]
    levels = [
        line for line in text.splitlines()
        if re.search(r"= f32\[%d,4,16,2,64,64\]" % rows, line)
        and "/delta_scan/" in line
    ]
    assert levels and all(
        " custom-call(" in line or " get-tuple-element(" in line
        or " bitcast(" in line for line in levels
    ), levels
    # The experts' products are ONE kernel call each at the family's
    # two terms a side (PR 50: ops/grouped_matmul.py; 144 calls of the
    # shipped kernels before): four MoE parts x (3 forward, 3 the
    # backward sweep's second forward, 6 backward), each rung compiled
    # twice since PR 58, the first before the loop and the loop's body
    # (48 while the loop started from zeros).
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*gmm_cut_in_vmem', text
    )) == 96
    # Beside them the attention layer's two (one forward since PR 63:
    # the rematerialised block keeps its results) and, since PR 61, the
    # delta rule's chunk-to-chunk pass: a forward kernel a DeltaNet
    # layer, again rematerialised, and one backward.
    # Since PR 67 the layer's short convolution likewise
    # (ops/short_conv.py).
    # Since PR 69 what a chunk owes before its state enters it too: the
    # solve's cell once a layer (the rematerialised block keeps W), the
    # apply's again rematerialised, one backward.
    assert text.count("tpu_custom_call") == 96 + 2 + 9 + 9 + 12
    assert_conv_kernels(text, 3)
    for kernel, count in (
        ("delta_rule_forward", 6), ("delta_rule_backward", 3),
        ("delta_sides_solve", 3), ("delta_sides_apply", 6),
        ("delta_sides_backward", 3),
    ):
        assert len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*' + kernel, text
        )) == count, kernel
    # The mechanism's own witness: the state goes from chunk to chunk
    # in VMEM, so no [128, 128] matrix a (row, chunk, value head) is
    # under `delta_scan` (the `jax.numpy` form's `left`, `handed_on`,
    # `entering` and their cotangents: 134 MB each a layer).
    assert not _states_of_every_cell(
        _shapes(text, "/delta_scan/"), rows * 4 * 32
    )
    assert "/moe/moe_sweep/jit(_rung)/moe_experts" in text
    assert "/moe/moe_sweep/while/body/jit(_rung)/moe_experts" in text
    # A forward and a backward loop a MoE part, their turns counted on
    # the device from the step's own group sizes.
    sweeps = re.findall(
        r"while\([^\n]*op_name=\"[^\"]*/moe/moe_sweep/while\"", text
    )
    assert len(sweeps) >= 8, len(sweeps)
