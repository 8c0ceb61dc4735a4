"""Compile for the chip, without the chip (tests/chip_fixtures.py):
`phi4flash_policy.learner`'s whole update, one AOT compile of the real
cell, and the selective scan's two kernels alone at the cell's shapes.
A file of its own: tests/chip_fixtures.py says why.
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
from torchbeast_tpu.ops import selective_scan


@pytest.mark.parametrize("steps", [256, 200])
def test_selective_scan_kernels_lower_for_v5e(one_chip, monkeypatch, steps):
    """The check interpret mode cannot make: the scan's forward and
    backward kernels at the cell's shapes (16 rows x 256 steps, 5,120
    channels, 16 state columns) compile for the chip's compiler, each a
    Mosaic call, and the program around them holds the states at the
    step blocks' starts and the columns laid along the lanes, never a
    state a step; an unroll of 200 steps is padded to the same two step
    blocks."""
    rows, D, N = 16, 5120, 16
    blocks = -(-steps // 128)
    assert selective_scan.kernels_apply(steps, D, N)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(a, dt, A, B_in, C_in, state, done):
        y, last = selective_scan.selective_scan_kernels(
            a, dt, A, B_in, C_in, state, done
        )
        assert y.shape == a.shape
        return jnp.sum(y * y) + jnp.sum(last)

    traced = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6))))
    text = traced.lower(
        _struct(one_chip, (rows, steps, D)),
        _struct(one_chip, (rows, steps, D)), _struct(one_chip, (N, D)),
        _struct(one_chip, (rows, steps, N)),
        _struct(one_chip, (rows, steps, N)),
        _struct(one_chip, (rows, N, D)),
        _struct(one_chip, (rows, steps), jnp.bool_),
    ).compile().as_text()
    assert text.count("selective_scan_forward") >= 1
    assert text.count("selective_scan_backward") >= 1
    shapes = {
        tuple(int(d) for d in dims.split(","))
        for dims in re.findall(r"f32\[([0-9,]+)\]", text)
    }
    assert (rows, blocks, N, D) in shapes
    assert (rows, 128 * blocks, N, 128) in shapes
    assert not {s for s in shapes if int(np.prod(s)) >= rows * steps * N * D}


def test_phi4flash_cell_update_compiles_for_v5e(one_chip, monkeypatch):
    """`phi4flash_policy.learner`'s update as the benchmark builds it
    (the configuration's own argv and sizes: published layers 14-19,
    blocks rematerialised, a [256, B] batch), whole, for a described
    v5e: it fits beside the driver's copy of the weights (under the
    rule's 15.0 GiB with it) and fills the chip; the selective scans
    are ops/selective_scan.py's kernels (two layers, each forward, made
    again, and backward), their states over the unroll, [T, B, 16, 5120]
    in any order of its axes, in no buffer of the program (the states at
    the step blocks' starts are, T / 128 of [B, 16, 5120], and the
    columns B_t, C_t laid along the lanes); the three layers that
    attend run the fused pass as ONE grouped attention of 128-wide
    heads, the scores over 4,351 keys in its VMEM; the two Mamba states
    and tails are its arguments and the layers that read another's
    values have no state; the frames enter `Dense_0` as bfloat16
    integers (PR 52)."""
    from perfbench import manifest
    from perfbench.drivers import learner as learner_driver
    from torchbeast_tpu import monobeast

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
        manifest.HERE, "configs", "phi4flash_3b8_policy.json"
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
    # Two Mamba layers and two windows carry; two layers carry nothing.
    assert [len(item) for item in state] == [2, 3, 2, 3]
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
    weights = 4 * sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    assert weights == 4 * config["param_count"] == 4 * 705_368_199
    print("memory", memory, "total GiB", total / 2**30,
          "with the copy", (total + weights) / 2**30)
    assert total + weights < 15.0 * 2**30, memory
    assert total > 4 * 2**30, memory  # the cell fills the chip
    text = compiled.as_text()
    shapes = {
        tuple(int(d) for d in dims.split(","))
        for dims in re.findall(r"f32\[([0-9,]+)\]", text)
    }
    # No state of the scan for every step of the unroll: T x B x N x D
    # = 335,544,320 elements (1.34 GB), whatever the order of the axes
    # and whether or not the chunks are an axis of their own.
    D, N = config["expand"] * config["hidden_size"], config["d_state"]
    unroll_of_states = (steps + 1) * rows * N * D
    assert not {s for s in shapes if int(np.prod(s)) >= unroll_of_states}
    # The states at the step blocks' starts, the columns along the lanes.
    assert (rows, (steps + 1) // 128, N, D) in shapes
    assert (rows, steps + 1, N, 128) in shapes
    assert text.count("selective_scan_forward") >= 4
    assert text.count("selective_scan_backward") >= 2
    assert (N, rows, D) in shapes  # the carried states are arguments
    assert (config["d_conv"] - 1, rows, D) in shapes
    # No f32 scores over either window's keys.
    scores = {
        s for s in shapes
        if len(s) >= 3 and s[-1] in (4095, 4351, 4352, 767, 768)
        and s[-2] >= steps
    }
    assert not scores, scores
    # Three layers attend, each rematerialised: three forward calls
    # since PR 63 (a block keeps the kernel's results), six before.
    for kernel in ("fused_attend_forward", "fused_attend_backward"):
        assert len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*' + kernel, text
        )) == 3, kernel
    # The two Mamba-1 layers' convolutions are ops/short_conv.py's
    # kernels (PR 67).
    assert_conv_kernels(text, 2)
    # No float32 copy of the batch's frames anywhere in the program.
    frames = (steps + 1) * rows * int(np.prod(frame))
    assert not {
        s for s in shapes
        if int(np.prod(s)) >= frames and s[-1] != D and s[-2:] != (N, D)
    }
    # The family's scopes reach the compiled program.
    for scope in (
        "mamba1_in_proj", "mamba1_conv", "mamba1_x_proj", "selective_scan",
        "mamba1_out_proj", "attention_sliding", "attention_full",
        "attention_cross", "attention_difference", "memory_unit", "/mlp/",
    ):
        assert scope in text, scope
