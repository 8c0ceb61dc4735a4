"""Compile for the chip, without the chip (tests/chip_fixtures.py):
`ling3_policy.learner`'s whole update, one AOT compile of the real
cell. A file of its own: tests/chip_fixtures.py says why.
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
from torchbeast_tpu.models import ling3
from torchbeast_tpu.ops import delta_rule


@pytest.mark.parametrize(
    "rows, steps, H, chunk, sub, D, precision",
    [
        (8, 256, 32, 64, 16, 128, "high"),  # the cell's
        (2, 200, 4, 64, 16, 128, "highest"),  # six passes, padded
        (2, 64, 2, 64, 16, 128, None),  # one chunk: no turn remakes
        (2, 96, 3, 32, 8, 128, "high"),  # one head a cell
    ],
)
def test_kda_scan_kernels_lower_for_v5e(
    one_chip, monkeypatch, rows, steps, H, chunk, sub, D, precision
):
    """The check interpret mode cannot make: `kda_scan` with its
    chunk-to-chunk pass in ops/delta_rule.py's kernels under a hand-on
    a key channel (`hand_on`: a [per, Dk] row a key head turned to a
    column in the cell, its cotangent a row back), value and every
    gradient, compiles for the chip's compiler at the cell's shapes (8
    rows x 256 steps in 4 chunks of 64, 32 heads of 128 x 128, three
    passes) and at others `kernels_apply` admits; each kernel is one
    Mosaic call and the program around them holds no [128, 128] matrix
    a (row, chunk, head)."""
    assert delta_rule.kernels_apply(steps, min(chunk, steps), D, D)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(q, k, v, g, beta, state, done):
        with jax.default_matmul_precision(precision):
            o, last = ling3.kda_scan(
                q, k, v, g, beta, state, done, chunk, sub
            )
        return jnp.sum(o * o) + jnp.sum(last)

    traced = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6))))
    text = traced.lower(
        _struct(one_chip, (rows, steps, H, D)),
        _struct(one_chip, (rows, steps, H, D)),
        _struct(one_chip, (rows, steps, H, D)),
        _struct(one_chip, (rows, steps, H, D)),
        _struct(one_chip, (rows, steps, H)),
        _struct(one_chip, (rows, H, D, D)),
        _struct(one_chip, (rows, steps), jnp.bool_),
    ).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    assert len(calls) == 2, len(calls)
    assert sum("delta_rule_forward" in call for call in calls) == 1
    assert sum("delta_rule_backward" in call for call in calls) == 1
    chunks = -(-steps // chunk)
    if chunks > 1:
        cells = rows * chunks * H
        states = {
            tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"f32\[([0-9,]+)\]", text)
        }
        assert not {
            s for s in states
            if s[-2:] == (128, 128) and int(np.prod(s[:-2])) >= cells
        }


def test_ling3_cell_update_compiles_for_v5e(one_chip, monkeypatch):
    """`ling3_policy.learner`'s update as the benchmark builds it (the
    configuration's own argv and sizes: one dense layer and one period
    `K K K K K M`, experts 0/64, blocks rematerialised, a [256, 8]
    batch), whole, for a described v5e: it fits beside the driver's copy
    of the weights (under 15.0 GiB with it) and fills the chip. The six
    KDA layers' matrix states [8, 32, 128, 128] are the program's
    arguments; their short convolutions are ops/short_conv.py's kernels
    (12,288 channels); each solves once (ten products forward, two
    backward, at the highest, the rematerialised block solving no
    second time: `unit_lower_inverse`, shared with Qwen3-Next); the
    state goes from chunk to chunk in ops/delta_rule.py's kernels under
    a hand-on a key channel (twelve forward calls, six backward, and no
    [128, 128] matrix a (row, chunk, head) under `kda_scan`); the latent layer's cache leg over 1,023 slots is the fused
    latent leg (no f32 array over the 1,023 or 1,279 keys); the router
    scores all 512 experts and the kernels see the 8 held."""
    from perfbench import manifest
    from perfbench.drivers import learner as learner_driver
    from torchbeast_tpu import monobeast

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
        manifest.HERE, "configs", "ling3_flash_policy.json"
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
    weights = 4 * sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    assert weights == 4 * config["param_count"] == 4 * 793_733_063
    print("memory", memory, "total GiB", total / 2**30,
          "with the copy", (total + weights) / 2**30)
    assert total + weights < 15.0 * 2**30, memory
    assert total > 4 * 2**30, memory  # the cell fills the chip
    text = compiled.as_text()
    shapes = {
        tuple(int(d) for d in dims.split(","))
        for dims in re.findall(r"f32\[([0-9,]+)\]", text)
    }
    # The carried matrix states are the program's arguments.
    assert (32, rows, 128, 128) in shapes
    # No f32 scores over the latent layer's keys: the fused latent leg.
    scores = {
        s for s in shapes if len(s) >= 3 and s[-1] in (1023, 1279, 1280)
    }
    assert not scores, scores
    assert text.count("fused_latent_leg_forward") >= 1
    assert text.count("fused_latent_leg_backward") >= 1
    # A KDA layer solves once: ten products forward, two backward.
    solves = [
        line for line in text.splitlines()
        if " convolution(" in line and "/kda_solve/" in line
    ]
    assert len(solves) == 6 * (10 + 2), len(solves)
    assert all(
        "operand_precision={highest,highest}" in line for line in solves
    )
    assert_conv_kernels(text, 6)
    # A forward kernel a KDA layer, again rematerialised, one backward.
    for kernel, count in (
        ("delta_rule_forward", 12), ("delta_rule_backward", 6),
    ):
        assert len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*' + kernel, text
        )) == count, kernel
    under_scan = {
        tuple(int(d) for d in dims.split(","))
        for line in text.splitlines() if "/kda_scan/" in line
        for dims in re.findall(r"f32\[([0-9,]+)\]", line)
    }
    assert not {
        s for s in under_scan
        if s[-2:] == (128, 128) and int(np.prod(s[:-2])) >= rows * 4 * 32
    }
    # Every scope the family adds reaches the compiled program.
    for scope in (
        "kda_in_proj", "kda_conv", "kda_gate", "kda_intra", "kda_solve",
        "kda_inter", "kda_out", "latent_head_gate", "router_groups",
    ):
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
