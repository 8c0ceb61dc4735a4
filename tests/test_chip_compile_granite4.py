"""Compile for the chip, without the chip (tests/chip_fixtures.py):
`granite4_policy.learner`'s whole update, one AOT compile of the real
cell, and the Mamba-2 scan's two kernels and the short convolution's
two alone at shapes they admit beside the cells'. A file of its own:
tests/chip_fixtures.py says why.
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
    assert_scan_kernels,
    on as _on,
    one_chip,
    struct as _struct,
    topo,
)
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import nemotron3
from torchbeast_tpu.ops import short_conv, ssd_scan


@pytest.mark.parametrize(
    "rows, steps, H, P, G, N, chunk, precision",
    [
        (2, 160, 4, 64, 1, 128, 80, "high"),  # --unroll_length 80
        (2, 40, 8, 32, 2, 128, 16, "highest"),  # four heads a tile, padded
        (2, 32, 16, 16, 1, 128, 16, None),  # eight heads a tile, one pass
        (2, 96, 2, 128, 1, 256, 48, "high"),  # a head a tile, a wide state
        (2, 416, 4, 64, 2, 256, 208, "high"),  # a chunk of 1.6 lane tiles
        (2, 512, 4, 64, 1, 384, 256, "high"),
        # All that `kernels_apply` lets the backward kernel hold: four
        # chunks of 128 heads' [64, 256] states, 32 MB of VMEM.
        (1, 1024, 128, 64, 1, 256, 256, "high"),
    ],
)
def test_ssd_scan_kernels_compile_for_v5e(
    one_chip, monkeypatch, rows, steps, H, P, G, N, chunk, precision
):
    """The check interpret mode cannot make, at shapes
    `ssd_scan.kernels_apply` admits and no cell has (the two cells'
    are in the whole updates, here and in
    tests/test_chip_compile_nemotron3.py): `ssd_scan`, value and every
    gradient, compiles for the chip's compiler with chunks that are no
    whole lane tiles (80, 48, 208 steps: [80, 80] scratch, products 80
    deep, `rows[0:1, sources]` on part of a tile), heads of 16, 32 and
    128, a state of two and three lane tiles, one, three and six
    passes, and the most entering states the backward kernel is let
    hold; each kernel is one Mosaic call."""
    assert ssd_scan.kernels_apply(steps, chunk, H, P, G, N)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(x, dt, A, B_in, C_in, state, done):
        with jax.default_matmul_precision(precision):
            y, last = nemotron3.ssd_scan(
                x, dt, A, B_in, C_in, state, done, chunk
            )
        return jnp.sum(y * y) + jnp.sum(last)

    traced = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6))))
    text = traced.lower(
        _struct(one_chip, (rows, steps, H, P)),
        _struct(one_chip, (rows, steps, H)),
        _struct(one_chip, (H,)),
        _struct(one_chip, (rows, steps, G, N)),
        _struct(one_chip, (rows, steps, G, N)),
        _struct(one_chip, (rows, H, P, N)),
        _struct(one_chip, (rows, steps), jnp.bool_),
    ).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    assert len(calls) == 2, len(calls)
    assert sum("ssd_scan_forward" in call for call in calls) == 1
    assert sum("ssd_scan_backward" in call for call in calls) == 1


@pytest.mark.parametrize(
    "rows, steps, channels, taps, bias",
    [
        (2, 8, 128, 2, True),  # one sublane tile, one lane tile, one turn
        (2, 80, 384, 3, False),  # --unroll_length 80: turns of 16 steps
        (1, 1000, 128, 4, True),  # turns of 8 steps
        (2, 24, 17 * 128, 8, True),  # a tail of seven steps: all the tile
        (1, 8192, 256, 4, False),  # a lane tile of a row is a cell's bytes
        (8, 512, 4352, 4, True),  # Granite's cell
        (16, 256, 8192, 4, False),  # Qwen3-Next's
    ],
)
def test_short_conv_kernels_compile_for_v5e(
    one_chip, monkeypatch, rows, steps, channels, taps, bias
):
    """The check interpret mode cannot make, at shapes `short_conv.
    kernels_apply` admits (the five cells' are in the whole updates):
    `conv_over_episodes`, value, new tail and every gradient, compiles
    for the chip's compiler with unrolls of one sublane tile and of
    turns of 8 and 16 steps, two to eight taps, one lane tile a cell
    and thirty-two; each kernel is one Mosaic call."""
    assert short_conv.kernels_apply(steps, channels, taps)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(inputs, tail, weights, offset, done):
        conv, new_tail = nemotron3.conv_over_episodes(
            inputs, tail, done, weights, offset if bias else None
        )
        return jnp.sum(conv * conv) + jnp.sum(new_tail)

    traced = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))
    text = traced.lower(
        _struct(one_chip, (rows, steps, channels)),
        _struct(one_chip, (taps - 1, rows, channels)),
        _struct(one_chip, (taps, channels)),
        _struct(one_chip, (channels,)),
        _struct(one_chip, (rows, steps), jnp.bool_),
    ).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    assert len(calls) == 2, len(calls)
    assert sum("short_conv_forward" in call for call in calls) == 1
    assert sum("short_conv_backward" in call for call in calls) == 1


def test_granite4_cell_update_compiles_for_v5e(one_chip, monkeypatch):
    """`granite4_policy.learner`'s update as the benchmark builds it
    (the configuration's own argv: one period of ten layers, nine
    Mamba-2 mixers whole and one attention layer over a 4,095-slot
    cache, blocks rematerialised, [512, 8] batch), whole, for a
    described v5e: where the fit is settled before any chip time. Its
    bytes with the driver's copy of the weights stay under the rule's
    15.0 GiB (rung 1 of the configuration's `fit`: nothing cut); the
    attention layer's scores over 4,607 keys stay in the fused pass's
    kernels (heads of 64 padded to the lanes, the scale the config's
    1/64), its forward kernel called once; every mixer's scan is
    ops/ssd_scan.py's kernels (PR 65), no state but the carried one an
    array."""
    from perfbench import flops_granite4, manifest
    from perfbench.drivers import learner as learner_driver
    from torchbeast_tpu import monobeast

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
        manifest.HERE, "configs", "granite4_h_micro_policy.json"
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
    # The count the configuration's `reduced_why` and `flops_granite4`
    # state.
    assert weights == 4 * flops_granite4.param_count(config) == (
        4 * 804_305_863
    )
    print("memory", memory, "total GiB", total / 2**30)
    # The rule's 15.0 GiB of the chip's 15.75, with the driver's copy
    # of the weights beside the update.
    assert total + weights < 15.0 * 2**30, memory
    assert total > 8 * 2**30, memory  # the cell fills the chip
    text = compiled.as_text()
    shapes = {
        tuple(int(d) for d in dims.split(","))
        for dims in re.findall(r"f32\[([0-9,]+)\]", text)
    }
    # No f32 array over the 4,095 + 512 keys: the attention layer's
    # scores stay in the fused pass, forward, rematerialised and
    # backward.
    scores = {
        s for s in shapes if len(s) >= 3 and s[-1] in (4095, 4607, 4608)
    }
    assert not scores, scores
    # One forward call: the second forward of the rematerialised block
    # reads the kept results.
    for kernel in ("fused_attend_forward", "fused_attend_backward"):
        assert len(re.findall(
            r'custom_call_target="tpu_custom_call".*' + kernel, text
        )) == 1, kernel
    # The nine mixers' scans, their states [64, 8, 64, 128] a layer.
    assert_scan_kernels(text, shapes, 9, 2, rows * 64 * 64 * 128)
    # And their convolutions over [512, 4352], ops/short_conv.py's.
    assert_conv_kernels(text, 9)
