"""Compile for the chip, without the chip (tests/chip_fixtures.py):
`xing4_policy.learner`'s whole update, one AOT compile of the real
cell. A file of its own: tests/chip_fixtures.py says why.
"""

import json
import os
import re

import numpy as np

import jax

from tests.chip_fixtures import (  # noqa: F401 (fixtures)
    B,
    NUM_ACTIONS,
    T,
    on as _on,
    one_chip,
    topo,
)
from torchbeast_tpu import learner as learner_lib


def test_xing4_cell_update_compiles_for_v5e(one_chip, monkeypatch):
    """`xing4_policy.learner`'s update as the benchmark builds it (the
    configuration's own argv: 5 layers, 1,023-slot latent caches, share
    0/8, blocks rematerialised, [81, 32] batch), whole, for a described
    v5e: where the fit is settled before any chip time. Its bytes with
    the driver's copy of the weights stay under the rule's 15.0 GiB (no
    fallback of the configuration's `fit` taken); the streams cross the
    blocks a token a row, [4, 2592, 3584], never with the 4 on a tile's
    rows nor with an unroll's 81 steps on them (88 a tile, and laid out
    again at every kernel: ops/stream_mix.py); the
    cache leg's scores stay in `fused_latent_leg`'s kernels; 8 held at
    top 4 sweeps a rung of the window (`moe.window_rungs`)."""
    from perfbench import flops_xing4, manifest
    from perfbench.drivers import learner as learner_driver
    from torchbeast_tpu import monobeast

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
        manifest.HERE, "configs", "xing4_29b_policy.json"
    )) as f:
        config = json.load(f)
    flags = monobeast.make_parser().parse_args(
        config["program_argv"]
        + ["--unroll_length", str(T), "--batch_size", str(B)]
    )
    hp = monobeast.hparams_from_flags(flags)
    frame = tuple(config["frame_shape"])
    model, _ = monobeast._init_model_and_params(
        flags, NUM_ACTIONS, B, frame, init_params=False
    )
    optimizer = learner_lib.make_optimizer(hp)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        monobeast.dummy_env_outputs(1, B, frame, np.uint8),
        model.initial_state(B),
    ))
    batch, state = jax.eval_shape(lambda: (
        learner_driver._make_batch(
            jax.random.PRNGKey(0), T + 1, B, NUM_ACTIONS, frame
        ),
        model.initial_state(B),
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
    # The count the configuration's `fit` and `flops_xing4` state.
    assert weights == 4 * flops_xing4.param_count(config) == 4 * 743_118_101
    print("memory", memory, "total GiB", total / 2**30)
    # The rule's 15.0 GiB of the chip's 15.75, with the driver's copy
    # of the weights beside the update.
    assert total + weights < 15.0 * 2**30, memory
    assert total > 8 * 2**30, memory  # the cell fills the chip
    # The family's `update_compiler_options` (Kanana-2's) reached the
    # compiler: the blocks' shared parts compiled once.
    assert memory.generated_code_size_in_bytes < 200 * 2**20, memory
    text = compiled.as_text()
    shapes = {
        tuple(int(d) for d in dims.split(","))
        for dims in re.findall(r"f32\[([0-9,]+)\]", text)
    }
    # The streams, stream-major and a token a row; never with the 4 on
    # a tile's rows ([2592, 4, 3584] is the combine's tokens x top 4,
    # tiled by 4), never [4, B, T, d].
    assert (4, 2592, 3584) in shapes
    assert (32, 81, 4, 3584) not in shapes
    assert (4, 32, 81, 3584) not in shapes
    # No f32 array over the slots of rank 4 or more: the scores.
    scores = {s for s in shapes if s[-1] in (1023, 1024) and len(s) >= 4}
    assert not scores, scores
    assert text.count("fused_latent_leg_forward") >= 10
    assert text.count("fused_latent_leg_backward") >= 5
    # The experts held sweep a rung (the first rung and the loop's
    # body), their float32 tiles cut into the family's three bfloat16
    # terms in VMEM (ops/grouped_matmul.py; 96 such calls at Kanana-2's
    # two terms, 108 at three).
    assert "/moe/moe_sweep/jit(_rung)/moe_experts" in text
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*gmm_cut_in_vmem', text
    )) == 108
    # The residual path's kernels (ops/stream_mix.py), ten sublayers:
    # the maps + pre-sum forward and rematerialised, their backward and
    # the mix's.
    calls = {
        kernel: len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*' + kernel, text
        ))
        for kernel in ("stream_maps_forward", "stream_maps_backward",
                       "stream_mix_backward")
    }
    print("stream kernels", calls)
    assert calls == {
        "stream_maps_forward": 20, "stream_maps_backward": 10,
        "stream_mix_backward": 10,
    }
