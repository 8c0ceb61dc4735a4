"""Compile for the chip, without the chip (tests/chip_fixtures.py):
`kanana2_policy.learner`'s whole update, one AOT compile of the real
cell. A file of its own: tests/chip_fixtures.py says why.
"""

import os

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


def test_kanana2_cell_update_compiles_for_v5e(one_chip, monkeypatch):
    """`kanana2_policy.learner`'s update as the benchmark builds it (the
    configuration's own argv: 5 layers, 4,095-slot latent caches, share
    0/8, blocks rematerialised, [81, 32] batch), whole, for a described
    v5e: its bytes, read before the cell's first chip run (PR 38: 11.4
    GiB; PR 41: 10.42, the score-sized temporaries gone; the driver
    keeps a 2.12 GiB copy of the weights beside it), and
    the absorbed form seen in the program: no array of decompressed
    cached keys or values (4,095 slots x 32 heads of 128, 192 or 256)
    is there, and since PR 41 no f32 array over the slots at all: the
    cache leg's scores [32, 32, 81, 4095] live in the VMEM of `fused_
    latent_leg`'s two kernels."""
    import json
    import re

    from perfbench import manifest
    from perfbench.drivers import learner as learner_driver
    from torchbeast_tpu import monobeast

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
        manifest.HERE, "configs", "kanana2_30b_policy.json"
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
    # `temp_size_in_bytes` 5,196,932,096 with the sweep's loops started from
    # zeros, 5,105,227,264 with each first rung before its loop (PR 58: the
    # zeros of a layer's five sums are gone).
    weights = 4 * sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    assert weights == 4 * 568_124_423
    print("memory", memory, "total GiB", total / 2**30)
    # 15.75 GiB a chip, less the driver's copy of the weights.
    assert total < 15.75 * 2**30 - weights, memory
    assert total > 8 * 2**30, memory  # the cell fills the chip
    # The family's `update_compiler_options` reached the compiler: the
    # blocks' shared parts compiled once (416 MB of program without).
    assert memory.generated_code_size_in_bytes < 200 * 2**20, memory
    shapes = {
        tuple(int(d) for d in dims.split(","))
        for dims in re.findall(r"f32\[([0-9,]+)\]", compiled.as_text())
    }
    # The cache leg's scores, at their own size or a padded one.
    scores = {s for s in shapes if s[-1] in (4095, 4096) and len(s) >= 4}
    assert not scores, scores
    # (Rank 4 or more: [32, 4095, 128] is the cached rope keys placed,
    # padded to a lane tile and laid batch-major for the kernels.)
    decompressed = {
        s for s in shapes
        if 4095 in s and s[-1] in (128, 192, 256, 320) and len(s) >= 4
    }
    assert not decompressed, decompressed
    # The grouped expert matmuls, ONE kernel call a product at the
    # family's two terms a side (PR 50: ops/grouped_matmul.py cuts the
    # float32 tiles in VMEM; three calls a product, 144, before): four
    # MoE layers x (3 forward, 3 the backward sweep's second forward, 6
    # backward), each rung compiled twice since PR 58, the first before
    # the loop and the loop's body (48 while the loop started from
    # zeros); and the cache leg's kernels: five layers x (forward,
    # rematerialised, backward).
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 96 + 15
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*gmm_cut_in_vmem', text
    )) == 96
    assert "/moe/moe_sweep/jit(_rung)/moe_experts" in text
    assert "/moe/moe_sweep/while/body/jit(_rung)/moe_experts" in text
    assert compiled.as_text().count("fused_latent_leg_forward") >= 10
    assert compiled.as_text().count("fused_latent_leg_backward") >= 5
