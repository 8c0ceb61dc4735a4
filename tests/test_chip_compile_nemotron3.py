"""Compile for the chip, without the chip (tests/chip_fixtures.py):
`nemotron3_policy.learner`'s whole update, one AOT compile of the real
cell. A file of its own: tests/chip_fixtures.py says why.
"""

import os

import numpy as np

import jax

from tests.chip_fixtures import (  # noqa: F401 (fixtures)
    NUM_ACTIONS,
    assert_conv_kernels,
    assert_scan_kernels,
    on as _on,
    one_chip,
    topo,
)
from torchbeast_tpu import learner as learner_lib


def test_nemotron3_cell_update_compiles_for_v5e(one_chip, monkeypatch):
    """`nemotron3_policy.learner`'s update as the benchmark builds it
    (the configuration's own argv and sizes: one period of 11 layers,
    mixers 0/4, experts 0/64, blocks rematerialised, a [256, B] batch),
    whole, for a described v5e: it fits beside the driver's copy of the
    weights (ISSUE 42's rule: under 15.0 GiB with it); the attention
    layer's scores over 4,351 keys live in `fused_attend`'s VMEM (no
    f32 array over the keys is in the program); the experts' kernels
    see one rung at a time of the window of the sorted rows that 8
    held experts can draw (PR 44: 2,816 rows, twice an even load's),
    not all tokens x 22 (PR 42) nor the whole window's tokens x 8."""
    import json
    import re

    from perfbench import manifest
    from perfbench.drivers import learner as learner_driver
    from torchbeast_tpu import monobeast

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
        manifest.HERE, "configs", "nemotron3_super_policy.json"
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
    # Traced afresh, as the benchmark's own process does: the count of
    # the experts' calls below rests on XLA merging two calls whose
    # bodies are equal, and a body's source locations are those of the
    # trace that made it. jit's tracing cache is keyed by the matmul
    # precision among others, so the forward (traced under `high`) can
    # take a `gmm` of these shapes that an earlier test of this process
    # traced (tests/test_chip_compile_moe.py, `high-nemotron3`) where
    # the backward (traced outside it) makes its own: the same kernel
    # from two call stacks, not merged, ten calls more (PR 65, seen in
    # a whole run: the file after the other in one worker; so at the
    # parent commit).
    jax.clear_caches()
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
    # `temp_size_in_bytes` 4,192,582,656 with the sweep's loops started from
    # zeros, 4,199,352,832 with each first rung before its loop (PR 58: +6.8
    # MB, 0.05% of the cell's 12.76 GiB on the chip).
    weights = 4 * sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    assert weights == 4 * 755_035_623
    print("memory", memory, "total GiB", total / 2**30)
    assert total + weights < 15.0 * 2**30, memory
    assert total > 8 * 2**30, memory  # the cell fills the chip
    text = compiled.as_text()
    shapes = {
        tuple(int(d) for d in dims.split(","))
        for dims in re.findall(r"f32\[([0-9,]+)\]", text)
    }
    # (4,096 is the hidden width: keys are 4,095 + 256, or padded.)
    scores = {
        s for s in shapes if len(s) >= 3 and s[-1] in (4095, 4351, 4352)
    }
    assert not scores, scores
    assert text.count("fused_attend_forward") >= 1
    assert text.count("fused_attend_backward") >= 1
    # The sorted rows of all the assignments are never an operand of a
    # kernel: 22 a token; nor is the window's 8 a token, which is swept
    # a rung at a time.
    from torchbeast_tpu.models import moe

    tokens = (steps + 1) * rows
    rung, window = moe.window_rungs(tokens, 22, 8, 512)
    assert (rung, window) == (2816, 8 * tokens)
    assert not {
        s for s in shapes if s[0] in (22 * tokens, window) and s[-1] == 2688
    }
    assert {s for s in shapes if s == (rung, 2688)}
    # The experts' products are ONE kernel call each at the family's
    # two terms a side (PR 50: ops/grouped_matmul.py; 150 calls of the
    # shipped kernels before): five MoE layers x (2 forward, 2 the
    # backward sweep's second forward, 4 backward), each rung compiled
    # twice since PR 58, the first before the loop and the loop's body,
    # and the two forward of the rematerialised block's loop. That
    # block's FIRST rung is live (the latent's up-projection reads the
    # sweep's sum for its weight gradient) and costs no call: it is the
    # product on the operands that the backward sweep's first rung
    # makes again, and XLA merges the two (the loop that is left takes
    # no turn on a step of one rung; 50 while the loops started from
    # zeros, that one's turn among them). Beside them the attention
    # layer's two (one forward since PR 63: the rematerialised block
    # keeps its results). XLA merges two kernel calls only if their
    # serialised bodies are equal to the byte, and a body carries the
    # source locations of the trace that made it: see `jax.clear_caches`
    # above (100 with a `gmm` of these shapes traced earlier in the
    # process, PR 65).
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*gmm_cut_in_vmem', text
    )) == 90
    assert "/moe/moe_sweep/jit(_rung)/moe_experts" in text
    assert "/moe/moe_sweep/while/body/jit(_rung)/moe_experts" in text
    # The five mixers' scans (PR 65), their states [32, 16, 64, 128] a
    # layer: three more kernel calls a mixer.
    assert_scan_kernels(text, shapes, 5, 2, rows * 32 * 64 * 128)
    # And their convolutions (PR 67: ops/short_conv.py), three again.
    assert_conv_kernels(text, 5)
    assert text.count("tpu_custom_call") == 90 + 2 + 3 * 5 + 3 * 5
