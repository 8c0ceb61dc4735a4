"""Compile for the chip, without the chip (tests/chip_fixtures.py):
`lfm2_policy.learner`'s whole update, one AOT compile of the real cell,
and the fused attention pass alone at heads of 64. A file of its own:
tests/chip_fixtures.py says why.
"""

import json
import os
import re

import numpy as np

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
from torchbeast_tpu.ops import attention, fused_attention


def test_fused_pass_lowers_at_heads_of_64_for_v5e(one_chip, monkeypatch):
    """The check interpret mode cannot make: the fused pass, forward
    and backward, at the cell's attention shapes (16 rows x 256 steps,
    32 query heads on 8 key/value heads of 64, 4,095 + 256 keys, the
    cache taking no gradient) compiles for the chip's compiler at one
    term an operand and at the family's two (float32 tiles cut in VMEM,
    three one-pass dots a product and no dot at Mosaic's `highest` in
    the kernels' text), a head padded to the 128 lanes with zero
    columns: the kernels' key tiles are [block, 128] and the result is
    64 wide."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, steps, M, H, Hkv, hd = 16, 256, 4095, 32, 8, 64
    assert attention.fused_pass_applies(
        (rows, steps, H, hd), (rows, M + steps, Hkv, hd), None
    )
    # A T=1 act step (34 MB of scores) and a head of 32 or 96 are not it.
    assert not attention.fused_pass_applies(
        (rows, 1, H, hd), (rows, M + 1, Hkv, hd), None
    )
    for narrow in (32, 96):
        assert not attention.fused_pass_applies(
            (rows, steps, H, narrow), (rows, M + steps, Hkv, narrow), None
        )

    def loss(q, k_all, v_all, mask, terms):
        return jnp.sum(fused_attention.fused_attend(
            q, k_all, v_all, mask, M, terms=terms
        ) ** 2)

    traced = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), static_argnums=4
    )
    operands = (
        _struct(one_chip, (rows, steps, H, hd)),
        _struct(one_chip, (rows, M + steps, Hkv, hd)),
        _struct(one_chip, (rows, M + steps, Hkv, hd)),
        _struct(one_chip, (rows, steps, M + steps), jnp.bool_),
    )
    for terms in (2, 1):
        # The kernels' bodies as traced: every dot one pass, on bfloat16
        # operands; at two terms three a product (two products forward,
        # five backward).
        kernels = str(traced.trace(*operands, terms).jaxpr)
        assert "fused_attend_forward" in kernels
        assert "HIGHEST" not in kernels and "HIGH" not in kernels
        assert kernels.count("dot_general") == 7 * (3 if terms > 1 else 1)
        text = traced.lower(*operands, terms).compile().as_text()
        assert text.count("fused_attend_forward") >= 1
        assert text.count("fused_attend_backward") >= 1
        # No f32 array over the keys a query row: the scores stay in VMEM.
        shapes = {
            tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"f32\[([0-9,]+)\]", text)
        }
        assert not {
            s for s in shapes
            if len(s) >= 3 and s[-1] in (4095, 4351, 4352) and s[-2] >= steps
        }


def test_lfm2_cell_update_compiles_for_v5e(one_chip, monkeypatch):
    """`lfm2_policy.learner`'s update as the benchmark builds it (the
    configuration's own argv and sizes: the dense layer and one period
    `A c c c`, experts 0/4, blocks rematerialised, a [256, B] batch),
    whole, for a described v5e: it fits beside the driver's copy of the
    weights (under the rule's 15.0 GiB with it) and fills the chip; the
    attention layer's scores over 4,351 keys of heads of 64 live in
    `fused_attend`'s VMEM (no f32 array over the keys is in the
    program); the four conv layers' tails [2, B, 2048] are its
    arguments; the frames enter `Dense_0` as bfloat16 integers (PR 52);
    the experts' products are one kernel call each at the family's two
    terms a side (PR 50). Since PR 56 the experts sweep a rung of 5,120
    of the 16,384 sorted rows in a loop body, forward and backward. The
    compiler's account reads 8.82 GiB since (10.80 with the copy; 7.57
    and 9.55 before): it counts the backward loops' carried weight
    gradients, 352 MB a layer, beside the kernels' results they are
    added to. On the chip the cell's peak did not move (10.248 GiB
    against the parent's 10.245, PR 56's runs): the bound below is the
    rule's, and this reading is the compiler's, not the chip's."""
    from perfbench import manifest
    from perfbench.drivers import learner as learner_driver
    from torchbeast_tpu import monobeast

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
        manifest.HERE, "configs", "lfm2_8b_policy.json"
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
    # `temp_size_in_bytes` 4,825,537,024 with the sweep's loops started from
    # zeros, 4,613,905,920 with each first rung before its loop (PR 58: the
    # zeros of a layer's five sums are gone).
    weights = 4 * sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    assert weights == 4 * config["param_count"] == 4 * 532_101_383
    print("memory", memory, "total GiB", total / 2**30,
          "with the copy", (total + weights) / 2**30)
    assert total + weights < 15.0 * 2**30, memory
    assert total > 4 * 2**30, memory  # the cell fills the chip
    text = compiled.as_text()
    shapes = {
        tuple(int(d) for d in dims.split(","))
        for dims in re.findall(r"f32\[([0-9,]+)\]", text)
    }
    scores = {
        s for s in shapes if len(s) >= 3 and s[-1] in (4095, 4351, 4352)
    }
    assert not scores, scores
    assert text.count("fused_attend_forward") >= 1
    assert text.count("fused_attend_backward") >= 1
    # No float32 copy of the batch's frames anywhere in the program.
    frames = (steps + 1) * rows * int(np.prod(frame))
    assert not {s for s in shapes if int(np.prod(s)) >= frames}
    # The carried tails are the program's arguments.
    assert (2, rows, 2048) in shapes
    # Four MoE layers x 12 grouped products a rung, one kernel call
    # each: 3 in the forward sweep's and 9 in the backward's (the
    # rung's forward again, 6 backward); the rematerialised block's
    # sweep is dead, its value unused. Until PR 56 the same 12 stood
    # inline (3 forward, 3 rematerialised, 6 backward) over all 16,384
    # rows. Since PR 58 a rung is compiled twice, the first before the
    # loop and the loop's body (48 while the loop started from zeros),
    # and no zeros of a weight's shape are broadcast under a sweep.
    # Beside them the attention layer's two: one forward since PR 63
    # (the rematerialised block keeps its results), one backward.
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*gmm_cut_in_vmem', text
    )) == 96
    # And, since PR 67, the four conv layers' taps (ops/short_conv.py):
    # a forward kernel a layer, again rematerialised, and one backward.
    assert_conv_kernels(text, 4)
    assert text.count("tpu_custom_call") == 96 + 2 + 3 * 4
    assert "/moe/moe_sweep/jit(_rung)/moe_experts" in text
    assert "/moe/moe_sweep/while/body/jit(_rung)/moe_experts" in text
    assert not re.search(
        r"f32\[8,(2048,1792|1792,2048)\][^=\n]* broadcast\([^\n]*moe_sweep",
        text,
    )
    # The kernels and the SwiGLU's elementwise passes see a rung.
    assert "f32[5120,1792]" in text and "[16384,1792]" not in text
    # The family's scopes reach the compiled program.
    for scope in (
        "conv_operator/conv_in_proj", "conv_operator/conv_gate_taps",
        "conv_operator/conv_out_proj", "/attention/", "dense_mlp",
        "moe_route", "moe_experts", "moe_sweep",
    ):
        assert scope in text, scope
