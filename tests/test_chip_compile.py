"""Compile for the chip, without the chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached (`jax.experimental.topologies`). Interpret-mode
tests cannot see what it refuses: a scoped-VMEM overflow (`pool_bwd` at
the flagship's first trunk stage asked for 20.76 MB of the 16 MB limit),
a bf16 compare the v5e VPU lacks, a program that does not fit the
device. These tests compile every Pallas kernel a driver can select at
the flagship learner's shapes (unroll 80, batch 32, so the trunk pools
see N = 81 * 32 = 2592 rows), plus the flagship act step at the largest
inference bucket and the flagship update over the four chips against
its one-chip quarter; the whole one-chip update step is the `slow`
case. The families' whole-cell compiles have a file each (`tests/test_
chip_compile_<family>.py`); the described chip all of them share is
`tests/chip_fixtures.py`. Nothing runs: a compile that passes says
nothing about results or times.

Code that asks `jax.default_backend()` still sees the CPU here, so the
kernels get `interpret=False` explicitly and the whole-step cases patch
the backend name for the duration of the trace.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import __graft_entry__  # noqa: E402
from tests.chip_fixtures import (  # noqa: E402, F401
    B,
    NUM_ACTIONS,
    T,
    on as _on,
    one_chip,
    struct as _struct,
    topo,
)
from torchbeast_tpu import learner as learner_lib  # noqa: E402

POOL_N = (T + 1) * B
MAX_INFERENCE_BATCH = 64  # polybeast --max_inference_batch_size default


def _compile_vtrace(chip):
    from torchbeast_tpu.ops.pallas_vtrace import vtrace_targets

    tb = _struct(chip, (T, B))
    return jax.jit(
        lambda *a: vtrace_targets(*a, interpret=False)
    ).lower(tb, tb, tb, tb, tb, tb, _struct(chip, (B,))).compile()


def _compile_opt_tail(chip, param_dtype):
    from torchbeast_tpu.ops.pallas_opt import fused_rmsprop_tail

    bf16 = param_dtype == "bf16"
    _, params = __graft_entry__._flagship_param_structs(
        dtype=jnp.bfloat16 if bf16 else jnp.float32
    )
    opt = fused_rmsprop_tail(
        4.8e-4, decay=0.99, eps=0.01, momentum=0.9, max_norm=40.0,
        param_dtype=param_dtype,
        state_dtype=jnp.bfloat16 if bf16 else None,
        interpret=False,
    )
    state = jax.eval_shape(opt.init, params)
    return jax.jit(opt.update).lower(
        _on(chip, params), _on(chip, state), _on(chip, params)
    ).compile()


def _compile_attention(chip, shape, grad):
    from torchbeast_tpu.ops.pallas_attention import transformer_attention

    b, t, h, d, m = shape
    args = (
        _struct(chip, (b, t, h, d)),
        _struct(chip, (b, m + t, h, d)),
        _struct(chip, (b, m + t, h, d)),
        _struct(chip, (b, t), jnp.int32),
        _struct(chip, (b, m)),
        _struct(chip, (b, t), jnp.bool_),
        _struct(chip, (h, m + 1)),
    )

    def fwd(*a):
        return transformer_attention(m, False, *a)

    fn = fwd
    if grad:
        # value_and_grad: the loss keeps the kernel's forward live next
        # to its custom VJP (the backward alone recomputes through the
        # jnp reference).
        fn = jax.value_and_grad(
            lambda *a: jnp.sum(fwd(*a)), argnums=(0, 1, 2, 6)
        )
    return jax.jit(fn).lower(*args).compile()


def _compile_pool_bwd(chip, hwc, dtype):
    from torchbeast_tpu.ops.pallas_pool import pool_bwd

    h, w, c = hwc
    x = _struct(chip, (POOL_N, h, w, c), dtype)
    y = _struct(chip, (POOL_N, (h + 1) // 2, (w + 1) // 2, c), dtype)
    return pool_bwd.lower(x, y, y).compile()


KERNELS = {
    "vtrace-T80-B32": _compile_vtrace,
    "opt-tail-f32": lambda chip: _compile_opt_tail(chip, "f32"),
    "opt-tail-bf16": lambda chip: _compile_opt_tail(chip, "bf16"),
    "attn-fwd-8x20x4x64x40": lambda chip: _compile_attention(
        chip, (8, 20, 4, 64, 40), grad=False
    ),
    "attn-grad-8x20x4x64x40": lambda chip: _compile_attention(
        chip, (8, 20, 4, 64, 40), grad=True
    ),
    "attn-fwd-1x1x4x64x40": lambda chip: _compile_attention(
        chip, (1, 1, 4, 64, 40), grad=False
    ),
    "attn-grad-1x1x4x64x40": lambda chip: _compile_attention(
        chip, (1, 1, 4, 64, 40), grad=True
    ),
    # C=16: the shape whose padded [N, 86, 1376] operands overflowed
    # scoped VMEM before the block chooser counted tile padding.
    "pool-bwd-stage1-84x84x16": lambda chip: _compile_pool_bwd(
        chip, (84, 84, 16), jnp.float32
    ),
    "pool-bwd-stage2-42x42x32": lambda chip: _compile_pool_bwd(
        chip, (42, 42, 32), jnp.float32
    ),
    "pool-bwd-stage3-21x21x32": lambda chip: _compile_pool_bwd(
        chip, (21, 21, 32), jnp.float32
    ),
    # bf16_train feeds the pool bf16 activations; the v5e VPU has no
    # bf16 compare, so the kernel must widen on load.
    "pool-bwd-stage1-bf16": lambda chip: _compile_pool_bwd(
        chip, (84, 84, 16), jnp.bfloat16
    ),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    compiled = KERNELS[name](one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "rows", [81 * 32 * 8, 8 * 8], ids=["learner-81x32x8", "act-8x8"]
)
def test_olmoe_experts_compile_for_v5e(one_chip, monkeypatch, rows):
    """The OLMoE cell's grouped expert matmuls (models/moe.py on the
    shipped megablox kernels) at the published widths, forward and
    backward: the learner's 20,736 sorted rows, and an act batch of 8
    whose 64 rows are padded to one tile. A contracted tile of 2048
    overflowed VMEM in `tgmm` here before the chip ever saw it."""
    from torchbeast_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d, width, experts = 2048, 1024, 64

    def loss(x, w_gate, w_up, w_down, sizes):
        hidden = jax.nn.silu(
            moe.grouped_matmul(x, w_gate, sizes)
        ) * moe.grouped_matmul(x, w_up, sizes)
        return jnp.sum(moe.grouped_matmul(hidden, w_down, sizes))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        _struct(one_chip, (rows, d)),
        _struct(one_chip, (experts, d, width)),
        _struct(one_chip, (experts, d, width)),
        _struct(one_chip, (experts, width, d)),
        _struct(one_chip, (experts,), jnp.int32),
    ).compile()
    # Two forward kernels (the sum needs no third), six backward.
    assert compiled.as_text().count("tpu_custom_call") >= 8


def test_mellum2_share_of_the_experts_compiles_for_v5e(one_chip, monkeypatch):
    """The Mellum2 cell's grouped matmuls: the learner's 20,736 sorted
    rows over all 64 groups, the weights of experts 16..31 alone
    (megablox's `group_offset`), at the published 2304 -> 896 -> 2304,
    forward and backward."""
    from torchbeast_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, d, width, experts, held = 81 * 32 * 8, 2304, 896, 64, 16

    def loss(x, w_gate, w_up, w_down, sizes):
        hidden = jax.nn.silu(
            moe.grouped_matmul(x, w_gate, sizes, 16)
        ) * moe.grouped_matmul(x, w_up, sizes, 16)
        return jnp.sum(moe.grouped_matmul(hidden, w_down, sizes, 16))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        _struct(one_chip, (rows, d)),
        _struct(one_chip, (held, d, width)),
        _struct(one_chip, (held, d, width)),
        _struct(one_chip, (held, width, d)),
        _struct(one_chip, (experts,), jnp.int32),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 8


# A rung of the window's rows, its width, the experts held and theirs:
# what `moe.window_rungs` hands the kernels in the three cells that
# trace under `high`.
CUT_IN_VMEM_CELLS = {
    "qwen3next": (5120, 2048, 32, 512, True),
    "kanana2": (4096, 2048, 16, 768, True),
    "nemotron3": (2816, 1024, 8, 2688, False),
}


@pytest.mark.parametrize("cell", sorted(CUT_IN_VMEM_CELLS))
@pytest.mark.parametrize("precision", ["high", "highest", "default"])
def test_experts_cut_in_vmem_compile_for_v5e(
    one_chip, monkeypatch, cell, precision
):
    """A rung's grouped matmuls as the three `high` cells call them
    (ops/grouped_matmul.py: `gmm`, `gmm` on transposed weights, `tgmm`,
    each at the up and at the down projection's shape), forward and
    backward, under the 16 MiB of VMEM a kernel is given unasked: one
    kernel call a product at two terms a side and at three; at one
    term the shipped kernels and none of ours."""
    from torchbeast_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rung, d, held, width, gated = CUT_IN_VMEM_CELLS[cell]

    def loss(x, w_gate, w_up, w_down, sizes):
        with jax.default_matmul_precision(precision):
            hidden = moe._experts_on_rows(
                x, w_gate if gated else None, w_up, w_down, sizes, 0,
                "silu", moe._terms_traced_under(),
            )
        return jnp.sum(hidden)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        _struct(one_chip, (rung, d)),
        _struct(one_chip, (held, d, width)),
        _struct(one_chip, (held, d, width)),
        _struct(one_chip, (held, width, d)),
        _struct(one_chip, (held + 1,), jnp.int32),
    ).compile().as_text()
    # Forward, and a product's two gradients (the sum needs no forward
    # of the last).
    calls_owed = 3 * (3 if gated else 2) - 1
    ours = len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*"
                          r"gmm_cut_in_vmem", text))
    calls = text.count("custom_call_target=\"tpu_custom_call\"")
    if precision == "default":
        assert ours == 0 and calls == calls_owed
    else:
        assert ours == calls == calls_owed
    assert "vmem_limit_bytes" not in text


@pytest.mark.parametrize("keys", [4176, 1104], ids=["full", "sliding"])
def test_mellum2_fused_attention_compiles_for_v5e(one_chip, monkeypatch, keys):
    """The Mellum2 cell's attention below `dense_transformer_attend`
    (ops/fused_attention.py), forward and backward at the published
    widths [32, 81, 32 on 4, 128] over a full layer's 4,176 keys and a
    window layer's 1,104: the rule takes the fused pass, two Mosaic
    kernels (704 rows x 384 keys a cell) fit the scoped VMEM they ask
    for, and the compiled program holds no f32 array whose last
    dimension is the keys: the scores' [.., 81, keys] (1.385 GB in the
    full layer) are never built. As the block calls it: the cache's
    keys take no gradient."""
    import re

    from torchbeast_tpu.ops import attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, t, h, hkv, d = B, T + 1, 32, 4, 128
    assert attention.fused_pass_applies((b, t, h, d), (b, keys, hkv, d), None)

    def loss(q, k_all, v_all, mask, dout):
        return jnp.sum(
            attention.dense_transformer_attend(
                q, k_all, v_all, mask, None, None, keys - t
            ) * dout
        )

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _struct(one_chip, (b, t, h, d)),
        _struct(one_chip, (b, keys, hkv, d)),
        _struct(one_chip, (b, keys, hkv, d)),
        _struct(one_chip, (b, t, keys), jnp.bool_),
        _struct(one_chip, (b, t, h, d)),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    padded = -(-keys // 384) * 384
    over_keys = {
        dims for dims in re.findall(r"f32\[([0-9,]+)\]", text)
        if dims.endswith((f",{keys}", f",{padded}"))
    }
    assert not over_keys, over_keys


def test_kanana2_fused_latent_leg_compiles_for_v5e(one_chip, monkeypatch):
    """The Kanana-2 cell's cache leg (ops/fused_attention.py `fused_
    latent_leg`), forward and backward at the published widths: 32
    heads' absorbed queries, head-major and their 81 steps padded to 88
    ([32 heads, 32, 88, 512] and [.., 64], f32), against ONE joined key a
    slot over 4,095 slots, a cotangent on both of its results. The rule
    takes it, two Mosaic kernels fit the scoped VMEM they ask for, and
    the compiled program holds no f32 array whose last dimension is the
    slots: the scores' [32, 32, 81, 4095] (1.36 GB) are never built."""
    import re

    from torchbeast_tpu.ops import attention
    from torchbeast_tpu.ops.fused_attention import (
        fused_latent_leg,
        padded_steps,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, t, h, latent, rope, slots = B, T + 1, 32, 512, 64, 4095
    tp = padded_steps(t)
    assert tp == 88
    assert attention.fused_latent_leg_applies(
        (b, t, h, rope), slots, latent, "default"
    )

    def loss(q_latent, q_rope, cache_latent, cache_rope, mask, dout, dlse):
        out, lse = fused_latent_leg(
            q_latent, q_rope, cache_latent, cache_rope, mask, 192 ** -0.5
        )
        return jnp.sum(out * dout) + jnp.sum(lse * dlse)

    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        _struct(one_chip, (h, b, tp, latent)),
        _struct(one_chip, (h, b, tp, rope)),
        _struct(one_chip, (slots, b, latent)),
        _struct(one_chip, (slots, b, rope)),
        _struct(one_chip, (b, t, slots), jnp.bool_),
        _struct(one_chip, (h, b, tp, latent)),
        _struct(one_chip, (h, b, tp)),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    over_slots = {
        dims for dims in re.findall(r"f32\[([0-9,]+)\]", text)
        if dims.endswith((f",{slots}", f",{slots + 1}"))
    }
    assert not over_slots, over_slots


def test_nemotron3_dispatch_moves_the_windows_rows_alone_on_v5e(
    one_chip, monkeypatch
):
    """`jax.grad` through `dropless_experts` at the Nemotron-3 cell's
    shapes (4,096 tokens, 22 of 512 a token, 8 held, relu^2 experts of
    2,688 in a latent of 1,024, traced under `high`), for a described
    v5e: with fewer experts held than a token chooses, rows are moved
    tokens x 8 at a time. No f32 array of the tokens x 22 sorted rows
    (90,112) nor of those and the window's (122,880) is in the
    program (PR 43: 2,121,320,960 bytes of temporaries before it,
    1,811,172,864 after). And (PR 44) the kernels sweep that window a
    rung of 2,816 rows at a time, in a loop on the device of as many
    turns as the step's rows fill, forward and backward: no array of
    the experts' width is as long as the whole window, zeros or
    otherwise, each loop holds one copy of the kernels, and the
    temporaries are 507,526,144 bytes."""
    from torchbeast_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tokens, top_k, experts, held, latent, width = 4096, 22, 512, 8, 1024, 2688

    def loss(x, gate, w_up, w_down, idx):
        with jax.default_matmul_precision("high"):
            y, _ = moe.dropless_experts(
                x, idx, gate, None, w_up, w_down, first_of=(0, experts),
                activation="relu2",
            )
        return jnp.sum(jnp.sin(y))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        _struct(one_chip, (tokens, latent)),
        _struct(one_chip, (tokens, top_k)),
        _struct(one_chip, (held, latent, width)),
        _struct(one_chip, (held, width, latent)),
        _struct(one_chip, (tokens, top_k), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert f"f32[{tokens * top_k},{latent}]" not in text
    assert f"f32[{tokens * (top_k + held)},{latent}]" not in text
    assert f"f32[{tokens * held},{latent}]" in text  # the gathers by slot
    rung, window = moe.window_rungs(tokens, top_k, held, experts)
    assert (rung, window) == (2816, tokens * held)
    assert f"f32[{rung},{width}]" in text and f"f32[{rung},{latent}]" in text
    assert f"[{window},{width}]" not in text
    # The forward loop and the backward's, under the sweep's own name
    # (PR 51: `moe_sweep`; a rung's parts enter their scopes inside).
    assert "/jvp(moe_sweep)/while/body/moe_experts" in text
    assert (
        "/transpose(jvp(moe_sweep))/while/body/jvp(moe_experts)" in text
    )
    assert compiled.memory_analysis().temp_size_in_bytes <= 507_526_144
    # Two forward kernels in the forward loop; those and four backward
    # in the backward loop; ONE call a product since PR 50 (the kernels
    # cut their operands in VMEM; three passes each, 24, before), and
    # no second copy.
    assert text.count("tpu_custom_call") == 8
    assert text.count("gmm_cut_in_vmem") >= 8


# tokens, top_k, experts, held, d, width, the precision the family traces
# under, the rung: the two cells that hold a QUARTER of their experts.
QUARTER_SHARE_CELLS = {
    "lfm2": (4096, 4, 32, 8, 2048, 1792, "high", 5120),
    "mellum2": (2592, 8, 64, 16, 2304, 896, "default", 6656),
}


@pytest.mark.parametrize("cell", sorted(QUARTER_SHARE_CELLS))
def test_quarter_share_sweeps_a_rung_on_v5e(one_chip, monkeypatch, cell):
    """`jax.grad` through `dropless_experts` at the LFM2 and Mellum2
    cells' layer shapes (8 of 32 held under 4 a token at `high`: the
    kernels that cut in VMEM; 16 of 64 under 8 at one bf16 pass: the
    shipped kernels), for a described v5e. Since PR 56 a quarter share
    with `held >= top_k` sweeps a rung of 1.25 times the even load
    (5,120 of 16,384 sorted rows; 6,656 of 20,736): the kernels, the
    activation and the operand casts see the rung's rows, no array of
    the experts' width is as long as all the sorted rows, and the only
    arrays of the model's width that long are the two gathers by
    `slot` (the forward's sum and the dispatch's gradient; the gates'
    gradient reads scalars back). Each loop holds one copy of the
    kernels: three products forward, those and six backward."""
    from torchbeast_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tokens, top_k, experts, held, d, width, precision, rung = (
        QUARTER_SHARE_CELLS[cell]
    )
    rows = tokens * top_k
    assert moe.window_rungs(tokens, top_k, held, experts) == (rung, rows)

    def loss(x, gate, w_gate, w_up, w_down, idx):
        with jax.default_matmul_precision(precision):
            y, _ = moe.dropless_experts(
                x, idx, gate, w_gate, w_up, w_down, first_of=(held, experts)
            )
        return jnp.sum(jnp.sin(y))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        _struct(one_chip, (tokens, d)),
        _struct(one_chip, (tokens, top_k)),
        _struct(one_chip, (held, d, width)),
        _struct(one_chip, (held, d, width)),
        _struct(one_chip, (held, width, d)),
        _struct(one_chip, (tokens, top_k), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert f"[{rows},{width}]" not in text
    assert f"f32[{rung},{width}]" in text and f"f32[{rung},{d}]" in text
    assert "/jvp(moe_sweep)/while/body/moe_experts" in text
    assert (
        "/transpose(jvp(moe_sweep))/while/body/jvp(moe_experts)" in text
    )
    assert text.count("tpu_custom_call") == 12
    # What is written as long as all the sorted rows at the model's
    # width: the two gathers by `slot`, [tokens, K, d] or flat.
    long_rows = rf"= f32\[({tokens},{top_k},{d}|{rows},{d})\]"
    long_gathers = [
        line for line in text.splitlines()
        if re.search(long_rows, line) and "gather" in line.split("=")[0]
    ]
    assert len(long_gathers) == 2, long_gathers


def test_flagship_act_step_compiles_for_v5e(one_chip, monkeypatch):
    """The acting program at the largest inference bucket."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params = __graft_entry__._flagship_param_structs()
    n = MAX_INFERENCE_BATCH
    env_output = {
        k: v[0] for k, v in __graft_entry__._make_batch(
            0, n, NUM_ACTIONS
        ).items() if k in ("frame", "reward", "done", "last_action")
    }
    compiled = learner_lib.make_act_step(model).lower(
        _on(one_chip, params),
        _on(one_chip, jax.random.PRNGKey(0)),
        _on(one_chip, env_output),
        _on(one_chip, model.initial_state(n)),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_flagship_dp4_update_divides_over_v5e_2x2(topo, one_chip,
                                                  monkeypatch):
    """`deep_lstm.learner_dp4`'s program, [81, 64] over a 2x2 mesh: each
    chip's share is the one-chip program at B = 16, and what crosses the
    chips is the gradients' all-reduce and scalar sums. (A time-major
    merge in the trunk makes the partitioner all-gather the frames,
    `bf16[81,64,84,84,4]`, and every chip run all 5,184 rows.)"""
    import chip_smoke
    from tests.test_parallel import COLLECTIVES, result_dims
    from torchbeast_tpu.parallel import (
        create_mesh,
        make_parallel_update_step,
    )
    from torchbeast_tpu.parallel import mesh as mesh_lib

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chips, rows = len(topo.devices), 64

    def compiled(b, make_step, shardings):
        _, hp, model, params, optimizer, staged = (
            chip_smoke.build_flagship_learner([], T, b)
        )
        repl, batch_sh, state_sh = shardings
        batch, state = staged(T, b)
        return make_step(model, optimizer, hp).lower(
            _on(repl, params),
            _on(repl, jax.eval_shape(optimizer.init, params)),
            _on(batch_sh, batch),
            _on(state_sh, state),
        ).compile()

    mesh = create_mesh(devices=topo.devices)
    dp4 = compiled(
        rows,
        lambda model, optimizer, hp: make_parallel_update_step(
            model, optimizer, hp, mesh
        ),
        (mesh_lib.replicated(mesh), mesh_lib.batch_sharding(mesh),
         mesh_lib.state_sharding(mesh)),
    )
    quarter = compiled(
        rows // chips, learner_lib.make_update_step, (one_chip,) * 3
    )

    collectives = result_dims(dp4.as_text(), *COLLECTIVES)
    assert {kind for kind, _ in collectives} == {"all-reduce"}, collectives
    # Nothing a chip receives has a batch axis: gradients, stats, counts.
    batch_shaped = [
        dims for _, dims in collectives
        if dims[:2] in ((T + 1, rows), (T + 1, rows // chips))
    ]
    assert not batch_shaped, batch_shaped

    def flops(program):
        cost = program.cost_analysis()
        return (cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"]

    assert flops(dp4) == pytest.approx(flops(quarter), rel=0.05)


def flagship_update_memory(chip, argv):
    """Compile the flagship update step exactly as chip_smoke.py builds
    it from `argv`, for the described chip; returns memory_analysis().
    Call with jax.default_backend patched to "tpu"."""
    import chip_smoke

    _, hp, model, params, optimizer, staged = (
        chip_smoke.build_flagship_learner(argv, T, B)
    )
    return learner_lib.make_update_step(model, optimizer, hp).lower(
        _on(chip, params),
        _on(chip, jax.eval_shape(optimizer.init, params)),
        *_on(chip, staged(T, B)),
    ).compile().memory_analysis()


@pytest.mark.slow
@pytest.mark.parametrize(
    "argv",
    [
        [],
        ["--precision", "bf16_train"],
        ["--opt_impl", "pallas", "--vtrace_impl", "pallas"],
    ],
    ids=["f32", "bf16_train", "f32-pallas"],
)
def test_flagship_update_step_compiles_for_v5e(one_chip, monkeypatch,
                                               argv):
    """The whole learner program, TPU branches taken (ops/pool.py's
    SelectAndScatter backward, compiled Pallas kernels where selected),
    inside the chip's 16 GB."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    memory = flagship_update_memory(one_chip, argv)
    total = (
        memory.temp_size_in_bytes + memory.argument_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert total < 16e9, memory
