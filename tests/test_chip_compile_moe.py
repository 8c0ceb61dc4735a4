"""Compile for the chip, without the chip: the routed experts.

The grouped matmuls of the MoE cells at their published widths
(models/moe.py on the shipped megablox kernels and on ops/grouped_
matmul.py's, which cut their operands in VMEM), the window a chip
holding fewer experts than a token chooses moves, and the rung a
quarter share sweeps, each forward and backward for the described v5e
of `tests/chip_fixtures.py`. Interpret-mode tests (`tests/test_moe*.py`)
cannot see what the chip's compiler refuses or how it lays a program
out. Nothing runs.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tests.chip_fixtures import (  # noqa: E402, F401
    one_chip,
    struct as _struct,
    topo,
)


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


def _a_rung_stands_before_each_loop_and_in_it(text):
    """The scope paths of a sweep's compiled text: the experts of the
    first rung directly under `moe_sweep`, those of the rungs from the
    second on under its `while/body`, forward and backward (a rung is
    a jitted function, traced once for its two places)."""
    for sweep, experts in (
        ("/jvp(moe_sweep)", "jit(_rung)/moe_experts"),
        (
            "/transpose(jvp(moe_sweep))",
            "jit(_rung_gradients)/jvp(moe_experts)",
        ),
    ):
        assert f"{sweep}/{experts}" in text, sweep
        assert f"{sweep}/while/body/{experts}" in text, sweep


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
    otherwise, and the temporaries were 507,526,144 bytes. And (PR 58)
    the first rung stands outside each loop, its results the loop's
    starting sums: a rung's kernels are in the program twice, once
    before each loop and once as its body, no zeros of a weight's shape
    are broadcast for the loop to add to, and the temporaries are
    282,201,600 bytes (190,986,240 at the parent, whose bound stood at
    PR 44's figure: two more prefetches of `w_down` stand in the
    program, one a first rung)."""
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
    # The forward sweep and the backward's, under the sweep's own name
    # (PR 51: `moe_sweep`; a rung's parts enter their scopes inside):
    # the first rung before the loop, the further ones its body (PR 58).
    _a_rung_stands_before_each_loop_and_in_it(text)
    assert f"f32[{held},{latent},{width}]" in text
    weight = rf"f32\[{held},({latent},{width}|{width},{latent})\]"
    assert not re.search(weight + r"[^=\n]* broadcast\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes <= 282_201_600
    # Two forward kernels in the forward sweep's rung; those and four
    # backward in the backward's; ONE call a product since PR 50 (the
    # kernels cut their operands in VMEM; three passes each, 24,
    # before). Since PR 58 a rung is compiled twice, the first and the
    # loop's body: 16 where one copy was 8.
    assert text.count("tpu_custom_call") == 16
    assert text.count("gmm_cut_in_vmem") >= 16


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
    gradient reads scalars back), each once in the first rung and once
    in the loop that takes the rungs from the second on (PR 58). A
    rung holds three products forward, those and six backward."""
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
    _a_rung_stands_before_each_loop_and_in_it(text)
    # 12 a rung, the first rung and the loop's body (PR 58; one copy,
    # 12, while the loop started from zeros).
    assert text.count("tpu_custom_call") == 24
    # No zeros of a weight's shape for the backward's sums to start
    # from: the first rung's `tgmm`s write them.
    assert not re.search(
        rf"f32\[{held},({d},{width}|{width},{d})\][^=\n]* broadcast\(", text
    )
    # What is written as long as all the sorted rows at the model's
    # width: the two gathers by `slot`, [tokens, K, d] or flat, of the
    # first rung and of the loop's body.
    long_rows = rf"= f32\[({tokens},{top_k},{d}|{rows},{d})\]"
    long_gathers = [
        line for line in text.splitlines()
        if re.search(long_rows, line) and "gather" in line.split("=")[0]
    ]
    assert len(long_gathers) == 4, long_gathers
