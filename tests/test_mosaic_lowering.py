"""Mosaic TPU lowering regression tests — no chip required.

`jax.export` with `platforms=["tpu"]` runs the full Pallas→Mosaic
lowering pipeline (including the block-mapping legality checks in
jax/_src/pallas/mosaic/lowering.py) client-side on any backend. Two
lowering failures that every CPU interpret-mode test had missed (block
shapes whose trailing dims were neither (8,128)-divisible nor
full-extent; a scoped-VMEM overflow at trunk shape) are why this file
pins the lowering of the kernels at both the unit-test and flagship
shapes. Export stops before the backend compile: what the chip's
compiler itself refuses (scoped VMEM, ops the VPU lacks) is
tests/test_chip_compile.py's to catch.
"""

import numpy as np
import pytest

import jax
import jax.export
import jax.numpy as jnp
from jax import lax

from torchbeast_tpu.ops.pallas_attention import transformer_attention
from torchbeast_tpu.ops.pallas_pool import _auto_block_n, pool_bwd


def _attn_inputs(b, t, h, d, m, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
    k = jnp.asarray(
        rng.standard_normal((b, m + t, h, d)).astype(np.float32)
    )
    v = jnp.asarray(
        rng.standard_normal((b, m + t, h, d)).astype(np.float32)
    )
    done = rng.random((t, b)) < 0.15
    seg = jnp.asarray(np.cumsum(done, axis=0).T.astype(np.int32))
    cache_valid = jnp.asarray((rng.random((b, m)) < 0.7).astype(np.float32))
    no_done = jnp.asarray(np.cumsum(done, axis=0).T == 0)
    rel_bias = jnp.asarray(
        rng.standard_normal((h, m + 1)).astype(np.float32) * 0.1
    )
    return q, k, v, seg, cache_valid, no_done, rel_bias


@pytest.mark.parametrize(
    "b,t,h,d,m",
    [
        (2, 12, 4, 16, 8),    # unit-test shape (pre-fix: block-shape fail)
        (8, 20, 4, 64, 40),   # flagship transformer unroll shape
        (1, 1, 4, 64, 40),    # stepwise acting (T=1)
    ],
)
def test_attention_lowers_for_tpu(b, t, h, d, m):
    args = _attn_inputs(b, t, h, d, m)
    jax.export.export(
        jax.jit(lambda *a: transformer_attention(m, False, *a)),
        platforms=["tpu"],
    )(*args)


@pytest.mark.parametrize(
    "shape",
    [
        (2, 21, 21, 32),   # unit-test shape
        (8, 84, 84, 32),   # widened trunk stage-1
        (640, 84, 84, 32), # full T*B learner batch
        (2592, 84, 84, 16),  # flagship stage-1: W*C = 1344, not 128-aligned
    ],
)
def test_pool_bwd_lowers_for_tpu(shape):
    def fwd(x):
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            ((0, 0), (1, 1), (1, 1), (0, 0)),
        )

    # Lowering only needs avals — abstract args keep the (640, 84, 84,
    # 32) case allocation-free instead of materializing ~580 MB.
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    y = jax.eval_shape(fwd, x)
    g = jax.ShapeDtypeStruct(y.shape, jnp.float32)
    jax.export.export(
        jax.jit(lambda x, y, g: pool_bwd(x, y, g)), platforms=["tpu"]
    )(x, y, g)


@pytest.mark.parametrize("param_dtype", ["f32", "bf16"])
def test_opt_tail_lowers_for_tpu(param_dtype):
    """The fused optimizer tail (ops/pallas_opt.py) lowers to Mosaic at
    the real leaf-shape zoo — odd 1-D biases, non-128 last dims, a
    trunk-fc-sized matrix that takes the chunked-grid path — in both
    resident dtypes, momentum on (the widest kernel arity)."""
    from torchbeast_tpu.ops.pallas_opt import fused_rmsprop_tail

    dt = jnp.bfloat16 if param_dtype == "bf16" else jnp.float32
    shapes = [(532,), (133, 532), (16, 128), (1,), (3872, 256)]
    params = {
        f"leaf{i}": jax.ShapeDtypeStruct(s, dt)
        for i, s in enumerate(shapes)
    }
    grads = params
    opt = fused_rmsprop_tail(
        4.8e-4, decay=0.99, eps=0.01, momentum=0.9, max_norm=40.0,
        param_dtype=param_dtype,
        state_dtype=jnp.bfloat16 if param_dtype == "bf16" else None,
        interpret=False,
    )
    state = jax.eval_shape(opt.init, params)
    jax.export.export(
        jax.jit(opt.update), platforms=["tpu"]
    )(grads, state, params)


def test_auto_block_n_stays_under_what_the_compiler_refused():
    """The chooser against scoped-VMEM sizes the v5e compiler reported
    at the flagship trunk stages (N=2592, limit 16 MB): stage 1 took
    20.76 MB at block_n=2, stage 2 22.49 MB at block_n=4 (2 compiled),
    stage 3 compiled at 8."""
    f32 = jnp.float32
    assert _auto_block_n(84, 84 * 16, 86, 86 * 16, f32) == 1
    assert _auto_block_n(42, 42 * 32, 44, 44 * 32, f32) in (2, 3)
    assert 2 <= _auto_block_n(21, 21 * 32, 24, 24 * 32, f32) <= 9
    # Halving the storage dtype never shrinks the block.
    assert (
        _auto_block_n(42, 42 * 32, 44, 44 * 32, jnp.bfloat16)
        >= _auto_block_n(42, 42 * 32, 44, 44 * 32, f32)
    )
    # A row too big for the budget still gets a block of one.
    assert _auto_block_n(210, 210 * 64, 212, 212 * 64, f32) == 1


@pytest.mark.parametrize(
    "rows, chunks, Hk, per, Q, Dk, Dv, terms",
    [
        (16, 4, 16, 2, 64, 128, 128, 2),  # `qwen3next_policy.learner`'s
        (2, 1, 2, 2, 64, 128, 128, 3),  # one chunk, six passes
        (2, 3, 3, 1, 16, 128, 256, 1),  # one key head a turn, one pass
    ],
)
def test_delta_rule_kernels_lower_for_tpu(
    rows, chunks, Hk, per, Q, Dk, Dv, terms
):
    """ops/delta_rule.py's forward and backward kernels lower to Mosaic
    at the cell's shapes and at others `kernels_apply` admits (the
    chip's compiler has them in tests/test_chip_compile_qwen3next.py)."""
    from torchbeast_tpu.ops import delta_rule

    assert delta_rule.kernels_apply(chunks * Q, Q, Dk, Dv)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    q = f32(rows, chunks, Hk, Q, Dk)
    operands = (
        q, q, f32(rows, chunks, Hk, 2 * per, Q),
        f32(rows, chunks, Hk, per, Q, Q), f32(rows, chunks, Hk, per, Q, Dv),
        f32(rows, chunks, Hk, per, Q, Dk), f32(rows, Hk, per, Dk, Dv),
    )
    jax.export.export(
        jax.jit(lambda *a: delta_rule._forward(
            *a, terms=terms, interpret=False
        )),
        platforms=["tpu"],
    )(*operands)
    jax.export.export(
        jax.jit(lambda *a: delta_rule._backward(
            *a, terms=terms, interpret=False
        )),
        platforms=["tpu"],
    )(*operands, operands[4], operands[6])
