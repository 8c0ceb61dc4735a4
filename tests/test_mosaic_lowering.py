"""Mosaic TPU lowering regression tests — no chip required.

`jax.export` with `platforms=["tpu"]` runs the full Pallas→Mosaic
lowering pipeline (including the block-mapping legality checks in
jax/_src/pallas/mosaic/lowering.py) client-side on any backend: block
shapes whose trailing dims are neither (8,128)-divisible nor
full-extent fail here and in no CPU interpret-mode test. This file pins
the lowering of every kernel module at the shapes the cells hand it and
at the corners its own rule admits. Export stops before the backend
compile: what the chip's compiler itself refuses (scoped VMEM, ops the
VPU lacks) is the `tests/test_chip_compile*.py` files' to catch.
"""

import pytest

import jax
import jax.export
import jax.numpy as jnp


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


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _bools(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bool_)


def _fused_pass(b, t, h, hkv, d, keys, scale=None):
    """`dense_transformer_attend` as a block calls it: q [b, t, h, d]
    over `keys` keys of `hkv` heads, the cache's taking no gradient."""
    from torchbeast_tpu.ops import attention

    def loss(q, k_all, v_all, mask):
        return jnp.sum(attention.dense_transformer_attend(
            q, k_all, v_all, mask, None, None, keys - t, scale=scale
        ) ** 2)

    return (
        attention.fused_pass_applies((b, t, h, d), (b, keys, hkv, d), None),
        loss, (0, 1, 2),
        (_f32(b, t, h, d), _f32(b, keys, hkv, d), _f32(b, keys, hkv, d),
         _bools(b, t, keys)),
        ("fused_attend_forward", "fused_attend_backward"),
    )


def _latent_leg(b, t, h, latent, rope, slots):
    """`fused_latent_leg` as `latent_cached_attend` calls it: absorbed
    queries head-major, their steps padded to whole tiles, against one
    joined key a slot."""
    from torchbeast_tpu.ops import attention, fused_attention

    tp = fused_attention.padded_steps(t)

    def loss(q_latent, q_rope, cache_latent, cache_rope, mask):
        out, lse = fused_attention.fused_latent_leg(
            q_latent, q_rope, cache_latent, cache_rope, mask,
            (latent + rope) ** -0.5,
        )
        return jnp.sum(out * out) + jnp.sum(lse)

    return (
        attention.fused_latent_leg_applies(
            (b, t, h, rope), slots, latent, "default"
        ),
        loss, (0, 1),
        (_f32(h, b, tp, latent), _f32(h, b, tp, rope),
         _f32(slots, b, latent), _f32(slots, b, rope), _bools(b, t, slots)),
        ("fused_latent_leg_forward", "fused_latent_leg_backward"),
    )


def _ssd_scan(rows, steps, H, P, G, N, chunk, precision="high"):
    from torchbeast_tpu.models import nemotron3
    from torchbeast_tpu.ops import ssd_scan

    def loss(x, dt, A, B_in, C_in, state, done):
        with jax.default_matmul_precision(precision):
            y, last = nemotron3.ssd_scan(
                x, dt, A, B_in, C_in, state, done, chunk
            )
        return jnp.sum(y * y) + jnp.sum(last)

    return (
        ssd_scan.kernels_apply(steps, min(chunk, steps), H, P, G, N),
        loss, tuple(range(6)),
        (_f32(rows, steps, H, P), _f32(rows, steps, H), _f32(H),
         _f32(rows, steps, G, N), _f32(rows, steps, G, N),
         _f32(rows, H, P, N), _bools(rows, steps)),
        ("ssd_scan_forward", "ssd_scan_backward"),
    )


def _delta_rule(rows, steps, Hk, per, chunk, Dk, Dv, precision="high"):
    """Qwen3-Next's `delta_scan`: the chunk-to-chunk pass and, where
    `sides_apply` holds, what a chunk owes before its state enters."""
    from torchbeast_tpu.models import qwen3next
    from torchbeast_tpu.ops import delta_rule

    Hv = Hk * per

    def loss(q, k, v, g, beta, state, done):
        with jax.default_matmul_precision(precision):
            o, last = qwen3next.delta_scan(
                q, k, v, g, beta, state, done, chunk
            )
        return jnp.sum(o * o) + jnp.sum(last)

    sides = delta_rule.sides_apply(steps, min(chunk, steps), Dk, Dv, per)
    return (
        delta_rule.kernels_apply(steps, min(chunk, steps), Dk, Dv),
        loss, tuple(range(6)),
        (_f32(rows, steps, Hk, Dk), _f32(rows, steps, Hk, Dk),
         _f32(rows, steps, Hv, Dv), _f32(rows, steps, Hv),
         _f32(rows, steps, Hv), _f32(rows, Hv, Dk, Dv),
         _bools(rows, steps)),
        ("delta_rule_forward", "delta_rule_backward") + sides * (
            "delta_sides_solve", "delta_sides_apply", "delta_sides_backward",
        ),
    )


def _kda(rows, steps, H, chunk, sub, D, precision="high"):
    """Ling-3.0's `kda_scan`: the same pass under a hand-on a key
    channel."""
    from torchbeast_tpu.models import ling3
    from torchbeast_tpu.ops import delta_rule

    def loss(q, k, v, g, beta, state, done):
        with jax.default_matmul_precision(precision):
            o, last = ling3.kda_scan(
                q, k, v, g, beta, state, done, chunk, sub
            )
        return jnp.sum(o * o) + jnp.sum(last)

    x = _f32(rows, steps, H, D)
    return (
        delta_rule.kernels_apply(steps, min(chunk, steps), D, D),
        loss, tuple(range(6)),
        (x, x, x, x, _f32(rows, steps, H), _f32(rows, H, D, D),
         _bools(rows, steps)),
        ("delta_rule_forward", "delta_rule_backward"),
    )


def _selective_scan(rows, steps, D, N):
    from torchbeast_tpu.ops import selective_scan

    def loss(a, dt, A, B_in, C_in, state, done):
        y, last = selective_scan.selective_scan_kernels(
            a, dt, A, B_in, C_in, state, done
        )
        return jnp.sum(y * y) + jnp.sum(last)

    return (
        selective_scan.kernels_apply(steps, D, N),
        loss, tuple(range(6)),
        (_f32(rows, steps, D), _f32(rows, steps, D), _f32(N, D),
         _f32(rows, steps, N), _f32(rows, steps, N), _f32(rows, N, D),
         _bools(rows, steps)),
        ("selective_scan_forward", "selective_scan_backward"),
    )


def _short_conv(rows, steps, channels, taps, bias=True):
    from torchbeast_tpu.models import nemotron3
    from torchbeast_tpu.ops import short_conv

    def loss(inputs, tail, weights, offset, done):
        conv, new_tail = nemotron3.conv_over_episodes(
            inputs, tail, done, weights, offset if bias else None
        )
        return jnp.sum(conv * conv) + jnp.sum(new_tail)

    return (
        short_conv.kernels_apply(steps, channels, taps),
        loss, (0, 1, 2, 3),
        (_f32(rows, steps, channels), _f32(taps - 1, rows, channels),
         _f32(taps, channels), _f32(channels), _bools(rows, steps)),
        ("short_conv_forward", "short_conv_backward"),
    )


def _stream_mix(n, tokens, d):
    """A sublayer's two calls: the maps and the pre-sum from one read
    of the streams, then the mix of what the sublayer made."""
    from torchbeast_tpu.ops import stream_mix

    columns = n * (n + 2)

    def loss(streams, phi, scale, b, h_res, h_post):
        streams, u, m = stream_mix.maps_and_pre(streams, phi, scale, b, 1e-6)
        mixed = stream_mix.mix(streams, u, h_res, h_post)
        return jnp.sum(mixed * mixed) + jnp.sum(m)

    return (
        stream_mix.kernels_apply(n, d, jnp.float32),
        loss, tuple(range(6)),
        (_f32(n, tokens, d), _f32(n, d, columns), _f32(columns),
         _f32(columns), _f32(n, n, tokens), _f32(n, tokens)),
        ("stream_maps_forward", "stream_maps_backward",
         "stream_mix_backward"),
    )


def _grouped_matmul(rows, d, held, width, gated, precision="high"):
    """A rung's experts as `models/moe.py` calls the kernels that cut
    their operands in VMEM: `gmm`, `gmm` on transposed weights, `tgmm`."""
    from torchbeast_tpu.models import moe

    def loss(x, w_gate, w_up, w_down, sizes):
        with jax.default_matmul_precision(precision):
            hidden = moe._experts_on_rows(
                x, w_gate if gated else None, w_up, w_down, sizes, 0,
                "silu", moe._terms_traced_under(),
            )
        return jnp.sum(hidden)

    with jax.default_matmul_precision(precision):
        admitted = moe._cut_in_kernel(moe._terms_traced_under())
    return (
        admitted, loss, (0, 1, 2, 3),
        (_f32(rows, d), _f32(held, d, width), _f32(held, d, width),
         _f32(held, width, d),
         jax.ShapeDtypeStruct((held + 1,), jnp.int32)),
        ("gmm_cut_in_vmem", "tgmm_cut_in_vmem"),
    )


# <kernel>-<corner>: the shapes each cell of BENCHMARK.json hands the
# kernel, then the smallest and the largest its own rule admits (a rule
# with no upper bound: a corner well past every cell). A scan's rule is
# asked with the chunk the caller makes of the unroll, min(chunk, steps),
# so its smallest unroll is one chunk of one sublane tile.
CORNERS = {
    "fused_attention-mellum2_full": (_fused_pass, (32, 81, 32, 4, 128, 4176)),
    "fused_attention-mellum2_sliding": (
        _fused_pass, (32, 81, 32, 4, 128, 1104)
    ),
    "fused_attention-trinity_sliding": (
        _fused_pass, (32, 81, 32, 4, 128, 2128)
    ),
    "fused_attention-lfm2": (_fused_pass, (16, 256, 32, 8, 64, 4351)),
    "fused_attention-qwen3next": (_fused_pass, (16, 256, 16, 2, 256, 4351)),
    "fused_attention-phi4flash_sliding": (
        _fused_pass, (16, 256, 40, 10, 128, 767)
    ),
    "fused_attention-granite4": (
        _fused_pass, (8, 512, 32, 8, 64, 4607, 1 / 64)
    ),
    # 128 MiB of scores to the byte: acting at one step over a long
    # cache, and one row of heads of 64.
    "fused_attention-smallest_one_step": (
        _fused_pass, (64, 1, 32, 32, 128, 16384)
    ),
    "fused_attention-smallest_one_row": (
        _fused_pass, (1, 8, 8, 1, 64, 524288)
    ),
    "fused_attention-largest_head": (
        _fused_pass, (4, 1024, 16, 16, 512, 9216)
    ),
    "fused_latent_leg-kanana2": (_latent_leg, (32, 81, 32, 512, 64, 4095)),
    "fused_latent_leg-xing4": (_latent_leg, (32, 81, 32, 512, 64, 1023)),
    "fused_latent_leg-ling3": (_latent_leg, (8, 256, 32, 512, 64, 1023)),
    "fused_latent_leg-smallest": (_latent_leg, (8, 1, 16, 128, 64, 262144)),
    "fused_latent_leg-largest": (_latent_leg, (4, 1024, 128, 1024, 128, 8191)),
    "ssd_scan-granite4": (_ssd_scan, (8, 512, 64, 64, 1, 128, 256)),
    "ssd_scan-nemotron3": (_ssd_scan, (16, 256, 32, 64, 2, 128, 128)),
    "ssd_scan-smallest": (_ssd_scan, (1, 16, 1, 128, 1, 128, 16, None)),
    "ssd_scan-largest": (_ssd_scan, (1, 1024, 128, 64, 1, 256, 256, "highest")),
    "delta_rule-qwen3next": (_delta_rule, (16, 256, 16, 2, 64, 128, 128)),
    "delta_rule-ling3": (_kda, (8, 256, 32, 64, 16, 128)),
    "delta_rule-smallest": (_delta_rule, (1, 16, 1, 1, 16, 128, 128, None)),
    "delta_rule-largest": (
        _delta_rule, (2, 2048, 2, 2, 128, 256, 256, "highest")
    ),
    "selective_scan-phi4flash": (_selective_scan, (16, 256, 5120, 16)),
    "selective_scan-smallest": (_selective_scan, (1, 2, 512, 8)),
    "selective_scan-largest": (_selective_scan, (2, 1000, 10240, 64)),
    "short_conv-qwen3next": (_short_conv, (16, 256, 8192, 4, False)),
    "short_conv-granite4": (_short_conv, (8, 512, 4352, 4)),
    "short_conv-nemotron3": (_short_conv, (16, 256, 2560, 4)),
    "short_conv-phi4flash": (_short_conv, (16, 256, 5120, 4)),
    "short_conv-lfm2": (_short_conv, (16, 256, 2048, 3, False)),
    "short_conv-smallest": (_short_conv, (1, 8, 128, 2, False)),
    "short_conv-largest": (_short_conv, (2, 12288, 16384, 8)),
    "stream_mix-xing4": (_stream_mix, (4, 2592, 3584)),
    "stream_mix-smallest": (_stream_mix, (1, 1, 128)),
    "stream_mix-largest": (_stream_mix, (4, 4096, 4992)),
    "grouped_matmul-qwen3next": (_grouped_matmul, (5120, 2048, 32, 512, True)),
    "grouped_matmul-kanana2": (_grouped_matmul, (4096, 2048, 16, 768, True)),
    "grouped_matmul-nemotron3": (
        _grouped_matmul, (2816, 1024, 8, 2688, False)
    ),
    "grouped_matmul-smallest": (
        _grouped_matmul, (128, 128, 1, 128, False, "highest")
    ),
    "grouped_matmul-largest": (
        _grouped_matmul, (16384, 7168, 4, 14336, True, "highest")
    ),
}


@pytest.mark.parametrize("corner", sorted(CORNERS))
def test_admitted_corners_lower_for_tpu(corner, monkeypatch):
    """Every kernel module behind a rule that chooses by shapes lowers
    to Mosaic, value and every gradient, at the shapes the cells hand
    it and at the smallest and the largest its own rule admits: what a
    rule admits beyond a cell's shape is met here before it is met on
    the chip. Each corner is asserted admitted by the rule first."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    make, shape = CORNERS[corner]
    admitted, loss, argnums, operands, kernels = make(*shape)
    assert admitted, shape
    text = jax.export.export(
        jax.jit(jax.value_and_grad(loss, argnums=argnums)),
        platforms=["tpu"],
    )(*operands).mlir_module()
    for kernel in kernels:
        assert kernel in text, kernel
