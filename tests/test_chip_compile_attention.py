"""Compile for the chip, without the chip: the fused attention kernels.

`ops/fused_attention.py`'s pass below `dense_transformer_attend` at the
Mellum2 cell's widths and its latent cache leg at Kanana-2's, forward
and backward for the described v5e of `tests/chip_fixtures.py`: the
rules take them, the Mosaic kernels fit the scoped VMEM they ask for,
and no array of the scores' size is built. Nothing runs.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tests.chip_fixtures import (  # noqa: E402, F401
    B,
    T,
    one_chip,
    struct as _struct,
    topo,
)


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
