"""What every policy family is held to in the same words, once: a case
a family of ONE parametrised test each (tests/family_scaffold.py has
the rule for the next family: one id here, no edit to another family's
file). What is a family's own is in `tests/test_<family>.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import family_scaffold as scaffold
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import monobeast, polybeast
from torchbeast_tpu.models import create_model
from torchbeast_tpu.models import stats as model_stats
from torchbeast_tpu.models.transformer import Recurrent
from torchbeast_tpu.ops import attention

A, B, FRAME = scaffold.A, scaffold.B, scaffold.FRAME
NAMES = list(scaffold.FAMILIES)


def _refused(match, **kwargs):
    return match, kwargs


# --- the registry ---------------------------------------------------------


def _published_olmoe(model):
    assert (model.d_model, model.num_heads, model.num_layers) == (2048, 16, 2)
    assert (model.num_experts, model.experts_per_token) == (64, 8)
    assert (model.expert_width, model.memory_len) == (1024, 128)
    # Centred frames are these families'; the d128 transformer keeps
    # [0, 1].
    assert create_model("transformer", num_actions=6).frame_range == (0.0, 1.0)
    k, v, valid = model.initial_state(3)[1]
    assert k.shape == v.shape == (128, 3, 16, 128)
    assert valid.shape == (128, 3)


def _published_mellum2(model):
    # The side inputs start at zero in this family (and the later ones).
    assert model.zero_init_extras
    assert not create_model("olmoe", num_actions=6).zero_init_extras
    assert not create_model("transformer", num_actions=6).zero_init_extras
    assert (model.d_model, model.num_heads, model.kv_heads) == (2304, 32, 4)
    assert (model.head_dim, model.sliding_window) == (128, 1024)
    assert (model.num_experts, model.experts_per_token) == (64, 8)
    assert (model.expert_width, model.memory_len) == (896, 4095)
    assert model.renormalise and model.rms_norm_eps == 1e-6
    assert model.held_experts() is None
    share = create_model(
        "mellum2", num_actions=6, num_layers=4, expert_share=(3, 4)
    )
    assert share.held_experts() == (48, 16)


def _published_ouro(model):
    assert (model.d_model, model.num_heads, model.head_dim) == (2048, 16, 128)
    assert (model.mlp_width, model.passes, model.memory_len) == (5632, 4, 255)
    assert (model.rms_norm_eps, model.rope_theta) == (1e-6, 1e6)
    assert not model.zero_init_extras
    assert model.matmul_precision == "high"
    # 4 x 8 caches of [255, B, 16, 128] over 8 blocks, pass-major.
    assert model.layer_caches() == ((255, 16, 128),) * 32
    assert model.block_passes() == (tuple(range(8)),) * 4
    state = jax.eval_shape(lambda: model.initial_state(3))
    assert len(state) == 32 and state[31][0].shape == (255, 3, 16, 128)


def _published_kanana2(model):
    assert model.zero_init_extras
    assert (model.d_model, model.num_heads, model.latent_rank) == (
        2048, 32, 512
    )
    assert (
        model.nope_head_dim, model.rope_head_dim, model.value_head_dim
    ) == (128, 64, 128)
    assert (model.dense_layers, model.mlp_width) == (1, 6144)
    assert (
        model.num_experts, model.experts_per_token, model.expert_width,
        model.shared_experts,
    ) == (128, 6, 768, 2)
    assert model.renormalise and model.routed_scaling == 2.448
    assert (model.rms_norm_eps, model.rope_theta) == (1e-6, 1e6)
    assert (model.memory_len, model.bias_update_rate) == (4095, 0.001)
    assert model.layer_caches() == ((4095, 1, (512, 64)),) * 5
    assert model.held_experts() is None
    share = create_model(
        "kanana2", num_actions=6, num_layers=5, expert_share=(7, 8)
    )
    assert share.held_experts() == (112, 16)
    # The published heads are not `fused_attend`'s (128 lanes a head):
    # 192-wide unroll keys, one 576-wide cache key. The cache leg has a
    # fused pass of its own, which the learner's shapes take at the
    # family's one bf16 pass and a T=1 act step does not.
    for q_width, keys in ((192, 81), (576, 4095)):
        assert not attention.fused_pass_applies(
            (32, 81, 32, q_width), (32, keys, 1, q_width), None
        )
    assert model.cache_leg_precision == "default"
    for steps, fused in ((81, True), (1, False)):
        assert attention.fused_latent_leg_applies(
            (32, steps, 32, 576), 4095, 512, model.cache_leg_precision
        ) is fused


def _published_nemotron3(model):
    assert model.zero_init_extras
    assert (model.d_model, model.num_heads, model.kv_heads, model.head_dim) == (
        4096, 32, 2, 128
    )
    assert (
        model.mamba_heads, model.mamba_head_dim, model.mamba_groups,
        model.state_size, model.conv_kernel, model.chunk_size,
    ) == (128, 64, 8, 128, 4, 128)
    assert model.mamba_heads * model.mamba_head_dim == 2 * model.d_model
    assert (
        model.num_experts, model.experts_per_token, model.expert_width,
        model.latent_width, model.shared_width,
    ) == (512, 22, 2688, 1024, 5376)
    assert model.renormalise and model.routed_scaling == 5.0
    assert (model.rms_norm_eps, model.memory_len) == (1e-5, 4095)
    assert model.pattern() == "*EMEMEMEMEM"
    assert model.held_mixers() == (32, 2, 8, 1)
    assert model.held_experts() == (0, 8)
    window, nothing, carried = model.layer_caches()[:3]
    assert window == (4095, 1, 128) and nothing is None
    assert carried == Recurrent(((32, 64, 128), (3, 2048 + 2 * 2 * 128)))
    whole = create_model("nemotron3", num_actions=6)
    assert whole.num_layers == 88 == len(whole.pattern())
    assert whole.pattern()[25:36] == model.pattern()
    assert [whole.pattern().count(c) for c in "M*E"] == [40, 8, 40]
    assert whole.held_mixers() == (128, 8, 32, 2)
    # Two chips a layer halve the key/value heads; eight hold one each.
    assert create_model(
        "nemotron3", num_actions=6, mixer_share=(1, 2)
    ).held_mixers() == (64, 4, 16, 1)
    assert create_model(
        "nemotron3", num_actions=6, mixer_share=(7, 8)
    ).held_mixers() == (16, 1, 4, 1)
    # The cell's attention layer (8 query heads of 128 on one key/value
    # head over 4,095 + 256 keys, 570 MB of f32 scores at B=16) is
    # `fused_attend`'s; a T=1 act step is not.
    assert attention.fused_pass_applies(
        (16, 256, 8, 128), (16, 4351, 1, 128), None
    )
    assert not attention.fused_pass_applies(
        (16, 1, 8, 128), (16, 4096, 1, 128), None
    )


def _published_qwen3next(model):
    assert model.zero_init_extras
    assert (model.d_model, model.num_heads, model.kv_heads, model.head_dim) == (
        2048, 16, 2, 256
    )
    assert (model.rotary_factor, model.rope_theta) == (0.25, 1e7)
    assert (
        model.delta_key_heads, model.delta_value_heads, model.delta_key_dim,
        model.delta_value_dim, model.conv_kernel, model.chunk_size,
    ) == (16, 32, 128, 128, 4, 64)
    assert (
        model.num_experts, model.experts_per_token, model.expert_width,
        model.shared_width,
    ) == (512, 10, 512, 512)
    assert model.renormalise and model.aux_loss_weight == 0.001
    assert (model.rms_norm_eps, model.memory_len) == (1e-6, 4095)
    assert model.matmul_precision == "high"
    assert model.held_experts() == (0, 32)
    # One period: three matrix states with their conv tails, one window.
    carried = Recurrent(((32, 128, 128), (3, 2 * 2048 + 4096)))
    assert model.layer_caches() == (carried, None) * 3 + (
        (4095, 2, 256), None,
    )
    state = jax.eval_shape(lambda: model.initial_state(16))
    assert [leaf.shape for leaf in state[0]] == [
        (32, 16, 128, 128), (3, 16, 8192),
    ]
    assert 4 * sum(
        int(np.prod(leaf.shape[:1] + leaf.shape[2:]))
        for item in state[:3] for leaf in item
    ) == 6_586_368
    whole = create_model("qwen3next", num_actions=6)
    assert [type(e) is tuple for e in whole.layer_caches()[::2]] == (
        [False, False, False, True] * 12
    )
    assert whole.held_experts() is None
    # The cell's attention layer (8 query heads of 256 a key/value head
    # over 4,095 + 256 keys, 1.14 GB of f32 scores at B=16) is
    # `fused_attend`'s at a head of two lane tiles; a T=1 act step is not.
    assert attention.fused_pass_applies(
        (16, 256, 16, 256), (16, 4351, 2, 256), None
    )
    assert not attention.fused_pass_applies(
        (16, 1, 16, 256), (16, 4096, 2, 256), None
    )


def _published_lfm2(model):
    assert model.zero_init_extras
    assert (model.d_model, model.num_heads, model.kv_heads, model.head_dim) == (
        2048, 32, 8, 64
    )
    assert model.num_heads * model.head_dim == model.d_model
    assert (model.conv_kernel, model.conv_bias) == (3, False)
    assert (
        model.dense_width, model.expert_width, model.num_experts,
        model.experts_per_token, model.num_dense_layers,
    ) == (7168, 1792, 32, 4, 2)
    assert model.renormalise and model.use_expert_bias
    assert (model.routed_scaling, model.gate_sum_floor) == (1.0, 1e-6)
    assert (model.norm_eps, model.rope_theta) == (1e-5, 1e6)
    assert (model.memory_len, model.bias_update_rate) == (4095, 0.001)
    assert model.matmul_precision == "high"
    assert model.held_experts() == (0, 8)
    # The cut: published layer 1 (a conv operator over the dense SwiGLU),
    # then one period `A c c c`: four two-step tails and one window.
    tail = Recurrent(((2, 2048),))
    assert model.layers() == (
        ("conv", True), ("full_attention", False),
    ) + (("conv", False),) * 3
    assert model.layer_caches() == (tail, (4095, 8, 64), tail, tail, tail)
    state = jax.eval_shape(lambda: model.initial_state(16))
    assert [leaf.shape for leaf in state[0]] == [(2, 16, 2048)]
    # 16 KB a row and conv layer.
    assert 4 * 2 * 2048 == 16_384
    whole = create_model("lfm2", num_actions=6)
    assert "".join(
        "A" if kind == "full_attention" else "c" for kind, _ in whole.layers()
    ) == "ccAcccAcccAcccAcccAccAcc"
    assert [dense for _, dense in whole.layers()] == [True] * 2 + [False] * 22
    assert whole.layers()[1:6] == model.layers()
    assert whole.held_experts() is None
    # The cell's attention layer (4 query heads of 64 a key/value head
    # over 4,095 + 256 keys, 2.28 GB of f32 scores at B=16) is
    # `fused_attend`'s at HALF a lane tile a head; a T=1 act step is not.
    assert attention.fused_pass_applies(
        (16, 256, 32, 64), (16, 4351, 8, 64), None
    )
    assert not attention.fused_pass_applies(
        (16, 1, 32, 64), (16, 4096, 8, 64), None
    )


def _published_phi4flash(model):
    assert model.zero_init_extras
    assert (
        model.d_model, model.num_heads, model.num_key_value_heads,
        model.intermediate_size, model.sliding_window, model.mb_per_layer,
    ) == (2560, 40, 20, 10240, 512, 2)
    assert model.layer_norm_eps == 1e-5
    assert not model.mlp_bias
    assert (model.d_state, model.d_conv, model.expand, model.dt_rank) == (
        16, 4, 2, 160
    )
    assert model.dt_rank == -(-model.d_model // 16)
    assert model.memory_len == 4095
    assert model.matmul_precision == "high"
    # The cut: published layers 14-19, one pair of each stage.
    assert model.published_indices() == (14, 15, 16, 17, 18, 19)
    assert model.kinds() == (
        "mamba", "sliding", "mamba", "full", "memory", "cross"
    )
    carried = Recurrent(((16, 5120), (3, 5120)))
    assert model.layer_caches() == (
        carried, (511, 20, 64), carried, (4095, 20, 64), None, None
    )
    assert model.layer_shares() == (
        ((), ()), ((), ()), (("memory",), ()), (("keys_values",), ()),
        ((), ("memory",)), ((), ("keys_values",)),
    )
    whole = create_model("phi4flash", num_actions=6)
    kinds = whole.kinds()
    assert len(kinds) == 32 and whole.published_indices() == tuple(range(32))
    assert [kinds.count(k) for k in (
        "mamba", "sliding", "full", "memory", "cross"
    )] == [9, 8, 1, 7, 7]
    assert kinds[14:20] == model.kinds()
    # The cell's three attentions as ONE grouped attention of 40 query
    # heads on 10 key/value heads of 128 (2.85 GB of f32 scores over the
    # full window's 4,351 keys at B=16, 0.50 over the sliding layer's
    # 767) are `fused_attend`'s; a T=1 act step is not.
    for keys in (4351, 767):
        assert attention.fused_pass_applies(
            (16, 256, 40, 128), (16, keys, 10, 128), None
        )
    assert not attention.fused_pass_applies(
        (16, 1, 40, 128), (16, 4096, 10, 128), None
    )


def _published_xing4(model):
    assert model.zero_init_extras
    assert (
        model.d_model, model.num_heads, model.latent_rank, model.query_rank,
    ) == (3584, 32, 512, 768)
    assert (
        model.nope_head_dim, model.rope_head_dim, model.value_head_dim
    ) == (128, 64, 128)
    assert (model.dense_layers, model.mlp_width) == (2, 9216)
    assert (
        model.num_experts, model.experts_per_token, model.expert_width,
        model.shared_experts,
    ) == (64, 4, 1024, 1)
    assert model.renormalise and model.routed_scaling == 2.0
    assert (model.rms_norm_eps, model.rope_theta) == (1e-6, 1e4)
    assert model.yarn == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert (
        model.streams, model.sinkhorn_iters, model.hc_eps, model.res_clamp
    ) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (model.memory_len, model.bias_update_rate) == (1023, 0.001)
    assert model.layer_caches() == ((1023, 1, (512, 64)),) * 5
    assert model.held_experts() == (0, 8)
    # The cut: ONE leading dense layer; the streams between the blocks.
    assert model.leading_dense_layers() == 1
    x = jax.ShapeDtypeStruct((32, 81, 3584), jnp.float32)
    # A token a row, the batch's shape beside them (PR 60): the layout
    # ops/stream_mix.py's kernels take, held from block to block.
    streams = jax.eval_shape(model.into_streams, x)
    assert streams.x.shape == (4, 32 * 81, 3584)
    assert (streams.rows, streams.steps) == (32, 81)
    assert jax.eval_shape(model.out_of_streams, streams).shape == x.shape
    # 57 KB a token between blocks, in float32.
    assert 4 * 4 * 3584 == 57_344
    block = model.make_block("block_1", 1)
    assert (block.query_rank, block.sublayers) == (768, 10)
    assert block.score_scale == pytest.approx((0.1 * np.log(64) + 1) ** 2)
    # YaRN over the 32 rope frequencies: the fastest kept, the slowest
    # divided by 64.
    assert len(block.rope_inv_freq) == 32
    assert block.rope_inv_freq[0] == 1.0
    assert block.rope_inv_freq[-1] == pytest.approx(
        1e4 ** (-62 / 64) / 64
    )
    # The cache leg at 1,023 slots is the fused pass (339 MB of f32
    # scores at one bf16 pass); a T=1 act step is not.
    assert (model.matmul_precision, model.cache_leg_precision) == (
        "highest", "default"
    )
    for steps, fused in ((81, True), (1, False)):
        assert attention.fused_latent_leg_applies(
            (32, steps, 32, 576), 1023, 512, model.cache_leg_precision
        ) is fused


def _published_trinity(model):
    assert model.zero_init_extras
    assert (model.d_model, model.num_heads, model.kv_heads, model.head_dim) == (
        2048, 32, 4, 128
    )
    assert model.layer_period == ("sliding_attention",) * 3 + (
        "full_attention",
    )
    assert (model.sliding_window, model.dense_layers, model.mlp_width) == (
        2048, 2, 6144
    )
    assert (
        model.num_experts, model.experts_per_token, model.expert_width,
        model.shared_experts,
    ) == (128, 8, 1024, 1)
    assert model.renormalise and model.routed_scaling == 2.826
    assert (model.rms_norm_eps, model.rope_theta) == (1e-5, 1e4)
    assert (model.memory_len, model.bias_update_rate) == (4095, 0.001)
    # `mup_enabled`: the encoder's output times hidden_size ** 0.5; no
    # other family multiplies it.
    assert model.input_scale == 2048 ** 0.5
    assert model.matmul_precision == "highest"
    assert create_model("mellum2", num_actions=6).input_scale == 1.0
    assert model.held_experts() == (0, 16)
    # The cut: published layer 1 (dense, sliding), then one period; four
    # caches of the window less one and one of `memory_len`.
    assert model.leading_dense_layers() == 1
    assert [model.layer_kind(layer) for layer in range(5)] == [
        "sliding_attention"
    ] * 4 + ["full_attention"]
    assert model.layer_caches() == ((2047, 4, 128),) * 4 + ((4095, 4, 128),)
    state = jax.eval_shape(lambda: model.initial_state(32))
    assert [item[0].shape for item in state] == (
        [(2047, 32, 4, 128)] * 4 + [(4095, 32, 4, 128)]
    )
    # 1.61 GB of carried state at B=32, float32.
    assert 4 * sum(
        int(np.prod(leaf.shape)) for item in state for leaf in item
    ) == 1_611_529_600
    whole = create_model("trinity", num_actions=6)
    assert whole.leading_dense_layers() == 2 and whole.held_experts() is None
    assert [m for m, _, _ in whole.layer_caches()] == (
        [2047] * 3 + [4095]
    ) * 8
    # The cell's layers (32 query heads of 128 on 4 key/value heads over
    # 2,047 + 81 and 4,095 + 81 keys: 0.71 and 1.39 GB of f32 scores at
    # B=32) are `fused_attend`'s, rotated or not; a T=1 act step is not.
    for keys in (2128, 4176):
        assert attention.fused_pass_applies(
            (32, 81, 32, 128), (32, keys, 4, 128), None
        )
    assert not attention.fused_pass_applies(
        (32, 1, 32, 128), (32, 4096, 4, 128), None
    )


def _published_ling3(model):
    assert model.zero_init_extras
    assert (model.d_model, model.num_heads, model.head_dim) == (2560, 32, 128)
    assert (model.layer_group_size, model.dense_layers, model.mlp_width) == (
        6, 2, 6144
    )
    assert (
        model.conv_kernel, model.chunk_size, model.sub_chunk,
        model.safe_gate, model.gate_lower_bound,
    ) == (4, 64, 16, True, -5.0)
    # 16 steps at the floor stay inside float32; a chunk's 64 do not.
    assert 16 * 5.0 < 88 < 64 * 5.0
    assert (
        model.latent_rank, model.nope_head_dim, model.rope_head_dim,
        model.value_head_dim, model.rope_theta,
    ) == (512, 128, 64, 128, 6e6)
    assert (
        model.num_experts, model.experts_per_token, model.expert_width,
        model.shared_width, model.n_group, model.topk_group,
    ) == (512, 8, 768, 768, 8, 4)
    assert model.renormalise and model.routed_scaling == 2.5
    assert (model.rms_norm_eps, model.memory_len, model.bias_update_rate) == (
        1e-6, 1023, 0.001
    )
    assert (model.matmul_precision, model.cache_leg_precision) == (
        "high", "default"
    )
    # Share 0 of 64: eight experts, all inside group 0 of eight.
    assert model.held_experts() == (0, 8)
    # The cut: published layer 1 (dense, KDA), then one group `KKKKKM`;
    # six carried states and one latent window.
    assert model.leading_dense_layers() == 1
    assert [model.is_latent(layer) for layer in range(7)] == (
        [False] * 6 + [True]
    )
    state = jax.eval_shape(lambda: model.initial_state(8))
    assert [item[0].shape for item in state] == (
        [(32, 8, 128, 128)] * 6 + [(1023, 8, 1, 512)]
    )
    assert state[0][1].shape == (3, 8, 12288)
    assert [leaf.shape for leaf in state[6]] == [
        (1023, 8, 1, 512), (1023, 8, 1, 64), (1023, 8)
    ]
    # 13,467,648 bytes of KDA state a row, float32: six layers'.
    assert 6 * 4 * (32 * 128 * 128 + 3 * 12288) == 13_467_648
    whole = create_model("ling3", num_actions=6)
    assert whole.leading_dense_layers() == 2 and whole.held_experts() is None
    assert [
        layer for layer in range(42) if whole.is_latent(layer)
    ] == [5, 11, 17, 23, 29, 35, 41]
    # The cell's latent layer over 1,023 slots at 256 steps is the fused
    # latent leg (268 MB of f32 scores at one bf16 pass); a T=1 act step
    # is not.
    for steps, fused in ((256, True), (1, False)):
        assert attention.fused_latent_leg_applies(
            (8, steps, 32, 576), 1023, 512, model.cache_leg_precision
        ) is fused


def _published_granite4(model):
    assert model.zero_init_extras
    assert (model.d_model, model.num_heads, model.kv_heads, model.head_dim) == (
        2048, 32, 8, 64
    )
    assert (
        model.mamba_heads, model.mamba_head_dim, model.mamba_groups,
        model.state_size, model.conv_kernel, model.chunk_size,
    ) == (64, 64, 1, 128, 4, 256)
    assert (model.mlp_width, model.rms_norm_eps, model.memory_len) == (
        8192, 1e-5, 4095
    )
    # The config's four multipliers; 1/64 is NOT head_dim^-0.5.
    assert (
        model.input_scale, model.attention_multiplier,
        model.residual_multiplier, model.logits_scale,
    ) == (12.0, 0.015625, 0.22, 0.125)
    assert model.attention_multiplier != model.head_dim ** -0.5
    assert create_model("nemotron3", num_actions=6).logits_scale == 1.0
    assert model.matmul_precision == "high"
    # One period: `MMMMM*MMMM`, nine carried states and one window.
    assert model.pattern() == ("mamba",) * 5 + ("attention",) + (
        "mamba",
    ) * 4
    state = jax.eval_shape(lambda: model.initial_state(8))
    assert [item[0].shape for item in state] == (
        [(64, 8, 64, 128)] * 5 + [(4095, 8, 8, 64)] + [(64, 8, 64, 128)] * 4
    )
    assert state[0][1].shape == (3, 8, 4352)
    # 19,344,384 bytes of Mamba state a row, float32: nine layers'.
    assert 9 * 4 * (64 * 64 * 128 + 3 * 4352) == 19_344_384
    whole = create_model("granite4", num_actions=6)
    assert [
        layer for layer, kind in enumerate(whole.pattern())
        if kind == "attention"
    ] == [5, 15, 25, 35]
    # The cell's attention layer (32 query heads of 64 on 8 key/value
    # heads over 4,095 + 512 keys: 2.4 GB of f32 scores at B=8) is
    # `fused_attend`'s, its heads padded to the lanes; a T=1 act step is
    # not.
    assert attention.fused_pass_applies(
        (8, 512, 32, 64), (8, 4607, 8, 64), None
    )
    assert not attention.fused_pass_applies(
        (8, 1, 32, 64), (8, 4096, 8, 64), None
    )


# family: how the cell builds it, the depth of the published model, its
# own assertions, and what the registry refuses beside `use_lstm`.
REGISTRY = {
    "olmoe": (dict(num_layers=2), 16, _published_olmoe, []),
    "mellum2": (dict(num_layers=4), 28, _published_mellum2, [
        _refused("whole periods of 4", num_layers=3),
        *(_refused("expert_share", expert_share=bad)
          for bad in [(4, 4), (0, 3), (-1, 4)]),
    ]),
    "ouro": (dict(num_layers=8), 48, _published_ouro, []),
    "kanana2": (dict(num_layers=5), 48, _published_kanana2, [
        _refused("at least one MoE layer", num_layers=1),
        *(_refused("expert_share", expert_share=bad)
          for bad in [(8, 8), (0, 3), (-1, 8)]),
    ]),
    "nemotron3": (
        dict(num_layers=11, mixer_share=(0, 4), expert_share=(0, 64)), 88,
        _published_nemotron3, [
            _refused("whole periods of 11", num_layers=5),
            *(_refused("mixer_share", mixer_share=bad)
              for bad in [(4, 4), (0, 3), (-1, 8), (0, 16)]),
            _refused("expert_share", expert_share=(0, 7)),
        ],
    ),
    "qwen3next": (
        dict(num_layers=4, expert_share=(0, 16)), 48, _published_qwen3next, [
            _refused("whole periods of 4", num_layers=6),
            *(_refused("expert_share", expert_share=bad)
              for bad in [(16, 16), (0, 3), (-1, 16)]),
        ],
    ),
    "lfm2": (
        dict(num_layers=5, expert_share=(0, 4)), 24, _published_lfm2, [
            *(_refused(r"1 \+ 4k layers, or is all 24", num_layers=bad)
              for bad in [4, 1, 8]),
            *(_refused("expert_share", expert_share=bad)
              for bad in [(4, 4), (0, 3), (-1, 4)]),
        ],
    ),
    "phi4flash": (
        dict(num_layers=6), 32, _published_phi4flash, [
            *(_refused("whole pairs of layers around", num_layers=bad)
              for bad in [4, 7, 34]),
        ],
    ),
    "xing4": (
        dict(num_layers=5, memory_len=1023, expert_share=(0, 8)), 40,
        _published_xing4, [
            _refused("at least one MoE layer", num_layers=1),
            *(_refused("expert_share", expert_share=bad)
              for bad in [(8, 8), (0, 3), (-1, 8)]),
        ],
    ),
    "trinity": (
        dict(num_layers=5, expert_share=(0, 8)), 32, _published_trinity, [
            *(_refused(r"1 \+ 4k layers, or is all 32", num_layers=bad)
              for bad in [6, 4, 1, 36]),
            *(_refused("expert_share", expert_share=bad)
              for bad in [(8, 8), (0, 3), (-1, 8)]),
        ],
    ),
    "granite4": (
        dict(num_layers=10), 40, _published_granite4, [
            *(_refused("whole periods of 10", num_layers=bad)
              for bad in [5, 11, 45]),
        ],
    ),
    "ling3": (
        dict(num_layers=7, expert_share=(0, 64)), 42, _published_ling3, [
            *(_refused(r"1 \+ 6k layers, or is all 42", num_layers=bad)
              for bad in [6, 8, 1, 48]),
            *(_refused("expert_share", expert_share=bad)
              for bad in [(64, 64), (0, 3), (-1, 8)]),
        ],
    ),
}


@pytest.mark.parametrize("family", NAMES)
def test_registry_builds_the_published_widths_and_refuses_lstm(family):
    kwargs, depth, published, refusals = REGISTRY[family]
    model = create_model(family, num_actions=6, **kwargs)
    assert isinstance(model, scaffold.FAMILIES[family].net)
    assert model.frame_range == (-1.0, 1.0)
    assert create_model(family, num_actions=6).num_layers == depth
    published(model)
    with pytest.raises(ValueError, match="use_lstm"):
        create_model(family, num_actions=6, use_lstm=True)
    for match, bad in refusals:
        with pytest.raises(ValueError, match=match):
            create_model(family, num_actions=6, **bad)


# --- the parsers -----------------------------------------------------------


def _flags_olmoe(parse, build):
    flags = parse(["--model", "olmoe", "--num_layers", "3", "--memory_len", "9"])
    assert (flags.model, flags.num_layers, flags.memory_len) == ("olmoe", 3, 9)
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (3, 9, 64)
    # The flags are the transformer families' alone.
    with pytest.raises(ValueError, match="num_layers"):
        build(parse(["--model", "mlp", "--num_layers", "3"]))
    model = build(parse(
        ["--model", "transformer", "--num_layers", "1", "--memory_len", "7"]
    ))
    assert (model.num_layers, model.memory_len) == (1, 7)
    return build(flags), None


def _flags_mellum2(parse, build):
    flags = parse([
        "--model", "mellum2", "--num_layers", "8", "--memory_len", "9",
        "--expert_share", "1/4",
    ])
    assert (flags.model, flags.num_layers, flags.expert_share) == (
        "mellum2", 8, "1/4"
    )
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (8, 9, 48)
    assert model.held_experts() == (2, 2)
    # --num_layers 3 is refused, and says why.
    with pytest.raises(ValueError, match="whole periods of 4"):
        build(parse(["--model", "mellum2", "--num_layers", "3"]))
    with pytest.raises(ValueError, match="'i/n'"):
        build(parse(["--model", "mellum2", "--expert_share", "quarter"]))
    # The share is refused for a family without experts to divide.
    for other in ("deep", "transformer", "olmoe"):
        with pytest.raises(
            ValueError,
            match="--model mellum2 or kanana2 or nemotron3 or qwen3next or "
                  "lfm2 or xing4 or trinity or ling3 only",
        ):
            build(parse(["--model", other, "--expert_share", "0/4"]))
    return model, ["--model", "mellum2", "--num_layers", "4"]


def _flags_ouro(parse, build):
    model = build(parse([
        "--model", "ouro", "--num_layers", "3", "--memory_len", "9",
        "--remat", "all",
    ]))
    assert (model.num_layers, model.memory_len, model.d_model) == (3, 9, 64)
    # `passes` has no flag: the (shrunken) table's.
    assert model.passes == 3 and len(model.layer_caches()) == 9
    for flag, value in (("--num_experts", "4"), ("--expert_share", "0/4")):
        with pytest.raises(ValueError):
            build(parse(["--model", "ouro", flag, value]))
    return model, ["--model", "ouro", "--num_layers", "3"]


def _flags_kanana2(parse, build):
    flags = parse([
        "--model", "kanana2", "--num_layers", "3", "--memory_len", "9",
        "--expert_share", "1/8",
    ])
    assert (flags.model, flags.num_layers, flags.expert_share) == (
        "kanana2", 3, "1/8"
    )
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (3, 9, 48)
    assert model.held_experts() == (2, 2)
    with pytest.raises(ValueError, match="at least one MoE layer"):
        build(parse(["--model", "kanana2", "--num_layers", "1"]))
    with pytest.raises(ValueError, match="use_lstm"):
        build(parse(["--model", "kanana2", "--use_lstm"]))
    return model, ["--model", "kanana2", "--num_layers", "3"]


def _flags_nemotron3(parse, build):
    flags = parse([
        "--model", "nemotron3", "--num_layers", "3", "--memory_len", "9",
        "--expert_share", "1/8", "--mixer_share", "1/2",
    ])
    assert (flags.model, flags.expert_share, flags.mixer_share) == (
        "nemotron3", "1/8", "1/2"
    )
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (3, 9, 32)
    assert model.held_experts() == (2, 2)
    assert model.held_mixers() == (4, 2, 2, 1)
    with pytest.raises(ValueError, match="whole periods of 3"):
        build(parse(["--model", "nemotron3", "--num_layers", "2"]))
    with pytest.raises(ValueError, match="must be 'i/n'"):
        build(parse([
            "--model", "nemotron3", "--num_layers", "3",
            "--mixer_share", "half",
        ]))
    with pytest.raises(ValueError, match="mixer_share .* nemotron3 only"):
        build(parse(["--model", "kanana2", "--mixer_share", "0/2"]))
    with pytest.raises(ValueError, match="use_lstm"):
        build(parse(["--model", "nemotron3", "--use_lstm"]))
    return model, ["--model", "nemotron3", "--num_layers", "3"]


def _flags_qwen3next(parse, build):
    flags = parse([
        "--model", "qwen3next", "--num_layers", "4", "--memory_len", "9",
        "--expert_share", "1/4",
    ])
    assert (flags.model, flags.num_layers, flags.expert_share) == (
        "qwen3next", 4, "1/4"
    )
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (4, 9, 32)
    assert model.held_experts() == (4, 4)
    # The (shrunken) table's interval: a period of two.
    assert [type(e) is tuple for e in model.layer_caches()[::2]] == [
        False, True, False, True,
    ]
    with pytest.raises(ValueError, match="whole periods of 2"):
        build(parse(["--model", "qwen3next", "--num_layers", "3"]))
    # The mixers are whole on every chip: no share of them to take.
    with pytest.raises(ValueError, match="mixer_share .* nemotron3 only"):
        build(parse(["--model", "qwen3next", "--mixer_share", "0/2"]))
    with pytest.raises(ValueError, match="use_lstm"):
        build(parse(["--model", "qwen3next", "--use_lstm"]))
    return model, ["--model", "qwen3next", "--num_layers", "2"]


def _flags_lfm2(parse, build):
    flags = parse([
        "--model", "lfm2", "--num_layers", "5", "--memory_len", "9",
        "--expert_share", "1/4",
    ])
    assert (flags.model, flags.num_layers, flags.expert_share) == (
        "lfm2", 5, "1/4"
    )
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (5, 9, 32)
    assert model.held_experts() == (4, 4)
    # The (shrunken) table's period of two: the dense conv layer, `A c`
    # twice.
    assert [type(e) is tuple for e in model.layer_caches()] == [
        False, True, False, True, False,
    ]
    with pytest.raises(ValueError, match=r"1 \+ 2k layers, or is all 24"):
        build(parse(["--model", "lfm2", "--num_layers", "4"]))
    # The operators are whole on every chip: no share of them to take.
    with pytest.raises(ValueError, match="mixer_share .* nemotron3 only"):
        build(parse(["--model", "lfm2", "--mixer_share", "0/2"]))
    with pytest.raises(ValueError, match="use_lstm"):
        build(parse(["--model", "lfm2", "--use_lstm"]))
    return model, ["--model", "lfm2", "--num_layers", "3"]


def _flags_phi4flash(parse, build):
    flags = parse([
        "--model", "phi4flash", "--num_layers", "8", "--memory_len", "9",
    ])
    assert (flags.model, flags.num_layers, flags.memory_len) == (
        "phi4flash", 8, 9
    )
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (8, 9, 32)
    # Published layers 12-19: two pairs before the boundary's, one after;
    # the sliding layers carry the (shrunken) window less one.
    assert model.published_indices() == tuple(range(12, 20))
    assert [
        None if e is None else type(e) is tuple for e in model.layer_caches()
    ] == [False, True, False, True, False, True, None, None]
    assert [e[0] for e in model.layer_caches()[1:6:2]] == [3, 3, 9]
    with pytest.raises(ValueError, match="an even number from 6 to 32"):
        build(parse(["--model", "phi4flash", "--num_layers", "5"]))
    # A dense model whose layers are whole on a chip: no share to take.
    for flag, value in (("--expert_share", "0/4"), ("--mixer_share", "0/2"),
                        ("--num_experts", "4")):
        with pytest.raises(ValueError):
            build(parse(["--model", "phi4flash", flag, value]))
    with pytest.raises(ValueError, match="use_lstm"):
        build(parse(["--model", "phi4flash", "--use_lstm"]))
    return model, ["--model", "phi4flash", "--num_layers", "6"]


def _flags_xing4(parse, build):
    flags = parse([
        "--model", "xing4", "--num_layers", "3", "--memory_len", "9",
        "--expert_share", "1/8",
    ])
    assert (flags.model, flags.num_layers, flags.expert_share) == (
        "xing4", 3, "1/8"
    )
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (3, 9, 48)
    assert (model.query_rank, model.streams) == (20, 4)
    assert model.held_experts() == (2, 2)
    assert model.leading_dense_layers() == 1
    # All the published layers: both leading dense layers.
    assert build(parse(["--model", "xing4"])).leading_dense_layers() == 2
    with pytest.raises(ValueError, match="at least one MoE layer"):
        build(parse(["--model", "xing4", "--num_layers", "1"]))
    with pytest.raises(ValueError, match="mixer_share .* nemotron3 only"):
        build(parse(["--model", "xing4", "--mixer_share", "0/2"]))
    with pytest.raises(ValueError, match="use_lstm"):
        build(parse(["--model", "xing4", "--use_lstm"]))
    return model, ["--model", "xing4", "--num_layers", "3"]


def _flags_trinity(parse, build):
    flags = parse([
        "--model", "trinity", "--num_layers", "7", "--memory_len", "9",
        "--expert_share", "1/8",
    ])
    assert (flags.model, flags.num_layers, flags.expert_share) == (
        "trinity", 7, "1/8"
    )
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (7, 9, 48)
    assert model.held_experts() == (2, 2)
    assert model.leading_dense_layers() == 1
    # The (shrunken) table's period of two and window: the dense layer,
    # `s F` three times; the sliding layers carry 3 slots.
    assert [m for m, _, _ in model.layer_caches()] == [3] + [3, 9] * 3
    # All the published layers: every leading dense layer (the shrunken
    # table's one; the published two are the registry case's).
    whole = build(parse(["--model", "trinity"]))
    assert (whole.num_layers, whole.leading_dense_layers()) == (32, 1)
    # --num_layers 6 is refused, and says why.
    with pytest.raises(ValueError, match=r"1 \+ 2k layers, or is all 32"):
        build(parse(["--model", "trinity", "--num_layers", "6"]))
    with pytest.raises(ValueError, match="mixer_share .* nemotron3 only"):
        build(parse(["--model", "trinity", "--mixer_share", "0/2"]))
    with pytest.raises(ValueError, match="use_lstm"):
        build(parse(["--model", "trinity", "--use_lstm"]))
    return model, ["--model", "trinity", "--num_layers", "3"]


def _flags_granite4(parse, build):
    flags = parse([
        "--model", "granite4", "--num_layers", "6", "--memory_len", "9",
    ])
    assert (flags.model, flags.num_layers) == ("granite4", 6)
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (6, 9, 32)
    # The (shrunken) table's period of three, twice; the multipliers
    # have no flag: the table's.
    assert model.pattern() == ("mamba", "attention", "mamba") * 2
    assert (model.input_scale, model.logits_scale) == (3.0, 0.25)
    # --num_layers 4 is refused, and says why.
    with pytest.raises(ValueError, match="whole periods of 3"):
        build(parse(["--model", "granite4", "--num_layers", "4"]))
    # Dense and whole on a chip: no share of either kind.
    with pytest.raises(ValueError, match="mixer_share .* nemotron3 only"):
        build(parse(["--model", "granite4", "--mixer_share", "0/2"]))
    with pytest.raises(ValueError, match="expert_share"):
        build(parse(["--model", "granite4", "--expert_share", "0/2"]))
    with pytest.raises(ValueError, match="use_lstm"):
        build(parse(["--model", "granite4", "--use_lstm"]))
    return model, ["--model", "granite4", "--num_layers", "3"]


def _flags_ling3(parse, build):
    flags = parse([
        "--model", "ling3", "--num_layers", "5", "--memory_len", "9",
        "--expert_share", "1/8",
    ])
    assert (flags.model, flags.num_layers, flags.expert_share) == (
        "ling3", 5, "1/8"
    )
    model = build(flags)
    assert (model.num_layers, model.memory_len, model.d_model) == (5, 9, 32)
    # Half of group 0 of the (shrunken) table's four groups of four.
    assert model.held_experts() == (2, 2)
    assert model.leading_dense_layers() == 1
    # The (shrunken) table's group of two: the dense layer, `K M` twice.
    assert [model.is_latent(layer) for layer in range(5)] == [
        False, False, True, False, True
    ]
    # --num_layers 4 is refused, and says why; so is a share that cuts
    # a group unevenly.
    with pytest.raises(ValueError, match=r"1 \+ 2k layers, or is all 42"):
        build(parse(["--model", "ling3", "--num_layers", "4"]))
    with pytest.raises(ValueError, match="expert_share"):
        build(parse(["--model", "ling3", "--expert_share", "0/3"]))
    with pytest.raises(ValueError, match="mixer_share .* nemotron3 only"):
        build(parse(["--model", "ling3", "--mixer_share", "0/2"]))
    with pytest.raises(ValueError, match="use_lstm"):
        build(parse(["--model", "ling3", "--use_lstm"]))
    return model, ["--model", "ling3", "--num_layers", "3"]


FLAGS = {
    "olmoe": _flags_olmoe, "mellum2": _flags_mellum2, "ouro": _flags_ouro,
    "kanana2": _flags_kanana2, "nemotron3": _flags_nemotron3,
    "qwen3next": _flags_qwen3next, "lfm2": _flags_lfm2,
    "phi4flash": _flags_phi4flash, "xing4": _flags_xing4,
    "trinity": _flags_trinity, "granite4": _flags_granite4,
    "ling3": _flags_ling3,
}


@pytest.mark.parametrize("family", NAMES)
@pytest.mark.parametrize("driver", [monobeast, polybeast], ids=["mono", "poly"])
def test_parsers_take_the_family_and_its_flags(driver, family, monkeypatch):
    """Both parsers take `--model <family>` and the family's flags, the
    model built from them is the family's at the (shrunken) table's
    widths, what the family cannot do is refused with its reason, and
    `--remat all` reaches the blocks of a family whose class says so."""
    toy = scaffold.FAMILIES[family]
    widths = {
        k: v for k, v in toy.small.items()
        if k not in ("num_layers", "memory_len")
    }
    monkeypatch.setattr(
        toy.module, "PUBLISHED", dict(toy.module.PUBLISHED, **widths)
    )

    def build(flags):
        return monobeast._init_model_and_params(
            flags, A, B, FRAME, init_params=False
        )[0]

    parse = driver.make_parser().parse_args
    model, remat_flags = FLAGS[family](parse, build)
    assert isinstance(model, toy.net)
    if remat_flags:
        assert build(parse(remat_flags + ["--remat", "all"])).remat is True


# --- seeded outputs ----------------------------------------------------------

SEEDED = {
    "olmoe": (
        dict(
            num_layers=2, memory_len=5, d_model=32, num_heads=2,
            num_experts=4, experts_per_token=2, expert_width=16,
        ),
        dict(
            params=23461,
            logits=[
                0.9347226619720459, 2.0615499019622803, 0.7423094511032104,
                1.6697852611541748,
            ],
            baseline=-2.277128219604492,
            leaf_shapes=[[5, 2, 2, 16], [5, 2, 2, 16], [5, 2], [5, 2, 2, 16]],
            state_sum=1020.148193359375,
        ),
    ),
    "mellum2": (
        dict(
            num_layers=4, memory_len=9, d_model=48, num_heads=4, kv_heads=2,
            head_dim=16, sliding_window=4, num_experts=8,
            experts_per_token=2, expert_width=24,
        ),
        dict(
            params=153205,
            logits=[
                -0.5582820177078247, -0.5578451156616211,
                -0.16875900328159332, 0.3949725925922394,
            ],
            baseline=0.9923625588417053,
            leaf_shapes=[[3, 2, 2, 16], [3, 2, 2, 16], [3, 2], [3, 2, 2, 16]],
            state_sum=1876.7550048828125,
        ),
    ),
    "ouro": (
        dict(
            num_layers=2, memory_len=5, d_model=32, num_heads=2, head_dim=16,
            mlp_width=48, passes=3,
        ),
        dict(
            params=20166,
            logits=[
                -1.6723791360855103, -1.0398012399673462,
                -0.014137506484985352, -0.9085246920585632,
            ],
            baseline=0.43291524052619934,
            leaf_shapes=[[5, 2, 2, 16], [5, 2, 2, 16], [5, 2], [5, 2, 2, 16]],
            state_sum=3152.189697265625,
        ),
    ),
}


@pytest.mark.parametrize("family", list(SEEDED))
def test_seeded_logits_are_what_they_were_before_pr_38(family):
    """PR 38 let a cache entry's two leaves differ (models/transformer.
    py `layer_caches`, `initial_state`) and gave `DroplessMoE` a second
    router: the tree, state and outputs of the families that were there
    before it, at a seeded tiny size, are the numbers its parent commit
    gave. The model (4 actions) is initialised from fixed keys, warmed
    by one unroll and run on a second: its parameter count, the last
    step's logits of row 0 and baseline of row 1, the first four state
    leaves' shapes and the sum of the new state's magnitudes."""
    widths, pinned = SEEDED[family]
    model = scaffold.FAMILIES[family].net(num_actions=4, **widths)
    batches = [scaffold.inputs(seed, [(3, 1)]) for seed in range(3)]
    # ouro EAGERLY, as the numbers were read: as one program its logits
    # round 3e-6 from them, past the pin's 1e-6.
    jit = family != "ouro"
    tree = scaffold.init_params(model, batches[0], jit=jit)
    assert (
        sum(x.size for x in jax.tree_util.tree_leaves(tree))
        == pinned["params"]
    )
    _, state = scaffold.forward(model, jit)(
        tree, batches[1], model.initial_state(2)
    )
    out, new_state = scaffold.forward(model, jit)(tree, batches[2], state)
    np.testing.assert_allclose(
        out.policy_logits[-1, 0], pinned["logits"], rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        out.baseline[-1, 1], pinned["baseline"], rtol=1e-5, atol=1e-6
    )
    leaves = jax.tree_util.tree_leaves(new_state)
    assert [list(x.shape) for x in leaves[:4]] == pinned["leaf_shapes"]
    np.testing.assert_allclose(
        sum(float(jnp.sum(jnp.abs(x))) for x in leaves),
        pinned["state_sum"], rtol=1e-5,
    )


# --- the update's stats and their gauges -------------------------------------

_LOSSES = [
    "aux_loss", "baseline_loss", "entropy_loss", "episode_count",
    "episode_returns_sum", "grad_norm", "pg_loss", "total_loss",
]
# The keys of the update's stats at PR 44 (the parent of the PR that
# moved the folds to where a counter is sown), beside `_LOSSES`: every
# family with a share of its experts (and mixers) held, so that the
# `held` and `window` counters are there; `deep` sows nothing.
STATS_AT_PR_44 = {
    "deep": [],
    "olmoe": [
        "attention_two_leg_applications", "moe_assignments",
        "moe_load_max_over_mean",
    ],
    "mellum2": [
        "moe_assignments", "moe_held_assignments",
        "moe_held_load_max_over_mean", "moe_load_max_over_mean",
    ],
    "ouro": [
        "attention_two_leg_applications", "loop_block_applications",
        "loop_cache_bytes_per_row", "loop_exit_p_last",
        "loop_expected_exit_pass", "loop_passes",
    ],
    "kanana2": [
        "attention_latent_applications",
        "attention_latent_cache_bytes_per_row", "moe_assignments",
        "moe_bias_abs_max", "moe_bias_steps", "moe_held_assignments",
        "moe_held_load_max_over_mean", "moe_load_max_over_mean",
        "moe_shared_applications", "moe_window_rows",
        "moe_window_short_applications",
    ],
    # PR 67's count of the layers whose short convolution its kernels
    # ran, here and in the four families below that call it.
    "nemotron3": [
        "conv_kernel_applications",
        "moe_assignments", "moe_bias_abs_max", "moe_bias_steps",
        "moe_held_assignments", "moe_held_load_max_over_mean",
        "moe_latent_applications", "moe_load_max_over_mean",
        "moe_shared_applications", "moe_window_rows",
        "moe_window_short_applications", "ssm_applications", "ssm_chunks",
        "ssm_kernel_applications", "ssm_resets_per_row",
        "ssm_state_bytes_per_row",
    ],
    # Not a parent's: the family of PR 46, as it came (four of sixteen
    # held under three chosen: no window); PR 61's count of the layers
    # whose chunk-to-chunk pass its kernels ran.
    "qwen3next": [
        "attention_gated_applications", "conv_kernel_applications",
        "delta_applications", "delta_chunks", "delta_kernel_applications",
        "delta_resets_per_row", "delta_state_bytes_per_row",
        "moe_assignments", "moe_held_assignments",
        "moe_held_load_max_over_mean", "moe_load_max_over_mean",
        "moe_shared_applications",
    ],
    # The family of PR 53, as it came (four of sixteen held under three
    # chosen: no window).
    "lfm2": [
        "conv_kernel_applications", "conv_layers", "conv_resets_per_row",
        "conv_state_bytes_per_row",
        "moe_assignments", "moe_bias_abs_max", "moe_bias_steps",
        "moe_held_assignments", "moe_held_load_max_over_mean",
        "moe_load_max_over_mean",
    ],
    # The family of PR 55, as it came.
    "phi4flash": [
        "attention_differential_applications", "conv_kernel_applications",
        "shared_bytes_per_row",
        "shared_kv_readers", "shared_memory_readers", "ssm_applications",
        "ssm_chunks", "ssm_resets_per_row", "ssm_state_bytes_per_row",
    ],
    # The family of PR 59, as it came: Kanana-2's and the residual
    # path's three; PR 60's count of the sublayers its kernels ran.
    "xing4": [
        "attention_latent_applications",
        "attention_latent_cache_bytes_per_row", "hc_bytes_per_row",
        "hc_fused_applications", "hc_post_mean", "hc_res_row_error_max",
        "moe_assignments",
        "moe_bias_abs_max", "moe_bias_steps", "moe_held_assignments",
        "moe_held_load_max_over_mean", "moe_load_max_over_mean",
        "moe_shared_applications", "moe_window_rows",
        "moe_window_short_applications",
    ],
    # The family of PR 62, as it came (two of sixteen held under three
    # chosen: a window of one rung).
    "trinity": [
        "attention_gated_applications", "attention_unrotated_applications",
        "moe_assignments", "moe_bias_abs_max", "moe_bias_steps",
        "moe_held_assignments", "moe_held_load_max_over_mean",
        "moe_load_max_over_mean", "moe_shared_applications",
        "moe_window_rows", "moe_window_short_applications",
    ],
    # The family of PR 64, as it came: Nemotron-3's four of the mixer,
    # and the sublayers that follow every mixer; PR 65's count of the
    # mixers whose scan its kernels ran (Nemotron-3's list too).
    "granite4": [
        "attention_unrotated_applications", "conv_kernel_applications",
        "mlp_applications",
        "ssm_applications", "ssm_chunks", "ssm_kernel_applications",
        "ssm_resets_per_row", "ssm_state_bytes_per_row",
    ],
    # The family of PR 68, as it came (two of sixteen held under three
    # chosen: a window of one rung; the KDA layers', the latent
    # layer's, the router's groups').
    "ling3": [
        "attention_latent_applications", "conv_kernel_applications",
        "experts_held_rows_mean", "kda_applications", "kda_chunks",
        "kda_gate_at_floor_share", "kda_kernel_applications",
        "kda_log_decay_mean",
        "kda_log_decay_min", "kda_resets_per_row",
        "kda_state_bytes_per_row", "kda_sub_blocks",
        "moe_assignments", "moe_bias_abs_max", "moe_bias_steps",
        "moe_held_assignments", "moe_held_load_max_over_mean",
        "moe_load_max_over_mean", "moe_shared_applications",
        "moe_window_rows", "moe_window_short_applications",
        "router_group_load_max_share",
    ],
}
_HELD = {
    "ling3": dict(expert_share=(0, 8)),
    "trinity": dict(expert_share=(0, 8)),
    "xing4": dict(expert_share=(0, 8)),
    "mellum2": dict(expert_share=(1, 4)),
    "kanana2": dict(expert_share=(0, 8)),
    "nemotron3": dict(expert_share=(1, 8), mixer_share=(1, 2)),
    "qwen3next": dict(expert_share=(1, 4)),
    "lfm2": dict(expert_share=(1, 4)),
}


@pytest.mark.parametrize("family", list(STATS_AT_PR_44))
def test_update_stats_and_gauges_are_the_parents(family):
    """The update's stats carry the keys they carried when `learner.py`
    folded four collections by name, and polybeast's monitor makes the
    gauges it made from its list of 24: `<family>.<name>` for every
    counter, none for a loss. The keys alone: nothing is computed."""
    if family == "deep":
        model = create_model("deep", num_actions=A, use_lstm=True)
        batch = scaffold.learner_batch(0, [])
        batch["frame"] = jnp.zeros((6, B, 84, 84, 4), jnp.uint8)
        params = jax.eval_shape(
            model.init,
            {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
            batch, model.initial_state(B),
        )
    else:
        model, params = scaffold.build(family, **_HELD.get(family, {}))
        batch = scaffold.learner_batch(0, [], t=scaffold.FAMILIES[family].t)
    hp = learner_lib.HParams(
        batch_size=B, unroll_length=batch["done"].shape[0] - 1
    )
    optimizer = learner_lib.make_optimizer(hp)
    _, _, stats = jax.eval_shape(
        learner_lib.update_body(model, optimizer, hp),
        params, jax.eval_shape(optimizer.init, params), batch,
        model.initial_state(B),
    )
    counters = STATS_AT_PR_44[family]
    assert sorted(stats) == sorted(_LOSSES + counters)
    gauges = {model_stats.gauge_name(key) for key in stats} - {None}
    assert sorted(gauges) == sorted(
        name.replace("_", ".", 1) for name in counters
    )


_ENDS = [(4, 0), (7, 0), (8, 0), (10, 0), (0, 1), (5, 1)]


def test_the_three_folds_on_two_layers_of_each_kind():
    """Two periods of the toy nemotron3 (`*EM*EM`: two expert layers,
    two Mamba layers): `moe_assignments` is the two layers' SUM,
    `moe_load_max_over_mean` the worse layer's (MAX), `ssm_chunks` what
    both say alike (the SAME: 3, not 6), read against what each layer
    sowed."""
    model, params = scaffold.build("nemotron3", num_layers=6)
    t = scaffold.FAMILIES["nemotron3"].t
    _, sown = scaffold.apply(
        model, sample_action=False, mutable=model_stats.COLLECTIONS
    )(params, scaffold.inputs(3, _ENDS, t=t), model.initial_state(B))
    assert sorted(sown) == ["stats_max", "stats_same", "stats_sum"]

    def of_layers(layers, fold, name):
        return [
            float(sown["stats_" + fold][f"block_{layer}"][name])
            if inner is None
            else float(sown["stats_" + fold][f"block_{layer}"][inner][name])
            for layer, inner in layers
        ]

    stats = {k: float(v) for k, v in model_stats.folded(sown).items()}
    experts, mixers = [(1, "moe"), (4, "moe")], [(2, None), (5, None)]
    rows = of_layers(experts, "sum", "moe_assignments")
    assert rows == [3.0 * t * B] * 2
    assert stats["moe_assignments"] == sum(rows)
    loads = of_layers(experts, "max", "moe_load_max_over_mean")
    assert loads[0] != loads[1]
    assert stats["moe_load_max_over_mean"] == max(loads)
    assert of_layers(mixers, "same", "ssm_chunks") == [3.0, 3.0]
    assert stats["ssm_chunks"] == 3.0
    assert stats["ssm_applications"] == sum(
        of_layers(mixers, "sum", "ssm_applications")
    ) == 2.0
