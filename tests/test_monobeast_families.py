"""`--model <family>` through `monobeast.main`, a policy family an id
(tests/family_scaffold.py has the rule for the next family); a file of
its own so that no worker's chain is these cases AND the conv smokes
(ISSUE 49)."""

import numpy as np
import pytest

from tests.test_monobeast import make_flags
from torchbeast_tpu import monobeast


def _mellum2_through_main(stats, share=True):
    # 2 rows x 6 steps x top 2 x 4 layers, over all 8 experts.
    assert stats["moe_assignments"] == 2 * 6 * 2 * 4
    assert ("moe_held_assignments" in stats) == share


def _kanana2_through_main(stats):
    assert stats["attention_latent_applications"] == 3
    # A latent [6, 16], a rope key [6, 4] and a validity column, f32,
    # for each of the 3 caches.
    assert stats["attention_latent_cache_bytes_per_row"] == (
        3 * 4 * 6 * (16 + 4 + 1)
    )
    assert stats["moe_bias_steps"] == 2
    assert stats["moe_shared_applications"] == 2
    # (The mock env's frames are all alike: the experts held may draw
    # every row or none.)
    assert 0 <= stats["moe_held_assignments"] <= stats["moe_assignments"]
    # At least one update moved them: 0.001 a step from zero.
    assert stats["moe_bias_abs_max"] >= 0.001 - 1e-9
    assert stats["aux_loss"] == 0.0


def _xing4_through_main(stats):
    assert stats["attention_latent_applications"] == 3
    # Six sublayers a step, a row's four streams of [6, 32] float32.
    assert stats["hc_bytes_per_row"] == 6 * 4 * 4 * 6 * 32
    # Near the start: H_post about 1, H_res doubly stochastic.
    assert 0.8 < stats["hc_post_mean"] < 1.2
    assert 0 <= stats["hc_res_row_error_max"] < 1e-3
    assert stats["moe_bias_steps"] == 2
    assert stats["moe_shared_applications"] == 2
    assert 0 <= stats["moe_held_assignments"] <= stats["moe_assignments"]
    assert stats["aux_loss"] == 0.0


def _trinity_through_main(stats):
    # A dense layer and a period of two: three gates, one layer
    # un-rotated.
    assert stats["attention_gated_applications"] == 3
    assert stats["attention_unrotated_applications"] == 1
    assert stats["moe_bias_steps"] == 2
    assert stats["moe_shared_applications"] == 2
    assert 0 <= stats["moe_held_assignments"] <= stats["moe_assignments"]
    assert stats["aux_loss"] == 0.0


def _granite4_through_main(stats):
    # A period cut to `M * M`: two mixers whole around the attention
    # layer, a SwiGLU after each of the three.
    assert stats["ssm_applications"] == 2
    assert stats["ssm_chunks"] == 2  # 6 steps in chunks of 4
    # Two layers' [8, 8, 6] states and tails of 3 inputs over 8 x 8 +
    # 2 x 6 channels, f32.
    assert stats["ssm_state_bytes_per_row"] == 2 * 4 * (8 * 8 * 6 + 3 * 76)
    assert stats["ssm_resets_per_row"] >= 0
    assert stats["mlp_applications"] == 3
    assert stats["attention_unrotated_applications"] == 1
    assert stats["aux_loss"] == 0.0


def _ling3_through_main(stats):
    # The dense layer and a period cut to `K M`: two KDA mixers, one
    # latent layer, the router's four groups.
    assert stats["kda_applications"] == 2
    assert stats["kda_chunks"] == 2  # 6 steps in chunks of 4
    assert stats["kda_sub_blocks"] == 2
    # Two layers' [4, 8, 8] states and tails of 3 inputs over 3 x 4 x 8
    # channels, f32.
    assert stats["kda_state_bytes_per_row"] == 2 * 4 * (4 * 8 * 8 + 3 * 96)
    assert stats["kda_resets_per_row"] >= 0
    assert -5.0 <= stats["kda_log_decay_min"] <= 0.0
    assert stats["attention_latent_applications"] == 1
    assert stats["moe_bias_steps"] == 2
    assert stats["moe_shared_applications"] == 2
    assert 0.25 <= stats["router_group_load_max_share"] <= 1.0
    assert 0 <= stats["moe_held_assignments"] <= stats["moe_assignments"]
    assert stats["aux_loss"] == 0.0


def _nemotron3_through_main(stats):
    assert stats["ssm_applications"] == 1
    assert stats["ssm_chunks"] == 2  # 6 steps in chunks of 4
    # The held half: 4 heads' [4, 6] states and a tail of 3 inputs over
    # 4 x 4 + 2 x 2 x 6 channels, f32.
    assert stats["ssm_state_bytes_per_row"] == 4 * (4 * 4 * 6 + 3 * 40)
    assert stats["ssm_resets_per_row"] >= 0
    assert stats["moe_latent_applications"] == 1
    assert stats["moe_shared_applications"] == 1
    assert stats["moe_bias_steps"] == 1
    assert 0 <= stats["moe_held_assignments"] <= stats["moe_assignments"]
    assert stats["moe_bias_abs_max"] >= 0.001 - 1e-9
    assert stats["aux_loss"] == 0.0


def _qwen3next_through_main(stats):
    assert stats["delta_applications"] == 1
    assert stats["delta_chunks"] == 2  # 6 steps in chunks of 4
    # 4 value heads' [6, 5] matrix states and a tail of 3 inputs over
    # 2 x 2 x 6 + 4 x 5 channels, f32.
    assert stats["delta_state_bytes_per_row"] == 4 * (4 * 6 * 5 + 3 * 44)
    assert stats["delta_resets_per_row"] >= 0
    assert stats["attention_gated_applications"] == 1
    assert stats["moe_shared_applications"] == 2
    assert 0 <= stats["moe_held_assignments"] <= stats["moe_assignments"]
    assert stats["aux_loss"] >= 0.001 * 2 * 0.99  # two layers' balance


def _lfm2_through_main(stats):
    assert stats["conv_layers"] == 2
    # Two conv layers' tails of 2 products over 32 channels, f32.
    assert stats["conv_state_bytes_per_row"] == 2 * 4 * 2 * 32
    assert stats["conv_resets_per_row"] >= 0
    assert stats["moe_bias_steps"] == 2
    assert 0 <= stats["moe_held_assignments"] <= stats["moe_assignments"]
    assert stats["moe_bias_abs_max"] >= 0.001 - 1e-9
    assert stats["aux_loss"] == 0.0


def _phi4flash_through_main(stats):
    assert stats["ssm_applications"] == 2
    # Two Mamba layers' states [4, 64] and tails of 3 over 64 channels.
    assert stats["ssm_state_bytes_per_row"] == 2 * 4 * (4 + 3) * 64
    assert stats["ssm_resets_per_row"] >= 0
    assert stats["shared_memory_readers"] == 1
    assert stats["shared_kv_readers"] == 1
    assert stats["attention_differential_applications"] == 3
    assert stats["aux_loss"] == 0.0


def _ouro_through_main(stats):
    assert stats["loop_passes"] == 3
    assert stats["loop_block_applications"] == 6
    # k, v [6, 4, 8] and a validity column, f32, for each of 6 caches.
    assert stats["loop_cache_bytes_per_row"] == 6 * 4 * 6 * (2 * 32 + 1)
    assert 1.0 <= stats["loop_expected_exit_pass"] <= 3.0
    assert 0.0 <= stats["loop_exit_p_last"] <= 1.0


_MELLUM2_WIDTHS = dict(
    d_model=32, num_heads=4, kv_heads=2, head_dim=8, sliding_window=4,
    num_experts=8, experts_per_token=2, expert_width=16,
)
# A family a row (a `model_config` PR adds one: tests/family_scaffold.py):
# what its `PUBLISHED` table is shrunk to, its flags, and what the last
# update's stats must say.
#  mellum2: window 4, so the sliding layers carry 3 slots and the full
#   layer 6; with and without a share of the experts.
#  kanana2: a dense layer and two MoE layers, share 1 of 4, the blocks
#   rematerialised, the selection biases moved by the load after every
#   optimizer step and carried by the checkpoint.
#  nemotron3: one period of attention, latent MoE and Mamba-2, half of
#   each mixer's heads and a quarter of the experts: acting through the
#   rolling cache AND the Mamba state with its conv tail, unrolls of 5
#   scanned in chunks of 4.
#  qwen3next: one Gated DeltaNet layer and one gated attention layer, a
#   quarter of the experts: acting through the MATRIX state with its
#   conv tail AND the rolling cache of un-rotated keys, unrolls of 5
#   scanned in chunks of 4.
#  lfm2: the dense conv layer and a period cut to `A c`, a quarter of
#   the experts: acting through the two-step tails (entries of ONE
#   leaf) AND the rolling cache of un-rotated keys, the biases moved by
#   the load after every optimizer step.
#  phi4flash: published layers 14-19: acting through two Mamba-1 states
#   with their tails and two windows of different lengths, the memory
#   and the full layer's keys and values handed on inside every act
#   step; the learner's updates scan in chunks, blocks rematerialised.
#  xing4: a dense layer and two MoE layers, share 1 of 4: acting at T=1
#   makes the four streams of every step and sums them inside the step;
#   compressed queries, the blocks rematerialised.
#  trinity: the dense layer and a period cut to `s F`, share 1 of 4:
#   acting at T=1 through two sliding caches of 3 slots (rotated where
#   scored) and a full one of 6 (read as it lies), the gate and the four
#   norms inside
#   every step, the blocks rematerialised, the biases moved by the load.
#  granite4: a period cut to `M * M`: acting at T=1 through two Mamba-2
#   states on ONE B/C group with their tails and a rolling cache of
#   keys without positions, a SwiGLU after every mixer, the four
#   multipliers inside every step, the blocks rematerialised; the
#   learner's updates scan in chunks.
#  ling3: the dense layer and a period cut to `K M`, share 1 of 4 (one
#   of the router's four groups): acting at T=1 through two KDA matrix
#   states with their tails (a chunk of one step: the recurrence, a
#   decay a channel) and a rolling latent cache under a gate a head;
#   the learner's updates scan in chunks of 4 from sub-blocks of 2, the
#   blocks rematerialised, the biases moved by the load.
#  ouro: 2 layers run 3 times, through 3 x 2 rolling caches.
THROUGH_MAIN = {
    "mellum2-all-experts": (
        "mellum2", _MELLUM2_WIDTHS, dict(num_layers=4),
        lambda stats: _mellum2_through_main(stats, share=False),
    ),
    "mellum2-share-1-of-4": (
        "mellum2", _MELLUM2_WIDTHS, dict(num_layers=4, expert_share="1/4"),
        _mellum2_through_main,
    ),
    "kanana2": (
        "kanana2",
        dict(
            d_model=32, num_heads=4, latent_rank=16, nope_head_dim=8,
            rope_head_dim=4, value_head_dim=8, mlp_width=48, num_experts=8,
            experts_per_token=2, expert_width=16,
        ),
        dict(num_layers=3, expert_share="1/4", remat="all"),
        _kanana2_through_main,
    ),
    "nemotron3": (
        "nemotron3",
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8, mamba_heads=8,
            mamba_head_dim=4, mamba_groups=4, state_size=6, chunk_size=4,
            num_experts=8, experts_per_token=3, expert_width=10,
            latent_width=12, shared_width=20, layer_period="*EM",
        ),
        dict(
            num_layers=3, expert_share="1/4", mixer_share="1/2", remat="all",
        ),
        _nemotron3_through_main,
    ),
    "qwen3next": (
        "qwen3next",
        dict(
            d_model=32, attention_interval=2, num_heads=4, kv_heads=2,
            head_dim=16, delta_key_heads=2, delta_value_heads=4,
            delta_key_dim=6, delta_value_dim=5, chunk_size=4, num_experts=8,
            experts_per_token=2, expert_width=10, shared_width=12,
        ),
        dict(num_layers=2, expert_share="1/4", remat="all"),
        _qwen3next_through_main,
    ),
    "lfm2": (
        "lfm2",
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8, dense_width=48,
            expert_width=10, num_experts=8, experts_per_token=2,
            layer_period=("full_attention", "conv"),
        ),
        dict(num_layers=3, expert_share="1/4", remat="all"),
        _lfm2_through_main,
    ),
    "phi4flash": (
        "phi4flash",
        dict(
            d_model=32, num_heads=8, num_key_value_heads=4,
            intermediate_size=48, sliding_window=4, d_state=4, dt_rank=2,
        ),
        dict(num_layers=6, remat="all"),
        _phi4flash_through_main,
    ),
    "xing4": (
        "xing4",
        dict(
            d_model=32, num_heads=4, latent_rank=16, query_rank=12,
            nope_head_dim=8, rope_head_dim=4, value_head_dim=8, mlp_width=48,
            num_experts=8, experts_per_token=2, expert_width=16,
        ),
        dict(num_layers=3, expert_share="1/4", remat="all"),
        _xing4_through_main,
    ),
    "trinity": (
        "trinity",
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8,
            sliding_window=4, mlp_width=48, num_experts=8,
            experts_per_token=2, expert_width=16, input_scale=32 ** 0.5,
            layer_period=("sliding_attention", "full_attention"),
            dense_layers=1,
        ),
        dict(num_layers=3, expert_share="1/4", remat="all"),
        _trinity_through_main,
    ),
    "granite4": (
        "granite4",
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8, mamba_heads=8,
            mamba_head_dim=8, state_size=6, chunk_size=4, mlp_width=48,
            layer_period=("mamba", "attention", "mamba"),
            layer_types=("mamba", "attention", "mamba") * 2,
        ),
        dict(num_layers=3, remat="all"),
        _granite4_through_main,
    ),
    "ling3": (
        "ling3",
        dict(
            d_model=32, layer_group_size=2, dense_layers=1, num_heads=4,
            head_dim=8, chunk_size=4, sub_chunk=2, latent_rank=12,
            nope_head_dim=8, rope_head_dim=4, value_head_dim=6, mlp_width=48,
            num_experts=16, experts_per_token=3, expert_width=10,
            shared_width=12, n_group=4, topk_group=2,
        ),
        dict(num_layers=3, expert_share="1/4", remat="all"),
        _ling3_through_main,
    ),
    "ouro": (
        "ouro",
        dict(d_model=32, num_heads=4, head_dim=8, mlp_width=48, passes=3),
        dict(num_layers=2, remat="all"),
        _ouro_through_main,
    ),
}


@pytest.mark.parametrize("case", list(THROUGH_MAIN))
def test_train_family_through_main(tmp_path, monkeypatch, case):
    """`--model <family>` on the normal path, the family's table
    shrunk: acting at T=1 through what the family carries (6-slot
    caches), unrolls of 5, updates, the checkpoint; the last update's
    stats carry the family's counters."""
    import importlib

    family, widths, flags, check = THROUGH_MAIN[case]
    module = importlib.import_module(f"torchbeast_tpu.models.{family}")
    monkeypatch.setattr(
        module, "PUBLISHED", dict(module.PUBLISHED, **widths)
    )
    stats = monobeast.main(make_flags(
        tmp_path, xpid=f"smoke-{family}", model=family, memory_len=6, **flags
    ))
    assert stats["step"] >= 40
    assert np.isfinite(stats["total_loss"])
    check(stats)
    assert (tmp_path / f"smoke-{family}" / "model.ckpt").exists()
