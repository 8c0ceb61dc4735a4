"""`--model <family>` through `polybeast.train`, a policy family an
id (tests/family_scaffold.py has the rule for the next family); a file
of its own, as tests/test_monobeast_families.py is."""

import numpy as np
import pytest

from tests.test_polybeast import make_flags
from torchbeast_tpu import polybeast


def _ouro_trained(stats):
    assert stats["loop_passes"] == 3
    assert stats["loop_block_applications"] == 6
    assert 1.0 <= stats["loop_expected_exit_pass"] <= 3.0


def _kanana2_trained(stats):
    assert stats["attention_latent_applications"] == 2
    assert stats["moe_bias_steps"] == 1
    assert stats["moe_bias_abs_max"] >= 0.001 - 1e-9


def _nemotron3_trained(stats):
    assert stats["ssm_applications"] == 1
    assert stats["moe_latent_applications"] == 1
    assert stats["moe_bias_steps"] == 1


def _qwen3next_trained(stats):
    assert stats["delta_applications"] == 1
    assert stats["attention_gated_applications"] == 1
    assert stats["moe_shared_applications"] == 2


def _lfm2_trained(stats):
    assert stats["conv_layers"] == 2
    assert stats["moe_bias_steps"] == 2
    assert stats["moe_bias_abs_max"] >= 0.001 - 1e-9


def _phi4flash_trained(stats):
    assert stats["ssm_applications"] == 2
    assert stats["shared_memory_readers"] == 1
    assert stats["shared_kv_readers"] == 1


def _xing4_trained(stats):
    assert stats["attention_latent_applications"] == 2
    assert stats["hc_bytes_per_row"] == 4 * 4 * 4 * 6 * 32
    assert 0.8 < stats["hc_post_mean"] < 1.2
    assert stats["moe_bias_steps"] == 1


def _granite4_trained(stats):
    assert stats["ssm_applications"] == 2
    assert stats["mlp_applications"] == 3
    assert stats["attention_unrotated_applications"] == 1


def _ling3_trained(stats):
    assert stats["kda_applications"] == 2
    assert stats["attention_latent_applications"] == 1
    assert stats["moe_bias_steps"] == 2
    assert 0.25 <= stats["router_group_load_max_share"] <= 1.0


def _trinity_trained(stats):
    assert stats["attention_gated_applications"] == 3
    assert stats["attention_unrotated_applications"] == 1
    assert stats["moe_bias_steps"] == 2
    assert stats["moe_shared_applications"] == 2


# A family a row (a `model_config` PR adds one: tests/family_scaffold.py):
# what its `PUBLISHED` table is shrunk to, its depth, and what the last
# update's stats must say.
#  ouro: 2 layers run 3 times; the state table's slots hold the 3 x 2
#   caches, an act step runs the three passes.
#  kanana2: the slots hold the latent caches (entries of two unequal
#   leaves), the learner's updates move the selection biases.
#  nemotron3: the slots hold both kinds of state (the attention layer's
#   window, the Mamba layer's state and conv tail; the MoE layer has
#   none), the learner's updates scan in chunks.
#  qwen3next: the slots hold a matrix state with its conv tail beside
#   the attention layer's window; the delta rule runs in chunks.
#  lfm2: the slots hold two conv layers' two-step tails (entries of one
#   leaf) beside the attention layer's window.
#  phi4flash: the slots hold two Mamba-1 states with their tails and two
#   windows, and nothing for the two layers that read another layer's
#   values: the act step hands those on inside itself.
#  xing4: the slots hold the latent caches as kanana2's; an act step
#   makes the four streams and sums them inside itself, so the table
#   carries nothing of them.
#  trinity: the slots hold two caches of 3 slots and one of 6; an act
#   step rotates the sliding layers' and reads the full layer's as it
#   lies.
#  granite4: the slots hold two Mamba-2 states with their conv tails
#   around the attention layer's window; the learner's updates scan in
#   chunks of 4.
#  ling3: the slots hold two KDA matrix states with their conv tails
#   beside the latent layer's window (entries of two unequal leaves);
#   an act step is a chunk of one step, the learner's updates scan in
#   chunks of 4 from sub-blocks of 2.
FAMILIES = {
    "ling3": (
        dict(
            d_model=32, layer_group_size=2, dense_layers=1, num_heads=4,
            head_dim=8, chunk_size=4, sub_chunk=2, latent_rank=12,
            nope_head_dim=8, rope_head_dim=4, value_head_dim=6, mlp_width=48,
            num_experts=16, experts_per_token=3, expert_width=10,
            shared_width=12, n_group=4, topk_group=2,
        ),
        3, _ling3_trained,
    ),
    "granite4": (
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8, mamba_heads=8,
            mamba_head_dim=8, state_size=6, chunk_size=4, mlp_width=48,
            layer_period=("mamba", "attention", "mamba"),
            layer_types=("mamba", "attention", "mamba") * 2,
        ),
        3, _granite4_trained,
    ),
    "trinity": (
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8,
            sliding_window=4, mlp_width=48, num_experts=8,
            experts_per_token=2, expert_width=16, input_scale=32 ** 0.5,
            layer_period=("sliding_attention", "full_attention"),
            dense_layers=1,
        ),
        3, _trinity_trained,
    ),
    "xing4": (
        dict(
            d_model=32, num_heads=4, latent_rank=16, query_rank=12,
            nope_head_dim=8, rope_head_dim=4, value_head_dim=8, mlp_width=48,
            num_experts=8, experts_per_token=2, expert_width=16,
        ),
        2, _xing4_trained,
    ),
    "phi4flash": (
        dict(
            d_model=32, num_heads=8, num_key_value_heads=4,
            intermediate_size=48, sliding_window=4, d_state=4, dt_rank=2,
        ),
        6, _phi4flash_trained,
    ),
    "ouro": (
        dict(d_model=32, num_heads=4, head_dim=8, mlp_width=48, passes=3),
        2, _ouro_trained,
    ),
    "kanana2": (
        dict(
            d_model=32, num_heads=4, latent_rank=16, nope_head_dim=8,
            rope_head_dim=4, value_head_dim=8, mlp_width=48, num_experts=8,
            experts_per_token=2, expert_width=16,
        ),
        2, _kanana2_trained,
    ),
    "nemotron3": (
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8, mamba_heads=8,
            mamba_head_dim=4, mamba_groups=4, state_size=6, chunk_size=4,
            num_experts=8, experts_per_token=3, expert_width=10,
            latent_width=12, shared_width=20, layer_period="*EM",
        ),
        3, _nemotron3_trained,
    ),
    "qwen3next": (
        dict(
            d_model=32, attention_interval=2, num_heads=4, kv_heads=2,
            head_dim=16, delta_key_heads=2, delta_value_heads=4,
            delta_key_dim=6, delta_value_dim=5, chunk_size=4, num_experts=8,
            experts_per_token=2, expert_width=10, shared_width=12,
        ),
        2, _qwen3next_trained,
    ),
    "lfm2": (
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8, dense_width=48,
            expert_width=10, num_experts=8, experts_per_token=2,
            layer_period=("full_attention", "conv"),
        ),
        3, _lfm2_trained,
    ),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_polybeast_train_family(tmp_path, monkeypatch, family):
    """`--model <family>` through the async driver, the family's table
    shrunk: the state table's slots hold what the family carries, the
    blocks are rematerialised, the learner's updates report the
    family's counters."""
    import importlib

    widths, layers, check = FAMILIES[family]
    module = importlib.import_module(f"torchbeast_tpu.models.{family}")
    monkeypatch.setattr(
        module, "PUBLISHED", dict(module.PUBLISHED, **widths)
    )
    stats = polybeast.train(make_flags(
        tmp_path, xpid=f"poly-{family}", model=family, num_layers=layers,
        memory_len=6, remat="all",
    ))
    assert stats["step"] >= 60
    assert np.isfinite(stats["total_loss"])
    check(stats)
    assert (tmp_path / f"poly-{family}" / "model.ckpt").exists()
