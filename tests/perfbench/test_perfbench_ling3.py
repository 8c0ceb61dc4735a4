"""The cell `ling3_policy.learner`: its files, the configuration against
the catalog's row, the learner driver tiny on the CPU with the family's
widths shrunk (control flow, not speed), the reference seeing a fault
planted in the KDA mixer, the latent layer's gate and the router's
groups, and the counts behind its shares of a peak against hand counts.
Entries are found BY NAME, never as a list's last: the next
configuration's come after this one's."""

import importlib
import json
import os

import numpy as np
import pytest

from perfbench import flops, flops_ling3, manifest
from perfbench.drivers import learner as learner_driver

CELL = "ling3_policy.learner"
CONFIG = "ling3_flash_policy"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("mfu_pct.ling3", "hbm_bw_pct.ling3")
# What the family's table of widths is shrunk to, and the configuration
# keys that state the same sizes to the reference and the counts: at
# the published widths the 794M parameters with their gradients and
# optimizer state are 9.5 GB, which tier-1 must not allocate.
SMALL_FAMILY = dict(
    d_model=32, layer_group_size=2, dense_layers=1, num_heads=4,
    head_dim=8, chunk_size=4, sub_chunk=2, latent_rank=12, nope_head_dim=8,
    rope_head_dim=4, value_head_dim=6, mlp_width=48, num_experts=16,
    experts_per_token=3, expert_width=10, shared_width=12, n_group=4,
    topk_group=2,
)
SMALL_CONFIG = dict(
    hidden_size=32, layer_group_size=2, first_k_dense_replace=1,
    num_attention_heads=4, head_dim=8, kv_lora_rank=12, qk_nope_head_dim=8,
    qk_rope_head_dim=4, rotary_dim=4, v_head_dim=6, intermediate_size=48,
    published_num_experts=16, num_experts=4, expert_share=[1, 4],
    num_experts_per_tok=3, moe_intermediate_size=10,
    moe_shared_expert_intermediate_size=12, n_group=4, topk_group=2,
    # Published layer 0 (dense, KDA under a group of two), then `K M`.
    num_hidden_layers=3, layers_run=[0, 2, 3], memory_len=7,
    # 10 steps: two whole chunks of 4 and one padded.
    unroll_length=9, batch_size=4, frame_shape=[8, 8, 4],
    program_argv=["--model", "ling3", "--num_layers", "3",
                  "--memory_len", "7", "--expert_share", "1/4",
                  "--remat", "all", "--total_steps", "36"],
)
# The catalog row's `config`, copied here so that the test does not
# need the guide's file (it is checked against it where that is there).
_LIMITS = [0] * 35 + [4] * 7
_SHARED_LIMITS = [0] * 34 + [5] * 6 + [7] * 2
PUBLISHED_CONFIG = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560,
    "intermediate_size": 6144, "first_k_dense_replace": 2,
    "max_position_embeddings": 131072, "moe_intermediate_size": 768,
    "num_experts_per_tok": 8, "num_attention_heads": 32,
    "q_lora_rank": None, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts": 512,
    "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False,
    "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": _LIMITS,
    "share_expert_swiglu_limit_list": _SHARED_LIMITS,
}
REDUCED = {"num_hidden_layers": 7, "num_experts": 8}


def _config_file():
    with open(os.path.join(manifest.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture
def tiny(monkeypatch):
    from torchbeast_tpu.models import ling3

    monkeypatch.setattr(
        ling3, "PUBLISHED", dict(ling3.PUBLISHED, **SMALL_FAMILY)
    )
    cell = manifest.load_cell(CELL)
    return cell._replace(
        config=dict(cell.config, **SMALL_CONFIG),
        traffic=dict(cell.traffic, reference_rows=2, warmup_steps=2),
    )


def test_cell_loads_with_all_its_files():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config_name == CONFIG
    assert cell.traffic_name == "learner"
    assert cell.traffic == manifest.load_cell(
        "qwen3next_policy.learner"
    ).traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_frames_per_s", "peak_hbm_gib", "setup_s",
    }
    assert {m["name"] for m in cell.per_layer} == {
        "update_device_ms.learn", "device_idle_pct.learn", *METRICS,
    }
    importlib.import_module("perfbench.reference." + cell.config["reference"])
    benchmark = manifest.load_benchmark()
    # Each of this PR's entries is there ONCE, wherever it stands.
    assert [c["name"] for c in benchmark["configs"]].count(CONFIG) == 1
    assert [w["name"] for w in benchmark["workloads"]].count(CELL) == 1
    names = [m["name"] for m in benchmark["per_layer"]]
    assert all(names.count(metric) == 1 for metric in METRICS)
    listed = {
        m["name"] for m in benchmark["end_to_end"] + benchmark["per_layer"]
        if CELL in m.get("workloads", [])
    }
    assert listed == {
        "learn_frames_per_s", "peak_hbm_gib", "update_device_ms.learn",
        "device_idle_pct.learn", *METRICS,
    }
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        assert m.get("workloads", []).count(CELL) <= 1
        if m["name"] in METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "learn_frames_per_s"
    # No other cell reports this cell's two.
    for other in benchmark["workloads"]:
        if other["name"] != CELL:
            assert not set(METRICS) & {
                m["name"]
                for m in manifest.load_cell(other["name"]).per_layer
            }
    entry = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "a decay a channel" in entry["why"]
    config = next(c for c in benchmark["configs"] if c["name"] == CONFIG)
    assert len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "num_experts"]
    assert config["source"] == _config_file()["source"]
    # No cell takes four chips for this one's sake.
    assert sum(w["chips"] == 4 for w in benchmark["workloads"]) == 1


def test_config_equals_the_catalog_row_outside_reduced():
    """Every key of the catalog's row under its own key; depth and the
    experts held the two things cut, each stated beside the published
    value and the deployment. No width, head count, rank, group count or
    experts a token differs from the row."""
    config = _config_file()
    for key, value in PUBLISHED_CONFIG.items():
        assert config[key] == REDUCED.get(key, value), key
        if key in REDUCED:
            assert config["published_" + key] == value
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows if r["name"] == "Ling-3.0-flash-VL")
        assert row["config"] == PUBLISHED_CONFIG
        assert config["source"] == row["source_url"]
    # The cut: published layer 1 (the last leading dense one, KDA), then
    # the whole group 6-11, `K K K K K M`.
    assert config["layers_run"] == [1, 6, 7, 8, 9, 10, 11]
    group = config["layer_group_size"]
    assert [(l + 1) % group == 0 for l in config["layers_run"]] == (
        [False] * 6 + [True]
    )
    assert [
        l < config["first_k_dense_replace"] for l in config["layers_run"]
    ] == [True] + [False] * 6
    # The swiglu limits are 0 in every layer of the cut.
    for limits in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert not any(config[limits][l] for l in config["layers_run"])
    assert config["expert_share"] == [0, 64]
    assert config["expert_share"][1] * config["num_experts"] == 512
    assert "sixty-four chips" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    # 256 steps: four whole chunks of 64, each four sub-blocks of 16.
    assert (config["batch_size"], config["unroll_length"]) == (8, 255)
    assert (config["unroll_length"] + 1) % config["chunk_size"] == 0
    assert config["chunk_size"] % config["sub_chunk"] == 0
    assert config["sub_chunk"] * -config["kda_lower_bound"] <= 80
    assert config["memory_len"] == 1023
    assert set(config["reduced_why"]) == set(REDUCED)
    for key in (
        "kda_gate", "chunk_size", "initialisation", "use_qk_norm", "rotary",
        "linear_silu", "output_gate", "selection", "swiglu_limits",
        "not_run", "matmul_precision", "episode_ends", "memory_len",
        "observation_encoder", "unroll_length_and_batch_size",
        "learning_rate_schedule", "unused_keys",
    ):
        assert key in config["assumed"], key
    assert "32 rows" in config["assumed"]["unroll_length_and_batch_size"]
    assert "GiB" in config["fit"] and "no fallback taken" in config["fit"]
    assert "793,733,063" in config["reduced_why"]["num_experts"]
    assert "793,692,096" in config["reduced_why"]["num_experts"]


def test_published_table_equals_the_file():
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.models import ling3

    config = PUBLISHED_CONFIG
    assert ling3.PUBLISHED == {
        "d_model": config["hidden_size"],
        "num_layers": config["num_hidden_layers"],
        "layer_group_size": config["layer_group_size"],
        "num_heads": config["num_attention_heads"],
        "head_dim": config["head_dim"],
        "conv_kernel": config["short_conv_kernel_size"],
        "safe_gate": config["kda_safe_gate"],
        "gate_lower_bound": float(config["kda_lower_bound"]),
        "latent_rank": config["kv_lora_rank"],
        "nope_head_dim": config["qk_nope_head_dim"],
        "rope_head_dim": config["qk_rope_head_dim"],
        "value_head_dim": config["v_head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "dense_layers": config["first_k_dense_replace"],
        "mlp_width": config["intermediate_size"],
        "num_experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["moe_shared_expert_intermediate_size"],
        "n_group": config["n_group"],
        "topk_group": config["topk_group"],
        "renormalise": config["norm_topk_prob"],
        "routed_scaling": config["routed_scaling_factor"],
        "rms_norm_eps": config["rms_norm_eps"],
        # ASSUMED (the row has no key): the file's `assumed` says so.
        "chunk_size": 64, "sub_chunk": 16,
    }
    assert config["rotary_dim"] == config["qk_rope_head_dim"]
    assert config["num_kv_heads_for_linear_attn"] == 0
    assert config["q_lora_rank"] is None
    # The file's argv builds the cut the file states.
    file = _config_file()
    assert (file["chunk_size"], file["sub_chunk"]) == (64, 16)
    flags = monobeast.make_parser().parse_args(file["program_argv"])
    model, _ = monobeast._init_model_and_params(
        flags, 6, 8, (84, 84, 4), init_params=False
    )
    assert model.num_layers == file["num_hidden_layers"]
    assert [model.is_latent(layer) for layer in range(7)] == (
        [False] * 6 + [True]
    )
    assert model.leading_dense_layers() == 1
    assert model.held_experts() == (0, file["num_experts"])
    assert model.memory_len == file["memory_len"]
    assert model.bias_update_rate == file["bias_update_rate"]
    assert model.remat is True
    hp = monobeast.hparams_from_flags(monobeast.make_parser().parse_args(
        file["program_argv"] + ["--unroll_length", "255", "--batch_size", "8"]
    ))
    assert learner_lib.updates_horizon(hp) == 1
    # What the file says of the precision is what the family runs at.
    assert f"`{model.matmul_precision}`" in file["assumed"]["matmul_precision"]


def test_counts_against_hand_counts():
    """`flops_ling3.py` on the configuration's own file, against counts
    made by hand from the row."""
    config = _config_file()
    tokens = 256 * 8
    kda = (
        4 * 2560 * 4096 + 2 * 2560 * 32  # q, k, v, f; beta and the gate
        + 4096 * 2560 + 3 * 4096 * 4 + 32 + 4096 + 128
    )
    latent = (
        2560 * 6144 + 2560 * 576 + 512 + 512 * 8192 + 2560 * 32
        + 4096 * 2560
    )
    outside, expert = 2560 * 512 + 512 + 3 * 2560 * 768, 3 * 2560 * 768
    dense = 3 * 2560 * 6144
    assert (kda, latent, outside, expert, dense) == (
        52_646_048, 31_965_696, 7_209_472, 5_898_240, 47_185_920
    )
    assert flops_ling3.kda_param_count(config) == 2560 + kda
    assert flops_ling3.latent_param_count(config) == 2560 + latent
    assert flops_ling3.moe_param_count(config) == (
        2560 + outside + 8 * expert
    )
    # The issue's count (mixers, feed-forward parts, their norms, the
    # projection's matrix, the final norm), and with the projection's
    # bias, the side inputs' projection and the heads what the chip
    # holds.
    trunk = (
        6 * kda + latent + dense + 6 * (outside + 8 * expert)
        + 28_224 * 2560 + 15 * 2560
    )
    assert trunk == 793_692_096
    assert flops_ling3.param_count(config) == (
        trunk + 2560 + 7 * 2560 + 2560 + 2560 * 7 + 7
    ) == 793_733_063 == config["param_count"]
    parts = flops_ling3.forward_flops_per_step(config)
    assert parts["kda_in_proj"] == 6 * tokens * 2 * 2560 * (16_384 + 64)
    assert parts["kda_out_proj"] == 6 * tokens * 2 * 4096 * 2560
    # The recurrence over [128, 128] a head: the decay of every entry,
    # the read, the rank-one update, the output.
    assert parts["kda_scan"] == 6 * tokens * 32 * 128 * 128 * 7
    assert parts["kda_conv"] == 6 * tokens * 2 * 4 * 12_288
    assert parts["mlp"] == tokens * 2 * 3 * 2560 * 6144
    assert parts["router"] == 6 * tokens * 2 * 2560 * 512
    # 2,048 x 8 / 512 = 32 rows an expert held, 8 held, six layers.
    assert parts["experts"] == 6 * 8 * 32 * 2 * 3 * 2560 * 768
    assert parts["shared"] == 6 * tokens * 2 * 3 * 2560 * 768
    # A query at step t sees the M - t slots still in its band, and
    # itself and the t steps of the unroll before it.
    assert parts["cache_leg"] == 8 * 2 * 32 * (576 + 512) * sum(
        1023 - t for t in range(256)
    )
    assert parts["unroll_leg"] == 8 * 2 * 32 * (192 + 128) * sum(
        t + 1 for t in range(256)
    )
    forward = sum(parts.values())
    assert 2.29e12 < forward < 2.32e12
    # The six KDA mixers owe more than half, nearly all of it their
    # projections; the recurrence itself 2%.
    mixers = sum(v for k, v in parts.items() if k.startswith("kda_"))
    assert 0.57 < mixers / forward < 0.59
    assert parts["kda_scan"] / forward < 0.02
    latent_layer = sum(
        parts[k] for k in ("qkvo", "absorb", "cache_leg", "unroll_leg")
    )
    assert 0.11 < latent_layer / forward < 0.13
    assert parts["experts"] / forward < 0.01
    assert flops_ling3.train_flops_per_step(config) == (
        3 * forward - parts["projection"] - parts["cache_leg"]
    ) == 6_490_391_838_720
    # Six states [32, 128, 128] with tails [3, 12288] and one cache of
    # 1,023 slots of 512 + 64 with its validity, 8 rows, f32.
    assert flops_ling3.state_bytes(config) == 8 * (
        13_467_648 + 4 * 1023 * 577
    ) == 126_629_856
    assert flops_ling3.least_bytes_per_step(config) == (
        24 * 793_733_063 + 2 * 126_629_856
    ) == 19_302_853_224
    # The widened kernel, one call: 1,024 (row, chunk, head) cells; P =
    # 2 x 64 x 128 x 128, R = 2 x 64 x 64 x 128; three products forward,
    # 8 P + 2 R in the reverse walk and 2 P for each of three chunks of
    # four whose entering state is made again.
    P, R, cells = 2_097_152, 1_048_576, 8 * 4 * 32
    assert flops_ling3.chunk_pass_flops(config, backward=False) == (
        cells * (3 * P + R)
    ) == 7_516_192_768
    assert flops_ling3.chunk_pass_flops(config, backward=True) == (
        cells * (8 * P + 2 * R) + 8 * 3 * 32 * 2 * P
    ) == 22_548_578_304
    # q, k, A (padded to 128 lanes), U, Kd, O at 8,192 floats a cell,
    # the per-step rows and the hand-on at 1,024; the first and the last
    # state [8, 32, 128, 128].
    assert flops_ling3.chunk_pass_bytes(config, backward=False) == 4 * (
        cells * (6 * 8192 + 2 * 1024) + 2 * 8 * 32 * 128 * 128
    ) == 243_269_632
    assert flops_ling3.chunk_pass_bytes(config, backward=True) == 568_328_192
    # The metrics' scales are these counts.
    for name, want in (
        ("mfu_pct.ling3", 100 * 6_490_391_838_720),
        ("hbm_bw_pct.ling3", 100 * 19_302_853_224 / 819e9),
    ):
        with open(os.path.join(
            manifest.HERE, "layer_metrics", name + ".json"
        )) as f:
            assert json.load(f)["args"]["scale"] == pytest.approx(want)
    # drivers/learner.py calls flops.train_flops_per_step for every cell.
    assert config["trunk_channels"] == [] and config["use_lstm"] is False
    assert flops.forward_flops_per_frame(config)["fc"] == 2 * 28_224 * 2560


def test_reference_agrees_with_the_program(tiny):
    """Same seeded weights, same rows: on the CPU both compute in f32,
    so they agree to f32 rounding, far inside the chip's tolerance."""
    import jax

    *_, check = learner_driver.build(tiny, 2**31 + 5, jax.devices()[:1])
    report = check(first_step_loss=None)
    assert report["ok"]
    assert report["rel_diff"] < 1e-5
    assert np.isfinite(report["reference_loss"])


@pytest.mark.parametrize("fault", [
    None, "decay_a_head_not_a_channel", "state_not_reset_at_done",
    "conv_reads_across_an_episode_end", "latent_head_gate_left_out",
    "router_without_its_groups",
])
def test_reference_sees_a_fault_planted_in_the_program(
    tiny, fault, monkeypatch
):
    """The driver's comparison (the system's loss against the
    reference's, over the reference's scale, held to the driver's
    tolerance) on what the cell's traffic leaves out: states an actor
    carried, and decays at which the carried state is a large part of a
    KDA layer's output (as seeded nearly every channel's log-decay is ~0:
    the comparison then hardly reads the gate). The program as it is
    passes; ONE decay a head where the row has one a channel, a scan
    that does not reset at `done`, a convolution that reads across an
    episode end, the latent layer's head gate left out, a router that
    chooses without its groups: each is seen."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.models import kanana2, ling3

    config = tiny.config
    _, params, _, batch, state, _ = learner_driver.build(
        tiny, 7, jax.devices()[:1]
    )
    inner = dict(params["params"])
    for name in ("block_0", "block_2"):
        # Log-decays spread over (-5, 0), a channel each.
        spread = 4.0 + 2.0 * jax.random.normal(
            jax.random.PRNGKey(len(name)), inner[name]["dt_bias"].shape
        )
        inner[name] = dict(
            inner[name], dt_bias=inner[name]["dt_bias"] + spread,
        )
    params = {"params": inner}

    def build_model():
        flags = monobeast.make_parser().parse_args(
            config["program_argv"][:-4]  # no --remat: modules as they are
            + ["--unroll_length", "9", "--batch_size", "4"]
        )
        model, _ = monobeast._init_model_and_params(
            flags, config["num_actions"], 4, (8, 8, 4), init_params=False
        )
        return model, monobeast.hparams_from_flags(flags)

    # What an actor would hold: one unroll in, by the program as it is
    # written. Of this batch's 40 steps a third end an episode, so that
    # what an end does is a large part of the loss.
    batch = dict(batch, done=jax.random.bernoulli(
        jax.random.PRNGKey(3), 0.35, batch["done"].shape
    ))
    inputs = {
        k: batch[k] for k in ("frame", "reward", "done", "last_action")
    }
    jitted = jax.jit(lambda p, x, s: build_model()[0].apply(
        p, x, s, sample_action=False
    ))
    _, state = jitted(params, inputs, state)
    assert all(np.any(leaf) for leaf in jax.tree_util.tree_leaves(state))

    if fault == "decay_a_head_not_a_channel":
        right_gate = ling3.kda_gate
        monkeypatch.setattr(
            ling3, "kda_gate",
            lambda a, A_log, low, safe: jnp.broadcast_to(jnp.mean(
                right_gate(a, A_log, low, safe), axis=-1, keepdims=True
            ), a.shape),
        )
    elif fault == "state_not_reset_at_done":
        right_scan = ling3.kda_scan
        monkeypatch.setattr(
            ling3, "kda_scan",
            lambda q, k, v, g, beta, state, done, chunk, sub: right_scan(
                q, k, v, g, beta, state, jnp.zeros_like(done), chunk, sub
            ),
        )
    elif fault == "conv_reads_across_an_episode_end":
        right_conv = ling3.conv_over_episodes
        monkeypatch.setattr(
            ling3, "conv_over_episodes",
            lambda inputs, tail, done, taps, bias: right_conv(
                inputs, tail, jnp.zeros_like(done), taps, bias
            ),
        )
    elif fault == "latent_head_gate_left_out":
        # models/kanana2.py's one sigmoid is the head gate's.
        monkeypatch.setattr(
            kanana2, "nn", type("GateOpen", (), {
                "__getattr__": lambda self, name: (
                    jnp.ones_like if name == "sigmoid" else getattr(nn, name)
                ),
            })(),
        )
    elif fault == "router_without_its_groups":
        monkeypatch.setattr(
            ling3, "PUBLISHED",
            dict(ling3.PUBLISHED, n_group=1, topk_group=1),
        )
    model, hp = build_model()
    reference = importlib.import_module(
        "perfbench.reference." + config["reference"]
    )
    system_loss = jax.jit(
        lambda p: learner_lib.compute_loss(model, p, batch, state, hp)[0]
    )
    reference_loss = jax.jit(
        lambda p: reference.loss_and_scale(p, batch, state, config)
    )
    got = float(system_loss(params))
    want, scale = map(float, reference_loss(params))
    rel = abs(got - want) / scale
    if fault is None:
        assert rel < 1e-5, rel
    else:
        assert rel > learner_driver.REFERENCE_RTOL, (fault, rel)
