"""The cell `granite4_policy.learner`: its files, the configuration
against the catalog's row, the learner driver tiny on the CPU with the
family's widths shrunk (control flow, not speed), the reference seeing
a fault planted in each of the config's four multipliers and in the
mixer, and the counts behind its shares of a peak against hand counts.
Entries are found BY NAME, never as a list's last: the next
configuration's come after this one's."""

import importlib
import json
import os

import numpy as np
import pytest

from perfbench import flops, flops_granite4, manifest
from perfbench.drivers import learner as learner_driver

CELL = "granite4_policy.learner"
CONFIG = "granite4_h_micro_policy"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("mfu_pct.granite4", "hbm_bw_pct.granite4")
MAMBA, ATTENTION = "mamba", "attention"
# What the family's table of widths is shrunk to, and the configuration
# keys that state the same sizes to the reference and the counts: at
# the published widths the 804M parameters with their gradients and
# optimizer state are 9.7 GB, which tier-1 must not allocate.
SMALL_FAMILY = dict(
    d_model=32, num_heads=4, kv_heads=2, head_dim=8, mamba_heads=8,
    mamba_head_dim=8, state_size=6, chunk_size=4, mlp_width=48,
    layer_period=(MAMBA, ATTENTION, MAMBA),
    layer_types=(MAMBA, ATTENTION, MAMBA) * 2,
)
SMALL_CONFIG = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=6, mamba_chunk_size=4,
    shared_intermediate_size=48, intermediate_size=48,
    num_hidden_layers=3, layer_types=[MAMBA, ATTENTION, MAMBA],
    memory_len=7,
    # 10 steps: two whole chunks of 4 and one padded.
    unroll_length=9, batch_size=4, frame_shape=[8, 8, 4],
    program_argv=["--model", "granite4", "--num_layers", "3",
                  "--memory_len", "7", "--remat", "all",
                  "--total_steps", "36"],
)
# The catalog row's `config`, copied here so that the test does not
# need the guide's file (it is checked against it where that is there).
_PERIOD = [MAMBA] * 5 + [ATTENTION] + [MAMBA] * 4
PUBLISHED_CONFIG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": _PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
REDUCED = {"num_hidden_layers": 10, "layer_types": _PERIOD}


def _config_file():
    with open(os.path.join(manifest.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture
def tiny(monkeypatch):
    from torchbeast_tpu.models import granite4

    monkeypatch.setattr(
        granite4, "PUBLISHED", dict(granite4.PUBLISHED, **SMALL_FAMILY)
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
        "nemotron3_policy.learner"
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
    assert len(entry["why"]) <= 200 and "1 B/C group" in entry["why"]
    config = next(c for c in benchmark["configs"] if c["name"] == CONFIG)
    assert len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["source"] == _config_file()["source"]
    # No cell takes four chips for this one's sake.
    assert sum(w["chips"] == 4 for w in benchmark["workloads"]) == 1


def test_config_equals_the_catalog_row_outside_reduced():
    """Every key of the catalog's row under its own key; depth and the
    layers' kinds (the first ten of the published forty) the two things
    cut, each stated beside the published value and the deployment. No
    width, head count, state size or chunk differs from the row."""
    config = _config_file()
    for key, value in PUBLISHED_CONFIG.items():
        assert config[key] == REDUCED.get(key, value), key
        if key in REDUCED:
            assert config["published_" + key] == value
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows if r["name"] == "granite-4.0-h-micro")
        assert row["config"] == PUBLISHED_CONFIG
        assert config["source"] == row["source_url"]
    # The cut is the published layers 0-9, one period; the other three
    # periods repeat it letter for letter.
    assert config["layer_types"] == PUBLISHED_CONFIG["layer_types"][:10]
    assert config["layer_types"] * 4 == PUBLISHED_CONFIG["layer_types"]
    assert config["layers_run"] == list(range(10))
    assert "four pipeline stages" in config["deployment"]
    assert "stage 0" in config["deployment"]
    # 512 steps: two whole chunks of the published 256.
    assert (config["batch_size"], config["unroll_length"]) == (8, 511)
    assert (config["unroll_length"] + 1) % config["mamba_chunk_size"] == 0
    assert config["memory_len"] == 4095
    assert set(config["reduced_why"]) == set(REDUCED)
    for key in (
        "observation_encoder", "embedding_multiplier", "logits_scaling",
        "head_dim", "initialisation", "memory_len", "episode_ends",
        "matmul_precision", "unroll_length_and_batch_size",
        "learning_rate_schedule", "unused_keys",
    ):
        assert key in config["assumed"], key
    assert "GiB" in config["fit"] and "rung (1)" in config["fit"]
    assert "804,305,863" in config["reduced_why"]["num_hidden_layers"]


def test_published_table_equals_the_file():
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.models import granite4

    config = PUBLISHED_CONFIG
    assert granite4.PUBLISHED == {
        "d_model": config["hidden_size"],
        "num_layers": config["num_hidden_layers"],
        "layer_types": tuple(config["layer_types"]),
        "layer_period": tuple(config["layer_types"][:10]),
        "num_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "mamba_heads": config["mamba_n_heads"],
        "mamba_head_dim": config["mamba_d_head"],
        "mamba_groups": config["mamba_n_groups"],
        "state_size": config["mamba_d_state"],
        "conv_kernel": config["mamba_d_conv"],
        "chunk_size": config["mamba_chunk_size"],
        "mlp_width": config["shared_intermediate_size"],
        "input_scale": float(config["embedding_multiplier"]),
        "attention_multiplier": config["attention_multiplier"],
        "residual_multiplier": config["residual_multiplier"],
        "logits_scale": 1.0 / config["logits_scaling"],
        "rms_norm_eps": config["rms_norm_eps"],
        # ASSUMED (the row has no key): the file's `assumed` says so.
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 0.0001,
    }
    assert config["mamba_n_heads"] * config["mamba_d_head"] == (
        config["mamba_expand"] * config["hidden_size"]
    )
    assert config["num_local_experts"] == config["num_experts_per_tok"] == 0
    assert config["position_embedding_type"] == "nope"
    # The file's argv builds the cut the file states.
    file = _config_file()
    flags = monobeast.make_parser().parse_args(file["program_argv"])
    model, _ = monobeast._init_model_and_params(
        flags, 6, 8, (84, 84, 4), init_params=False
    )
    assert model.num_layers == file["num_hidden_layers"]
    assert list(model.pattern()) == file["layer_types"]
    assert model.memory_len == file["memory_len"]
    assert model.remat is True
    hp = monobeast.hparams_from_flags(monobeast.make_parser().parse_args(
        file["program_argv"] + ["--unroll_length", "511", "--batch_size", "8"]
    ))
    assert learner_lib.updates_horizon(hp) == 1
    # What the file says of the precision is what the family runs at.
    assert f"`{model.matmul_precision}`" in file["assumed"]["matmul_precision"]


def test_counts_against_hand_counts():
    """`flops_granite4.py` on the configuration's own file, against
    counts made by hand from the row."""
    config = _config_file()
    tokens = 512 * 8
    mixer = (
        2048 * (4096 + 4096 + 2 * 128 + 64)  # in_proj: z | xBC | dt
        + 5 * 4352 + 3 * 64 + 4096 + 4096 * 2048
    )
    assert mixer == 25_847_232
    mlp = 2048 + 2048 * 16_384 + 8192 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert (mlp, attention) == (50_333_696, 10_485_760)
    assert flops_granite4.mamba_param_count(config) == (
        2048 + mixer + mlp
    ) == 76_182_976
    assert flops_granite4.attention_param_count(config) == (
        2048 + attention + mlp
    ) == 60_821_504
    assert flops_granite4.param_count(config) == (
        9 * 76_182_976 + 60_821_504 + 28_224 * 2048 + 2048  # projection
        + 7 * 2048 + 2048 + 2048 + 2048 * 7 + 7
    ) == 804_305_863
    parts = flops_granite4.forward_flops_per_step(config)
    assert parts["mamba_in_proj"] == 9 * tokens * 2 * 2048 * 8512
    assert parts["mamba_out_proj"] == 9 * tokens * 2 * 4096 * 2048
    # The recurrence's two products a head over [64, 128], and D x.
    assert parts["mamba_scan"] == 9 * tokens * (
        2 * 2 * 64 * 64 * 128 + 2 * 4096
    )
    assert parts["mamba_conv"] == 9 * tokens * 2 * 4 * 4352
    assert parts["mlp"] == 10 * tokens * 2 * 3 * 2048 * 8192
    assert parts["qkvo"] == tokens * 2 * 2048 * (2 * 2048 + 2 * 512)
    # A query at step t sees the M - t slots still in its band, and
    # itself and the t steps of the unroll before it.
    assert parts["cache_leg"] == 8 * 4 * 2048 * sum(
        4095 - t for t in range(512)
    )
    assert parts["unroll_leg"] == 8 * 4 * 2048 * sum(
        t + 1 for t in range(512)
    )
    forward = sum(parts.values())
    assert 6.7e12 < forward < 6.9e12
    # The SwiGLUs owe three fifths and the mixers' projections most of
    # the rest; the recurrence itself 1%.
    assert 0.59 < parts["mlp"] / forward < 0.62
    mixers = sum(v for k, v in parts.items() if k.startswith("mamba_"))
    assert 0.28 < mixers / forward < 0.31
    assert parts["mamba_scan"] / forward < 0.012
    assert flops_granite4.train_flops_per_step(config) == (
        3 * forward - parts["projection"] - parts["cache_leg"]
    ) == 19_806_350_737_408
    # Nine states [64, 64, 128] with tails [3, 4352] and one cache of
    # 4,095 slots of 8 heads of 64 x 2 with its validity, 8 rows, f32.
    assert flops_granite4.state_bytes(config) == 8 * (
        19_344_384 + 4 * 4095 * (2 * 8 * 64 + 1)
    ) == 289_071_072
    assert flops_granite4.least_bytes_per_step(config) == (
        24 * 804_305_863 + 2 * 289_071_072
    ) == 19_881_482_856
    # The metrics' scales are these counts.
    for name, want in (
        ("mfu_pct.granite4", 100 * 19_806_350_737_408),
        ("hbm_bw_pct.granite4", 100 * 19_881_482_856 / 819e9),
    ):
        with open(os.path.join(
            manifest.HERE, "layer_metrics", name + ".json"
        )) as f:
            assert json.load(f)["args"]["scale"] == pytest.approx(want)
    # drivers/learner.py calls flops.train_flops_per_step for every cell.
    assert config["trunk_channels"] == [] and config["use_lstm"] is False
    assert flops.forward_flops_per_frame(config)["fc"] == 2 * 28_224 * 2048


def test_reference_agrees_with_the_program(tiny):
    """Same seeded weights, same rows: on the CPU both compute in f32,
    so they agree to f32 rounding, far inside the chip's tolerance."""
    import jax

    *_, check = learner_driver.build(tiny, 2**31 + 5, jax.devices()[:1])
    report = check(first_step_loss=None)
    assert report["ok"]
    assert report["rel_diff"] < 1e-5
    assert np.isfinite(report["reference_loss"])


FAULTS = {
    # The four multipliers, each left out of the program.
    "embedding_multiplier_left_out": dict(input_scale=1.0),
    "attention_multiplier_is_head_dim": dict(attention_multiplier=8 ** -0.5),
    "residual_multiplier_left_out": dict(residual_multiplier=1.0),
    "logits_scaling_left_out": dict(logits_scale=1.0),
}


@pytest.mark.parametrize("fault", [
    None, *FAULTS, "state_not_reset_at_done",
    "conv_reads_across_an_episode_end", "gate_after_the_norm",
])
def test_reference_sees_a_fault_planted_in_the_program(
    tiny, fault, monkeypatch
):
    """The driver's comparison (the system's loss against the
    reference's, over the reference's scale, held to the driver's
    tolerance) on what the cell's traffic leaves out: states an actor
    carried, and steps and decays at which the carried state is a large
    part of a Mamba layer's output (as seeded, dt is 0.001-0.1 and the
    D x skip carries the layer: the comparison then hardly reads the
    scan). The program as it is passes; each of the config's four
    multipliers left out (the scores' taken for head_dim^-0.5), a scan
    that does not reset at `done`, a convolution that reads across an
    episode end, the gate applied after the norm and not before it:
    each is seen."""
    import jax
    import jax.numpy as jnp

    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.models import granite4, nemotron3

    config = tiny.config
    _, params, _, batch, state, _ = learner_driver.build(
        tiny, 7, jax.devices()[:1]
    )
    inner = dict(params["params"])
    for name in ("block_0", "block_2"):
        inner[name] = dict(
            inner[name],
            dt_bias=jnp.full_like(inner[name]["dt_bias"], 1.0),
            A_log=jnp.full_like(inner[name]["A_log"], -3.0),
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

    if fault in FAULTS:
        monkeypatch.setattr(
            granite4, "PUBLISHED", dict(granite4.PUBLISHED, **FAULTS[fault])
        )
    elif fault == "state_not_reset_at_done":
        right_scan = nemotron3.ssd_scan
        monkeypatch.setattr(
            nemotron3, "ssd_scan",
            lambda x, dt, A, B_in, C_in, state, done, chunk: right_scan(
                x, dt, A, B_in, C_in, state, jnp.zeros_like(done), chunk
            ),
        )
    elif fault == "conv_reads_across_an_episode_end":
        right_conv = nemotron3.conv_over_episodes
        monkeypatch.setattr(
            nemotron3, "conv_over_episodes",
            lambda inputs, tail, done, taps, bias: right_conv(
                inputs, tail, jnp.zeros_like(done), taps, bias
            ),
        )
    elif fault == "gate_after_the_norm":
        right_norm = nemotron3.gated_group_norm
        monkeypatch.setattr(
            nemotron3, "gated_group_norm",
            lambda y, z, scale, groups, eps: right_norm(
                y, jnp.full_like(z, 1.2784645),  # silu there is 1
                scale, groups, eps,
            ) * jax.nn.silu(z),
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
