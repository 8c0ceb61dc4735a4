"""The cell `mellum2_policy.learner`: its files, the configuration
against the catalog's row, the learner driver tiny on the CPU with the
family's widths shrunk (control flow, not speed), the reference seeing a
wrong program, and the two counts behind its shares of a peak."""

import importlib
import json
import os

import numpy as np
import pytest

from perfbench import common, flops, flops_mellum2, manifest, peaks
from perfbench.drivers import learner as learner_driver

CELL = "mellum2_policy.learner"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# What the family's table of widths is shrunk to, and the configuration
# keys that state the same sizes to the reference and the counts. At the
# published widths the 547M parameters with their gradients and
# optimizer state are 6.6 GB, which tier-1 must not allocate.
SMALL_FAMILY = dict(
    d_model=48, num_heads=4, kv_heads=2, head_dim=16, sliding_window=4,
    num_experts=8, experts_per_token=2, expert_width=24,
)
SMALL_CONFIG = dict(
    hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=4, published_num_experts=8, num_experts=2,
    num_experts_per_tok=2, moe_intermediate_size=24, memory_len=7,
    unroll_length=3, batch_size=4, frame_shape=[8, 8, 4],
    program_argv=["--model", "mellum2", "--num_layers", "4",
                  "--memory_len", "7", "--expert_share", "0/4",
                  "--remat", "all", "--total_steps", "12"],
)
# The catalog row's `config`, copied here so that the test does not
# need the guide's file (it is checked against it where that is there).
PUBLISHED_CONFIG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16}


def _config_file():
    with open(os.path.join(
        manifest.HERE, "configs", "mellum2_12b_policy.json"
    )) as f:
        return json.load(f)


@pytest.fixture
def tiny(monkeypatch):
    from torchbeast_tpu.models import mellum2

    monkeypatch.setattr(
        mellum2, "PUBLISHED", dict(mellum2.PUBLISHED, **SMALL_FAMILY)
    )
    cell = manifest.load_cell(CELL)
    return cell._replace(
        config=dict(cell.config, **SMALL_CONFIG),
        traffic=dict(cell.traffic, reference_rows=2, warmup_steps=2),
    )


def test_cell_loads_with_all_its_files():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config_name == "mellum2_12b_policy"
    assert cell.traffic_name == "learner_q48"
    assert cell.traffic == manifest.load_cell("olmoe_policy.learner").traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_frames_per_s", "peak_hbm_gib", "setup_s",
    }
    assert {m["name"] for m in cell.per_layer} == {
        "update_device_ms.learn", "device_idle_pct.learn",
        "mfu_pct.mellum2", "hbm_bw_pct.mellum2",
    }
    importlib.import_module("perfbench.reference." + cell.config["reference"])
    assert cell.config["program_argv"] == [
        "--model", "mellum2", "--num_layers", "4", "--memory_len", "4095",
        "--expert_share", "0/4", "--remat", "all", "--total_steps", "2560",
    ]
    # The OLMoE cell's metrics are its own still.
    assert "mfu_pct.mellum2" not in {
        m["name"] for m in manifest.load_cell("olmoe_policy.learner").per_layer
    }


def test_config_equals_the_catalog_row_outside_reduced():
    """Every key of the catalog's row under its own key, nested groups
    whole; depth and the experts held the two things cut, and stated as
    cut beside the published counts and the deployment."""
    config = _config_file()
    for key, value in PUBLISHED_CONFIG.items():
        assert config[key] == REDUCED.get(key, value), key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows if r["name"].startswith("Mellum2-12B"))
        assert row["config"] == PUBLISHED_CONFIG
        assert row["source_url"] == config["source"]
    entry = next(
        c for c in manifest.load_benchmark()["configs"]
        if c["name"] == config["name"]
    )
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    assert sorted(config["reduced_why"]) == sorted(entry["reduced"])
    assert entry["source"] == config["source"]
    assert config["published_num_experts"] == 64
    assert config["published_num_hidden_layers"] == 28
    assert config["expert_share"] == [0, 4]
    assert "four chips share each layer" in config["deployment"]
    assert (config["batch_size"], config["unroll_length"]) == (32, 80)
    assert config["memory_len"] == 4095
    assert flops_mellum2.cache_lens(config) == [1023, 1023, 1023, 4095]
    for key in ("qk_norm", "load_balance_weight", "memory_len"):
        assert key in config["assumed"]


def test_published_table_equals_the_file():
    from torchbeast_tpu.models import mellum2

    config, yarn = PUBLISHED_CONFIG, PUBLISHED_CONFIG["rope_parameters"]
    full, sliding = yarn["full_attention"], yarn["sliding_attention"]
    assert full["rope_theta"] == sliding["rope_theta"]
    assert mellum2.PUBLISHED == {
        "d_model": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "num_layers": config["num_hidden_layers"],
        "layer_period": tuple(config["layer_types"][:4]),
        "sliding_window": config["sliding_window"],
        "num_experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "renormalise": config["norm_topk_prob"],
        "rms_norm_eps": config["rms_norm_eps"],
        "rope_theta": full["rope_theta"],
        "yarn": (
            full["factor"], full["original_max_position_embeddings"],
            full["beta_fast"], full["beta_slow"], full["attention_factor"],
        ),
    }
    assert config["layer_types"] == list(mellum2.PUBLISHED["layer_period"]) * 7
    # The file's argv builds the cut the file states.
    from torchbeast_tpu import monobeast

    file = _config_file()
    flags = monobeast.make_parser().parse_args(file["program_argv"])
    model, _ = monobeast._init_model_and_params(
        flags, 6, 32, (84, 84, 4), init_params=False
    )
    assert model.num_layers == file["num_hidden_layers"]
    assert model.held_experts() == (0, file["num_experts"])
    assert [m for m, _, _ in model.layer_caches()] == (
        flops_mellum2.cache_lens(file)
    )
    assert model.aux_loss_weight == file["load_balance_weight"]
    assert model.remat is True
    # One update's frames: the linear decay is at 0 from the second
    # update on (the file's `assumed.learning_rate_schedule` says why).
    from torchbeast_tpu import learner as learner_lib

    hp = monobeast.hparams_from_flags(monobeast.make_parser().parse_args(
        file["program_argv"] + ["--unroll_length", "80", "--batch_size", "32"]
    ))
    assert learner_lib.updates_horizon(hp) == 1
    assert "side_inputs_start_at_zero" in file["assumed"]
    assert "learning_rate_schedule" in file["assumed"]


def test_config_carries_what_flops_py_reads():
    """drivers/learner.py calls flops.train_flops_per_step for every
    cell: with no conv stage and no LSTM it counts the flat projection
    and the heads, and nothing of the blocks."""
    config = _config_file()
    assert config["trunk_channels"] == [] and config["use_lstm"] is False
    parts = flops.forward_flops_per_frame(config)
    assert parts["first_conv"] == parts["trunk_convs"] == parts["core"] == 0
    assert parts["fc"] == 2 * 84 * 84 * 4 * 2304
    assert parts["heads"] == 2 * 2304 * 7


def test_reference_agrees_with_the_program(tiny):
    """Same seeded weights, same rows: on the CPU both compute in f32,
    so they agree to f32 rounding, far inside the chip's tolerance."""
    import jax

    *_, check = learner_driver.build(tiny, 2**31 + 5, jax.devices()[:1])
    report = check(first_step_loss=None)
    assert report["ok"]
    assert report["rel_diff"] < 1e-5
    assert np.isfinite(report["reference_loss"])


@pytest.mark.parametrize(
    "fault", ["gates_as_they_are", "every_expert_held", "repeated_kv_head"]
)
def test_reference_sees_a_wrong_program(tiny, fault, monkeypatch):
    """A program that left its gates as they are, that added the other
    chips' experts' rows through its own weights, or whose query heads
    all read key/value head 0, differs from the reference by more than
    the driver's tolerance."""
    import jax

    from torchbeast_tpu.models import mellum2, moe
    from torchbeast_tpu.ops import attention

    *_, check = learner_driver.build(tiny, 7, jax.devices()[:1])
    assert check(first_step_loss=None)["ok"]
    if fault == "gates_as_they_are":
        monkeypatch.setattr(
            mellum2, "PUBLISHED", dict(mellum2.PUBLISHED, renormalise=False)
        )
    elif fault == "every_expert_held":
        right = moe.dropless_experts

        def wrong(x, idx, gate, *weights, first_of=None):
            count = weights[0].shape[0]
            y, _ = right(x, idx % count, gate, *weights)
            return y, right(x, idx, gate, *weights, first_of=first_of)[1]

        monkeypatch.setattr(moe, "dropless_experts", wrong)
    else:
        right = attention.dense_transformer_attend

        def wrong(q, k_all, v_all, *rest):
            return right(
                q, k_all.at[:, :, 1].set(k_all[:, :, 0]),
                v_all.at[:, :, 1].set(v_all[:, :, 0]), *rest,
            )

        monkeypatch.setattr(mellum2, "dense_transformer_attend", wrong)
    *_, check = learner_driver.build(tiny, 7, jax.devices()[:1])
    report = check(first_step_loss=None)
    assert report["rel_diff"] > learner_driver.REFERENCE_RTOL
    assert not report["ok"]


def test_cell_runs_end_to_end(tiny, monkeypatch):
    import jax

    monkeypatch.setattr(common, "device_report", lambda devices: {
        "platform": devices[0].platform, "kind": "TPU v5 lite",
        "count": len(devices), "memory_peak_bytes": 2**30,
    })
    cell = tiny._replace(traffic=dict(tiny.traffic, steps_ahead=3))
    result = learner_driver.run(
        cell, 11, 1.0, False, jax.devices()[:1], common.CompileMeter()
    )
    assert result["correct"], result["notes"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["facts"]["values"]["window_compiles"] == 0
    assert result["notes"]["check"]["rel_diff"] < 1e-5
    # The two shares read what the driver took itself.
    from perfbench import readers

    for spec in manifest.load_cell(CELL).per_layer:
        if spec["reader"] == "ratio":
            assert readers.read_metric(spec, result["facts"]) > 0


def test_flops_by_hand():
    config = _config_file()
    parts = flops_mellum2.forward_flops_per_step(config)
    tokens, d = 81 * 32, 2304
    assert parts["projection"] == tokens * 2 * 28224 * d
    assert parts["extras"] == tokens * 2 * 7 * d
    # q and o 2304 x 4096, k and v 2304 x 512, four layers.
    per_token_qkvo = 2 * d * 4096 * 2 + 2 * d * 512 * 2
    assert per_token_qkvo == 42_467_328
    assert parts["qkvo"] == 4 * tokens * per_token_qkvo
    # Every query of an 81-step unroll has 1,024 keys inside a sliding
    # layer's band and 4,096 inside the full layer's; scores and the
    # weighted sum, 2 x 32 heads x 128 each.
    from perfbench.flops_olmoe import band_keys

    assert band_keys(81, 1023) == 81 * 1024
    assert band_keys(81, 4095) == 81 * 4096
    assert parts["attention"] == 32 * 81 * (3 * 1024 + 4096) * 4 * 4096
    # The router routes over the published 64.
    assert parts["router"] == 4 * tokens * 2 * d * 64
    # 16 of 64 held: 2 of a token's 8 assignments, on average.
    assert parts["experts"] == 4 * tokens * 2 * 3 * 2 * d * 896
    assert parts["heads"] == tokens * 2 * d * 7
    per_token = sum(parts.values()) / tokens
    assert round(per_token / 1e6, 1) == 517.7
    shares = {k: v / sum(parts.values()) for k, v in parts.items()}
    # The two attention kinds (with their projections) and the share's
    # experts are three quarters of the operations.
    assert round(
        shares["qkvo"] + shares["attention"] + shares["experts"], 2
    ) == 0.75
    assert flops_mellum2.train_flops_per_step(config) == (
        3 * sum(parts.values()) - parts["projection"]
    ) == 3_688_534_278_144
    # A window shorter than the full cache's, a full cache shorter than
    # the window.
    short = dict(config, memory_len=500)
    assert flops_mellum2.cache_lens(short) == [500] * 4
    whole = dict(config, num_experts=64)
    assert flops_mellum2.forward_flops_per_step(whole)["experts"] == (
        4 * parts["experts"]
    )


def test_param_count_is_the_programs(tiny):
    import jax

    _, params, *_ = learner_driver.build(tiny, 3, jax.devices()[:1])
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert flops_mellum2.param_count(tiny.config) == count


def test_param_count_at_the_cells_size():
    """From shapes alone: nothing is allocated."""
    import jax

    from torchbeast_tpu import monobeast

    config = _config_file()
    flags = monobeast.make_parser().parse_args(config["program_argv"])
    model, _ = monobeast._init_model_and_params(
        flags, 6, 32, (84, 84, 4), init_params=False
    )
    shapes = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
            monobeast.dummy_env_outputs(1, 32, (84, 84, 4), np.uint8),
            model.initial_state(32),
        )
    )
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert flops_mellum2.param_count(config) == count == 546_972_935
    assert flops_mellum2.least_bytes_per_step(config) == 6 * 4 * 546_972_935
    # By hand: a layer's attention, norms, router and 16 experts.
    layer = (
        2 * 2304 * 4096 + 2 * 2304 * 512 + 2 * 2304 + 2 * 128
        + 2304 * 64 + 16 * 3 * 2304 * 896
    )
    assert layer == 120_476_416
    assert count == (
        28224 * 2304 + 2304 + 7 * 2304 + 2304 + 4 * layer + 2304
        + 2304 * 7 + 7
    )


@pytest.mark.parametrize("metric,want", [
    ("mfu_pct.mellum2", lambda c: 100 * flops_mellum2.train_flops_per_step(c)),
    ("hbm_bw_pct.mellum2", lambda c: (
        100 * flops_mellum2.least_bytes_per_step(c)
        / (1e9 * peaks.PEAK_HBM_GBPS["v5e"])
    )),
])
def test_metric_scale_is_the_functions_value(metric, want):
    with open(os.path.join(
        manifest.HERE, "layer_metrics", metric + ".json"
    )) as f:
        spec = json.load(f)
    assert spec["reader"] == "ratio"
    assert spec["args"]["scale"] == pytest.approx(want(_config_file()), rel=1e-12)
    # 60-400 ms a step on one chip must read as a share under 100.
    from perfbench import readers

    for step_s in (0.06, 0.4):
        facts = {"values": {"steps_per_s": 1 / step_s, "chips": 1,
                            "peak_flops": 197e12}}
        assert 0 < readers.read_metric(spec, facts) < 100
