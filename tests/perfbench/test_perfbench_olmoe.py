"""The cell `olmoe_policy.learner`: its files, the learner driver tiny on
the CPU with the family's widths shrunk (control flow, not speed), the
reference seeing a wrong program, and the two counts behind its shares
of a peak."""

import importlib
import json
import os

import numpy as np
import pytest

from perfbench import common, flops, flops_olmoe, manifest, peaks
from perfbench.drivers import learner as learner_driver

CELL = "olmoe_policy.learner"
# What the family's table of widths is shrunk to, and the configuration
# keys that state the same sizes to the reference and the counts. At the
# published widths the 897M parameters with their gradients and
# optimizer state are 11 GB, which tier-1 must not allocate.
SMALL_FAMILY = dict(
    d_model=64, num_heads=4, num_experts=8, experts_per_token=2,
    expert_width=32,
)
SMALL_CONFIG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    num_experts=8, num_experts_per_tok=2, intermediate_size=32,
    memory_len=5, unroll_length=3, batch_size=4, frame_shape=[8, 8, 4],
    program_argv=["--model", "olmoe", "--num_layers", "2",
                  "--memory_len", "5"],
)


def _config_file():
    with open(os.path.join(
        manifest.HERE, "configs", "olmoe_1b7b_policy.json"
    )) as f:
        return json.load(f)


@pytest.fixture
def tiny(monkeypatch):
    from torchbeast_tpu.models import olmoe

    monkeypatch.setattr(
        olmoe, "PUBLISHED", dict(olmoe.PUBLISHED, **SMALL_FAMILY)
    )
    cell = manifest.load_cell(CELL)
    return cell._replace(
        config=dict(cell.config, **SMALL_CONFIG),
        traffic=dict(cell.traffic, reference_rows=2, warmup_steps=2),
    )


def test_cell_loads_with_all_its_files():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config_name == "olmoe_1b7b_policy"
    assert cell.traffic_name == "learner_q48"
    assert cell.traffic["driver"] == "learner"
    assert cell.traffic["steps_ahead"] == 48
    base = manifest.load_cell("deep_lstm.learner").traffic
    for key in set(base) - {"steps_ahead", "why"}:
        assert cell.traffic[key] == base[key], key
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_frames_per_s", "peak_hbm_gib", "setup_s",
    }
    assert {m["name"] for m in cell.per_layer} == {
        "update_device_ms.learn", "device_idle_pct.learn",
        "mfu_pct.olmoe", "hbm_bw_pct.olmoe",
    }
    importlib.import_module("perfbench.reference." + cell.config["reference"])
    assert cell.config["program_argv"] == [
        "--model", "olmoe", "--num_layers", "2", "--memory_len", "128",
    ]


def test_config_keeps_the_published_widths():
    """Every number of the catalog's row under its own key; depth the
    one thing cut, and stated as cut."""
    config = _config_file()
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304,
    }
    for key, value in published.items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 2
    entry = next(
        c for c in manifest.load_benchmark()["configs"]
        if c["name"] == config["name"]
    )
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    from torchbeast_tpu.models import olmoe

    assert olmoe.PUBLISHED == {
        "d_model": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "num_layers": 16,
        "num_experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["intermediate_size"],
        "rms_norm_eps": config["rms_norm_eps"],
        "rope_theta": config["rope_theta"],
    }


def test_config_carries_what_flops_py_reads():
    """drivers/learner.py calls flops.train_flops_per_step for every
    cell: with no conv stage and no LSTM it counts the flat projection
    and the heads, and nothing of the blocks."""
    config = _config_file()
    assert config["trunk_channels"] == [] and config["use_lstm"] is False
    parts = flops.forward_flops_per_frame(config)
    assert parts["first_conv"] == parts["trunk_convs"] == parts["core"] == 0
    assert parts["fc"] == 2 * 84 * 84 * 4 * 2048
    assert parts["heads"] == 2 * 2048 * 7
    assert flops.train_flops_per_step(config) == (
        3 * (parts["fc"] + parts["heads"]) * 81 * 32
    )


def test_reference_agrees_with_the_program(tiny):
    """Same seeded weights, same rows: on the CPU both compute in f32,
    so they agree to f32 rounding, far inside the chip's tolerance."""
    import jax

    *_, check = learner_driver.build(tiny, 2**31 + 5, jax.devices()[:1])
    report = check(first_step_loss=None)
    assert report["ok"]
    assert report["rel_diff"] < 1e-5
    assert np.isfinite(report["reference_loss"])


@pytest.mark.parametrize("fault", ["renormalised_gates", "dropped_assignment"])
def test_reference_sees_a_wrong_program(tiny, fault, monkeypatch):
    """A program that renormalised its gates, or dropped one token's
    first expert as a full capacity would, differs from the reference
    by more than the driver's tolerance (8 tokens in the check here)."""
    import jax

    from torchbeast_tpu.models import moe

    right = moe.dropless_experts

    def wrong(x, idx, gate, *weights):
        if fault == "renormalised_gates":
            gate = gate / gate.sum(axis=-1, keepdims=True)
        else:
            gate = gate.at[0, 0].set(0.0)
        return right(x, idx, gate, *weights)

    *_, check = learner_driver.build(tiny, 7, jax.devices()[:1])
    assert check(first_step_loss=None)["ok"]
    monkeypatch.setattr(moe, "dropless_experts", wrong)
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


def test_flops_by_hand_for_one_layer():
    config = dict(_config_file(), num_hidden_layers=1)
    parts = flops_olmoe.forward_flops_per_step(config)
    tokens, d = 81 * 32, 2048
    assert parts["projection"] == tokens * 2 * 28224 * d
    assert parts["qkvo"] == tokens * 4 * 2 * d * d == tokens * 33_554_432
    # Every query of an 81-step unroll with a 128-slot cache has 129
    # keys inside its band; scores and the weighted sum, 2 x d each.
    assert flops_olmoe.band_keys(81, 128) == 81 * 129
    assert parts["attention"] == 32 * 81 * 129 * 4 * d
    assert parts["router"] == tokens * 2 * d * 64
    assert parts["experts"] == tokens * 8 * 3 * 2 * d * 1024
    per_token = sum(
        parts[k] for k in ("qkvo", "attention", "router", "experts")
    ) / tokens
    assert round(per_token / 1e6, 1) == 135.5
    # A window shorter than the unroll: late queries lose the cache and
    # then the unroll's first steps.
    assert flops_olmoe.band_keys(4, 2) == 3 + 3 + 3 + 3
    assert flops_olmoe.band_keys(3, 0) == 1 + 1 + 1
    # Backward twice the forward, but no input gradient for the frames.
    assert flops_olmoe.train_flops_per_step(config) == (
        3 * sum(parts.values()) - parts["projection"]
    )


def test_param_count_is_the_programs(tiny):
    import jax

    _, params, *_ = learner_driver.build(tiny, 3, jax.devices()[:1])
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert flops_olmoe.param_count(tiny.config) == count
    config = _config_file()
    assert flops_olmoe.param_count(config) == 896_976_903
    assert flops_olmoe.least_bytes_per_step(config) == 6 * 4 * 896_976_903


@pytest.mark.parametrize("metric,want", [
    ("mfu_pct.olmoe", lambda c: 100 * flops_olmoe.train_flops_per_step(c)),
    ("hbm_bw_pct.olmoe", lambda c: (
        100 * flops_olmoe.least_bytes_per_step(c)
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
    # 35-60 ms a step on one chip must read as a share under 100.
    facts = {"values": {"steps_per_s": 1 / 0.035, "chips": 1,
                        "peak_flops": 197e12}}
    from perfbench import readers

    assert 0 < readers.read_metric(spec, facts) < 100
