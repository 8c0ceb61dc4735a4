"""The cell `ouro_policy.learner`: its files, the configuration against
the catalog's row, the learner driver tiny on the CPU with the family's
widths shrunk (control flow, not speed), the reference seeing a wrong
program, and the two counts behind its shares of a peak."""

import importlib
import json
import os

import numpy as np
import pytest

from perfbench import common, flops, flops_ouro, manifest, peaks
from perfbench.drivers import learner as learner_driver

CELL = "ouro_policy.learner"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# What the family's table is shrunk to, and the configuration keys that
# state the same sizes to the reference and the counts. At the published
# widths the 469M parameters with their gradients and optimizer state
# are 5.6 GB and the 32 caches 4.3 GB, which tier-1 must not allocate.
SMALL_FAMILY = dict(
    d_model=64, num_heads=4, head_dim=16, mlp_width=96, passes=3,
)
SMALL_CONFIG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, intermediate_size=96, total_ut_steps=3,
    num_hidden_layers=2, memory_len=7, unroll_length=3, batch_size=4,
    frame_shape=[8, 8, 4],
    program_argv=["--model", "ouro", "--num_layers", "2",
                  "--memory_len", "7", "--remat", "all"],
)
# The catalog row's `config`, copied here so that the test does not
# need the guide's file (it is checked against it where that is there).
PUBLISHED_CONFIG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
REDUCED = {"num_hidden_layers": 8}


def _config_file():
    with open(os.path.join(
        manifest.HERE, "configs", "ouro_2b6_policy.json"
    )) as f:
        return json.load(f)


@pytest.fixture
def tiny(monkeypatch):
    from torchbeast_tpu.models import ouro

    monkeypatch.setattr(
        ouro, "PUBLISHED", dict(ouro.PUBLISHED, **SMALL_FAMILY)
    )
    cell = manifest.load_cell(CELL)
    return cell._replace(
        config=dict(cell.config, **SMALL_CONFIG),
        traffic=dict(cell.traffic, reference_rows=2, warmup_steps=2),
    )


def test_cell_loads_with_all_its_files():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config_name == "ouro_2b6_policy"
    assert cell.traffic_name == "learner"
    assert cell.traffic == manifest.load_cell("deep_lstm.learner").traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_frames_per_s", "peak_hbm_gib", "setup_s",
    }
    assert {m["name"] for m in cell.per_layer} == {
        "update_device_ms.learn", "device_idle_pct.learn",
        "mfu_pct.ouro", "hbm_bw_pct.ouro",
    }
    importlib.import_module("perfbench.reference." + cell.config["reference"])
    assert cell.config["program_argv"] == [
        "--model", "ouro", "--num_layers", "8", "--memory_len", "255",
        "--remat", "all",
    ]
    # The other transformer cells' metrics are their own still.
    for other in ("olmoe_policy.learner", "mellum2_policy.learner"):
        assert not {"mfu_pct.ouro", "hbm_bw_pct.ouro"} & {
            m["name"] for m in manifest.load_cell(other).per_layer
        }


def test_config_equals_the_catalog_row_outside_reduced():
    """Every key of the catalog's row under its own key; depth the one
    thing cut, and stated as cut beside the published count and the
    deployment. The loop is as published."""
    config = _config_file()
    for key, value in PUBLISHED_CONFIG.items():
        assert config[key] == REDUCED.get(key, value), key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows if r["name"] == "Ouro-2.6B")
        assert row["config"] == PUBLISHED_CONFIG
        assert row["source_url"] == config["source"]
    entry = next(
        c for c in manifest.load_benchmark()["configs"]
        if c["name"] == config["name"]
    )
    assert entry["reduced"] == ["num_hidden_layers"]
    assert sorted(config["reduced_why"]) == sorted(entry["reduced"])
    assert entry["source"] == config["source"]
    assert config["published_num_hidden_layers"] == 48
    assert config["total_ut_steps"] == 4
    assert "pipeline stages" in config["deployment"]
    assert (config["batch_size"], config["unroll_length"]) == (32, 80)
    assert config["memory_len"] == 255
    assert flops_ouro.applications(config) == 32
    for key in (
        "sandwich_norms", "norm_after_every_pass", "exit_gate", "memory_len",
        "observation_encoder", "heads", "optimizer_and_precision",
    ):
        assert key in config["assumed"], key
    assert "GiB" in config["fit"]


def test_published_table_equals_the_file():
    from torchbeast_tpu.models import ouro

    config = PUBLISHED_CONFIG
    assert ouro.PUBLISHED == {
        "d_model": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "head_dim": config["head_dim"],
        "mlp_width": config["intermediate_size"],
        "num_layers": config["num_hidden_layers"],
        "passes": config["total_ut_steps"],
        "rms_norm_eps": config["rms_norm_eps"],
        "rope_theta": config["rope_theta"],
    }
    assert config["num_key_value_heads"] == config["num_attention_heads"]
    # The file's argv builds the cut the file states.
    from torchbeast_tpu import monobeast

    file = _config_file()
    flags = monobeast.make_parser().parse_args(file["program_argv"])
    model, _ = monobeast._init_model_and_params(
        flags, 6, 32, (84, 84, 4), init_params=False
    )
    assert model.num_layers == file["num_hidden_layers"]
    assert model.passes == file["total_ut_steps"]
    assert model.layer_caches() == (
        (file["memory_len"], 16, 128),
    ) * flops_ouro.applications(file)
    assert model.remat is True


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
    "fault", ["norm_after_the_last_pass_only", "one_pass_of_work_for_three",
              "a_plain_mlp"],
)
def test_reference_sees_a_wrong_program(tiny, fault, monkeypatch):
    """A program that norms once, after the last pass; one that does
    one pass's work and idles through the other two (their blocks'
    output thrown away); one whose SwiGLU lost its activation: each
    differs from the reference by more than the driver's tolerance."""
    import jax

    from torchbeast_tpu.models import ouro

    *_, check = learner_driver.build(tiny, 7, jax.devices()[:1])
    assert check(first_step_loss=None)["ok"]
    if fault == "norm_after_the_last_pass_only":
        monkeypatch.setattr(
            ouro.OuroNet, "block_passes",
            lambda self: (tuple(range(self.num_layers)) * self.passes,),
        )
    elif fault == "one_pass_of_work_for_three":
        right = ouro._OuroBlock.__call__
        calls = []

        def wrong(self, x, *args, **kwargs):
            calls.append(1)
            y, k, v = right(self, x, *args, **kwargs)
            # Of every 3 x 2 applications (one forward pass of the
            # net), the last four (two passes) hand x on as it came.
            # (One idle pass of three moves this loss by 4e-3 of its
            # scale at this size: the norm after every pass keeps the
            # passes' outputs close at seeded weights.)
            return (x if (len(calls) - 1) % 6 >= 2 else y), k, v

        monkeypatch.setattr(ouro._OuroBlock, "__call__", wrong)
        tiny = tiny._replace(config=dict(
            tiny.config, program_argv=tiny.config["program_argv"][:-2],
        ))  # no --remat: the patched method is the module's own
    else:
        monkeypatch.setattr(ouro.nn, "silu", lambda x: x)
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
    parts = flops_ouro.forward_flops_per_step(config)
    tokens, d = 81 * 32, 2048
    assert parts["projection"] == tokens * 2 * 28224 * d
    assert parts["extras"] == tokens * 2 * 7 * d
    # One application of a layer to one token: q, k, v, o 2048 x 2048;
    # 256 keys inside the band, scores and the weighted sum over 16
    # heads of 128; gate, up and down 2048 x 5632.
    from perfbench.flops_olmoe import band_keys

    assert band_keys(81, 255) == 81 * 256
    qkvo, scores, mlp = 4 * 2 * d * d, 256 * 4 * d, 3 * 2 * d * 5632
    assert (qkvo, scores, mlp) == (33_554_432, 2_097_152, 69_206_016)
    assert round((qkvo + scores + mlp) / 1e6, 1) == 104.9
    # 4 passes x 8 layers: the same weights do 32 applications' work.
    assert parts["qkvo"] == 32 * tokens * qkvo
    assert parts["attention"] == 32 * tokens * scores
    assert parts["mlp"] == 32 * tokens * mlp
    assert parts["heads"] == tokens * 2 * d * 7
    assert round(sum(parts.values()) / 1e12, 2) == 9.0
    shares = {k: v / sum(parts.values()) for k, v in parts.items()}
    # The loop is all but the projection: 97% of the operations.
    assert round(
        shares["qkvo"] + shares["attention"] + shares["mlp"], 3
    ) == 0.967
    assert flops_ouro.train_flops_per_step(config) == (
        3 * sum(parts.values()) - parts["projection"]
    ) == 26_691_671_162_880
    # One pass is a quarter of the loop's work; the published depth six
    # times this cut's.
    once = flops_ouro.forward_flops_per_step(dict(config, total_ut_steps=1))
    assert 4 * once["mlp"] == parts["mlp"]
    whole = flops_ouro.forward_flops_per_step(
        dict(config, num_hidden_layers=48)
    )
    assert whole["qkvo"] == 6 * parts["qkvo"]


def test_flops_by_hand_at_the_small_size(tiny):
    """d 64, 4 heads of 16, width 96, 2 layers x 3 passes, 7 slots, a
    [4, 4] batch of 8x8x4 frames."""
    parts = flops_ouro.forward_flops_per_step(tiny.config)
    tokens = 4 * 4
    assert parts["projection"] == tokens * 2 * 256 * 64
    assert parts["qkvo"] == 6 * tokens * 2 * 64 * 4 * 64
    # Queries at steps 0..3 of a 7-slot band see 8 keys each.
    assert parts["attention"] == 6 * 4 * (4 * 8) * 4 * 64
    assert parts["mlp"] == 6 * tokens * 3 * 2 * 64 * 96
    assert flops_ouro.param_count(tiny.config) == (
        256 * 64 + 64 + 7 * 64 + 64
        + 2 * (4 * 64 * 64 + 3 * 64 * 96 + 4 * 64)
        + 64 + 65 + 64 * 7 + 7
    )
    looped = 2 * (4 * 64 * 64 + 3 * 64 * 96 + 4 * 64)
    count = flops_ouro.param_count(tiny.config)
    assert flops_ouro.least_bytes_per_step(tiny.config) == 4 * (
        6 * looped + 2 * (count - looped) + 4 * count
    )


def test_param_count_is_the_programs(tiny):
    import jax

    _, params, *_ = learner_driver.build(tiny, 3, jax.devices()[:1])
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert flops_ouro.param_count(tiny.config) == count


def test_param_count_at_the_cells_size():
    """From shapes alone: nothing is allocated."""
    import jax

    from torchbeast_tpu import monobeast

    config = _config_file()
    flags = monobeast.make_parser().parse_args(config["program_argv"])
    model, _ = monobeast._init_model_and_params(
        flags, 6, 32, (84, 84, 4), init_params=False
    )
    shapes, state = jax.eval_shape(
        lambda: (
            model.init(
                {"params": jax.random.PRNGKey(0),
                 "action": jax.random.PRNGKey(1)},
                monobeast.dummy_env_outputs(1, 32, (84, 84, 4), np.uint8),
                model.initial_state(32),
            ),
            model.initial_state(32),
        )
    )
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert flops_ouro.param_count(config) == count == 468_946_952
    # By hand: a layer's four projections, SwiGLU and four norms.
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == flops_ouro.layer_param_count(config) == 51_388_416
    assert count == (
        28224 * 2048 + 2048 + 7 * 2048 + 2048 + 8 * layer + 2048
        + 2049 + 2048 * 7 + 7
    )
    # 8 reads of the looped weights, 2 of the rest, 4 optimizer passes.
    assert flops_ouro.least_bytes_per_step(config) == 4 * (
        8 * 8 * layer + 2 * (count - 8 * layer) + 4 * count
    ) == 21_121_302_720
    # The carried state: 32 caches of k, v [255, 32, 16, 128] and their
    # validity columns, four times a plain stack's.
    state_bytes = sum(
        4 * int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state)
    )
    assert state_bytes == 32 * 4 * 255 * 32 * (2 * 2048 + 1) == 4_279_234_560


@pytest.mark.parametrize("metric,want", [
    ("mfu_pct.ouro", lambda c: 100 * flops_ouro.train_flops_per_step(c)),
    ("hbm_bw_pct.ouro", lambda c: (
        100 * flops_ouro.least_bytes_per_step(c)
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
    # 0.15-1 s a step on one chip must read as a share under 100 (the
    # MXU's peak would do the counted operations in 0.135 s).
    from perfbench import readers

    for step_s in (0.15, 1.0):
        facts = {"values": {"steps_per_s": 1 / step_s, "chips": 1,
                            "peak_flops": 197e12}}
        assert 0 < readers.read_metric(spec, facts) < 100
