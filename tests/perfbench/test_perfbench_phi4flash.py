"""The cell `phi4flash_policy.learner`: its files, the configuration
against the catalog's row, the learner driver tiny on the CPU with the
family's widths shrunk (control flow, not speed), the reference seeing a
wrong program, and the counts behind its shares of a peak. Membership
is asserted, never position: the next configuration is appended after
this one's entries."""

import dataclasses
import importlib
import json
import os

import numpy as np
import pytest

from perfbench import common, flops, flops_phi4flash, manifest, peaks
from perfbench.drivers import learner as learner_driver
from tests.test_phi4flash import FAULTS, planted, with_louder_readers

CELL = "phi4flash_policy.learner"
CONFIG = "phi4flash_3b8_policy"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("mfu_pct.phi4flash", "hbm_bw_pct.phi4flash")
# What the family's table of widths is shrunk to, and the configuration
# keys that state the same sizes to the reference and the counts. At the
# published widths the 705M parameters with their gradients and
# optimizer state are 8.5 GB, which tier-1 must not allocate. Windows of
# equal length (7 slots both), so that the fault that has the cross
# layer read the sliding layer's keys has shapes.
SMALL_FAMILY = dict(
    d_model=32, num_heads=8, num_key_value_heads=4, intermediate_size=48,
    sliding_window=8, d_state=4, dt_rank=2,
)
SMALL_CONFIG = dict(
    hidden_size=32, num_attention_heads=8, num_key_value_heads=4,
    intermediate_size=48, sliding_window=8, d_state=4, dt_rank=2,
    memory_len=7, unroll_length=9, batch_size=4, frame_shape=[8, 8, 4],
    program_argv=["--model", "phi4flash", "--num_layers", "6",
                  "--memory_len", "7", "--remat", "all",
                  "--total_steps", "36"],
)
# The catalog row's `config`, copied here so that the test does not
# need the guide's file (it is checked against it where that is there).
PUBLISHED_CONFIG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}
REDUCED = {"num_hidden_layers": 6}
KINDS = ["mamba", "sliding", "mamba", "full", "memory", "cross"]


def _config_file():
    with open(os.path.join(manifest.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture
def tiny(monkeypatch):
    from torchbeast_tpu.models import phi4flash

    monkeypatch.setattr(
        phi4flash, "PUBLISHED", dict(phi4flash.PUBLISHED, **SMALL_FAMILY)
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
    assert cell.traffic == manifest.load_cell("ouro_policy.learner").traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_frames_per_s", "peak_hbm_gib", "setup_s",
    }
    assert {m["name"] for m in cell.per_layer} == {
        "update_device_ms.learn", "device_idle_pct.learn", *METRICS,
    }
    importlib.import_module("perfbench.reference." + cell.config["reference"])
    benchmark = manifest.load_benchmark()
    # The other cells' metrics are their own still, and this cell's two
    # are no other cell's.
    for other in benchmark["workloads"]:
        if other["name"] != CELL:
            assert not set(METRICS) & {
                m["name"]
                for m in manifest.load_cell(other["name"]).per_layer
            }
    # One configuration, one cell, two metrics and four list entries:
    # each is there ONCE. Where they stand in their lists is not this
    # test's to say (the next configuration's come after them).
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
    entry = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "selective scans" in entry["why"]
    config = next(c for c in benchmark["configs"] if c["name"] == CONFIG)
    assert len(config["why"]) <= 200
    # No cell takes four chips for this one's sake.
    assert sum(w["chips"] == 4 for w in benchmark["workloads"]) == 1


def test_config_equals_the_catalog_row_outside_reduced():
    """Every key of the catalog's row under its own key; depth the one
    thing cut, and stated as cut beside the published count and the
    deployment. No width, head count or window differs from the row."""
    config = _config_file()
    for key, value in PUBLISHED_CONFIG.items():
        assert config[key] == REDUCED.get(key, value), key
        if key in REDUCED:
            assert config["published_" + key] == value
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(
            r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning"
        )
        assert row["config"] == PUBLISHED_CONFIG
        assert row["source_url"] == config["source"]
    entry = next(
        c for c in manifest.load_benchmark()["configs"]
        if c["name"] == config["name"]
    )
    assert entry["reduced"] == list(REDUCED)
    assert sorted(config["reduced_why"]) == sorted(entry["reduced"])
    assert entry["source"] == config["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    # Published layers 14-19: one pair of each stage, 2 : 2 : 2.
    assert config["layers_run"] == [14, 15, 16, 17, 18, 19]
    assert flops_phi4flash.layers_run(config) == list(zip(range(14, 20), KINDS))
    assert "whole" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert config["unroll_length"] == 255 and config["memory_len"] == 4095
    assert config["batch_size"] == 16
    assert (
        config["d_state"], config["d_conv"], config["expand"],
        config["dt_rank"],
    ) == (16, 4, 2, 160)
    for key in (
        "d_state", "d_conv", "expand", "dt_rank", "dt_init", "no_dt_bc_norm",
        "attention_biases", "head_pairing", "lambda_vectors",
        "which_value_is_the_memory", "stage_boundaries", "final_norm",
        "episode_ends", "memory_len", "observation_encoder", "heads",
        "optimizer_and_precision", "side_inputs_start_at_zero",
        "learning_rate_schedule", "unroll_length_and_batch_size",
        "initialisation", "matmul_precision", "unused_keys",
    ):
        assert key in config["assumed"], key
    assert "GiB" in config["fit"]


def test_published_table_equals_the_file():
    from torchbeast_tpu.models import phi4flash

    table = {**phi4flash.PUBLISHED, **phi4flash.PUBLISHED_UNREAD}
    # Every key of config.json is in the family's two tables: under the
    # class's own name where `TransformerNet` has a field for it, else
    # under its own; the keys nothing reads are no fields of the class.
    assert not set(phi4flash.PUBLISHED) & set(phi4flash.PUBLISHED_UNREAD)
    fields = {f.name for f in dataclasses.fields(phi4flash.Phi4FlashNet)}
    assert set(phi4flash.PUBLISHED) <= fields
    assert not set(phi4flash.PUBLISHED_UNREAD) & fields
    renamed = {
        "hidden_size": "d_model", "num_hidden_layers": "num_layers",
        "num_attention_heads": "num_heads",
    }
    for key, value in PUBLISHED_CONFIG.items():
        assert table.pop(renamed.get(key, key)) == value, key
    # What is left is what config.json has no key for.
    assert table == {
        "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160,
        "time_step": (0.001, 0.1, 0.0001), "lambda_std": 0.1,
    }
    # The file's argv builds the cut the file states.
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.models.transformer import Recurrent

    file = _config_file()
    flags = monobeast.make_parser().parse_args(file["program_argv"])
    model, _ = monobeast._init_model_and_params(
        flags, 6, file["batch_size"], (84, 84, 4), init_params=False
    )
    assert model.num_layers == file["num_hidden_layers"]
    assert list(zip(model.published_indices(), model.kinds())) == (
        flops_phi4flash.layers_run(file)
    )
    carried = Recurrent(((16, 5120), (3, 5120)))
    assert model.layer_caches() == (
        carried, (511, 20, 64), carried, (file["memory_len"], 20, 64),
        None, None,
    )
    assert model.remat is True
    assert (model.d_state, model.d_conv, model.expand, model.dt_rank) == (
        file["d_state"], file["d_conv"], file["expand"], file["dt_rank"]
    )
    # One update's frames: the linear decay is at 0 from the second
    # update on (the file's `assumed.learning_rate_schedule` says why).
    hp = monobeast.hparams_from_flags(monobeast.make_parser().parse_args(
        file["program_argv"] + [
            "--unroll_length", str(file["unroll_length"]),
            "--batch_size", str(file["batch_size"]),
        ]
    ))
    assert learner_lib.updates_horizon(hp) == 1


def test_config_carries_what_flops_py_reads():
    """drivers/learner.py calls flops.train_flops_per_step for every
    cell: with no conv stage and no LSTM it counts the flat projection
    and the heads, and nothing of the layers."""
    config = _config_file()
    assert config["trunk_channels"] == [] and config["use_lstm"] is False
    parts = flops.forward_flops_per_frame(config)
    assert parts["first_conv"] == parts["trunk_convs"] == parts["core"] == 0
    assert parts["fc"] == 2 * 84 * 84 * 4 * 2560
    assert parts["heads"] == 2 * 2560 * 7


def test_reference_agrees_with_the_program(tiny):
    """Same seeded weights, same rows: on the CPU both compute in f32,
    so they agree to f32 rounding, far inside the chip's tolerance."""
    import jax

    *_, check = learner_driver.build(tiny, 2**31 + 5, jax.devices()[:1])
    report = check(first_step_loss=None)
    assert report["ok"]
    assert report["rel_diff"] < 1e-5
    assert np.isfinite(report["reference_loss"])


@pytest.mark.parametrize("fault", FAULTS)
def test_reference_sees_a_wrong_program(tiny, fault, monkeypatch):
    """The driver's comparison (the system's loss against the
    reference's, over the reference's scale, held to the driver's
    tolerance) on what the cell's traffic leaves out: states an actor
    carried. The program as it is passes; one whose memory units read
    layer 16's output AFTER its gate, one whose cross layer reads the
    sliding layer's keys and values, one whose `lambda_init` goes by
    the cut's index: each is seen (tests/test_phi4flash.py `planted`)."""
    import jax

    from perfbench.reference import phi4flash_policy as reference
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast

    config = tiny.config
    _, params, _, batch, state, _ = learner_driver.build(
        tiny, 7, jax.devices()[:1]
    )
    params = with_louder_readers(params)

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
    # written, with fewer ends than the checked batch has.
    inputs = {
        k: batch[k] for k in ("frame", "reward", "done", "last_action")
    }
    warm_model = build_model()[0]
    forward = jax.jit(lambda p, x, s: warm_model.apply(
        p, x, s, sample_action=False
    ))
    _, warm = forward(params, inputs, state)
    assert all(np.any(leaf) for leaf in jax.tree_util.tree_leaves(warm))

    with planted(fault, monkeypatch):
        model, hp = build_model()
        system = jax.jit(
            lambda p, b, s: learner_lib.compute_loss(model, p, b, s, hp)[0]
        )
        got = float(system(params, batch, warm))
    plain = jax.jit(
        lambda p, b, s: reference.loss_and_scale(p, b, s, config)
    )
    want, scale = map(float, plain(params, batch, warm))
    rel = abs(got - want) / scale
    if fault is None:
        assert rel < 1e-5
    else:
        assert rel > learner_driver.REFERENCE_RTOL, rel


def test_cell_runs_end_to_end(tiny, monkeypatch):
    import jax

    monkeypatch.setattr(common, "device_report", lambda devices: {
        "platform": devices[0].platform, "kind": "TPU v5 lite",
        "count": len(devices), "memory_peak_bytes": 2**30,
    })
    cell = tiny._replace(traffic=dict(tiny.traffic, steps_ahead=3))
    result = learner_driver.run(
        cell, 2**31 + 11, 1.0, False, jax.devices()[:1],
        common.CompileMeter(),
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
    parts = flops_phi4flash.forward_flops_per_step(config)
    tokens, rows, d, D = 256 * 16, 16, 2560, 5120
    assert parts["projection"] == tokens * 2 * 28224 * d
    assert parts["mamba_in_proj"] == 2 * tokens * 2 * d * 2 * D
    assert parts["mamba_conv"] == 2 * tokens * 2 * 4 * D
    assert parts["mamba_x_proj"] == 2 * tokens * 2 * (D * 192 + 160 * D)
    assert parts["scan"] == 2 * tokens * 6 * D * 16
    assert parts["mamba_out_proj"] == 2 * tokens * 2 * D * d
    # Wqkv to 40 + 20 + 20 heads of 64 and out_proj, twice; the cross
    # layer's Wq and out_proj.
    assert parts["qkvo"] == 2 * tokens * 2 * d * (5120 + d)
    assert parts["cross_qo"] == tokens * 2 * d * 2 * d
    assert parts["memory_unit"] == tokens * 2 * 2 * d * D
    assert parts["mlp"] == 6 * tokens * 3 * 2 * d * 10240
    # A query head scores over 64 and combines over 128, 40 heads; the
    # full and the cross layer see 4,095 slots, the sliding layer 511.
    pair = 2 * (64 + 128) * 40
    cache = flops_phi4flash.cache_pairs
    unroll = flops_phi4flash.unroll_pairs
    assert parts["cache_leg"] == rows * pair * (
        2 * cache(256, 4095) + cache(256, 511)
    )
    assert parts["unroll_leg"] == rows * pair * 3 * unroll(256, 511)
    assert unroll(256, 4095) == unroll(256, 511) == 256 * 257 // 2
    total = flops_phi4flash.train_flops_per_step(config)
    assert total == (
        3 * sum(parts.values()) - parts["projection"] - parts["cache_leg"]
    )
    # About 18 TFLOP an update, as ISSUE 55 has it.
    assert 17.5e12 < total < 18.5e12
    counts = flops_phi4flash.scan_counts(config)
    assert counts["exponents"] == 2 * 2 * tokens * D * 16
    assert counts["operations"] == 4 * parts["scan"]
    # a, dt, y and their cotangents [T, B, D], B and C [T, B, N], f32:
    # 3 D + 2 N forward, again backward, the gradients of a, dt, B, C
    # written and y's cotangent read.
    assert counts["stream_bytes"] == 2 * 4 * tokens * (
        (3 * D + 32) + (2 * D + 32) + (3 * D + 32)
    )


def test_flops_by_hand_at_a_small_size():
    """The tiny cell: 40 tokens of width 32; 8 query heads of 4 on 4
    key heads, both windows 7 slots."""
    config = dict(_config_file(), **SMALL_CONFIG)
    parts = flops_phi4flash.forward_flops_per_step(config)
    tokens, d, D, rows = 40, 32, 64, 4
    assert parts["projection"] == tokens * 2 * 256 * d
    assert parts["mamba_in_proj"] == 2 * tokens * 2 * d * 2 * D
    assert parts["mamba_x_proj"] == 2 * tokens * 2 * (D * 10 + 2 * D)
    assert parts["scan"] == 2 * tokens * 6 * D * 4
    assert parts["qkvo"] == 2 * tokens * 2 * d * (64 + d)
    # Query t of the 10 sees 7 - t cached slots (t < 7) and min(t, 7) +
    # 1 steps of the unroll.
    assert flops_phi4flash.cache_pairs(10, 7) == 28
    assert parts["cache_leg"] == 3 * rows * 28 * 2 * (4 + 8) * 8
    assert parts["mlp"] == 6 * tokens * 3 * 2 * d * 48


def test_param_count_is_the_programs(tiny):
    import jax

    _, params, *_ = learner_driver.build(tiny, 3, jax.devices()[:1])
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert flops_phi4flash.param_count(tiny.config) == count


def test_param_count_at_the_cells_size():
    """From shapes alone: nothing is allocated."""
    import jax

    from torchbeast_tpu import monobeast

    config = _config_file()
    rows = config["batch_size"]
    flags = monobeast.make_parser().parse_args(config["program_argv"])
    model, _ = monobeast._init_model_and_params(
        flags, 6, rows, (84, 84, 4), init_params=False
    )
    shapes, state = jax.eval_shape(
        lambda: (
            model.init(
                {"params": jax.random.PRNGKey(0),
                 "action": jax.random.PRNGKey(1)},
                monobeast.dummy_env_outputs(1, rows, (84, 84, 4), np.uint8),
                model.initial_state(rows),
            ),
            model.initial_state(rows),
        )
    )
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert flops_phi4flash.param_count(config) == count == 705_368_199
    assert config["param_count"] == count
    # By hand, as ISSUE 55's table has them.
    d, D = 2560, 5120
    mamba = (
        d * 2 * D + D * 4 + D + D * 192 + 160 * D + D + D * 16 + D + D * d
    )
    difference = 4 * 64 + 128
    own_keys = d * 5120 + 5120 + d * d + d + difference
    memory = d * D + D * d
    cross = d * d + d + d * d + d + difference
    assert (mamba, own_keys, memory, cross) == (
        41_241_600, 19_668_864, 26_214_400, 13_112_704
    ) == tuple(
        flops_phi4flash.mixer_param_count(config, kind)
        for kind in ("mamba", "full", "memory", "cross")
    )
    assert flops_phi4flash.mixer_param_count(config, "sliding") == own_keys
    mlp_and_norms = 3 * d * 10240 + 4 * d
    assert mlp_and_norms == 78_643_200 + 10_240
    layers = (
        2 * mamba + 2 * own_keys + memory + cross + 6 * mlp_and_norms
    )
    assert layers == 633_068_672
    assert count == (
        28224 * d + d + 7 * d + d + layers + 2 * d + d * 7 + 7
    )
    assert 28224 * d + d == 72_256_000
    # 16 bytes a parameter: 11.29 GB; a fourth pair would leave no room.
    assert 11.28e9 < 16 * count < 11.29e9
    assert 16 * (count + memory + cross + 2 * mlp_and_norms) > 14.4e9
    # The carried state: two Mamba states with their tails, the sliding
    # layer's 511 slots and the full layer's 4,095 of 20 key and 20
    # value heads of 64 with a validity column each.
    state_bytes = sum(
        4 * int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state)
    )
    assert state_bytes == 4 * rows * (
        2 * (16 + 3) * D + (511 + 4095) * (2 * 20 * 64 + 1)
    ) == flops_phi4flash.state_bytes(config)
    assert flops_phi4flash.least_bytes_per_step(config) == (
        6 * 4 * count + 2 * state_bytes
    )


@pytest.mark.parametrize("metric,want", [
    (METRICS[0], lambda c: 100 * flops_phi4flash.train_flops_per_step(c)),
    (METRICS[1], lambda c: (
        100 * flops_phi4flash.least_bytes_per_step(c)
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
    # 0.2-2 s a step on one chip must read as a share under 100 (the
    # MXU's peak would do the counted operations in 0.09 s).
    from perfbench import readers

    for step_s in (0.2, 2.0):
        facts = {"values": {"steps_per_s": 1 / step_s, "chips": 1,
                            "peak_flops": 197e12}}
        assert 0 < readers.read_metric(spec, facts) < 100
