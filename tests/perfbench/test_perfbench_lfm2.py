"""The cell `lfm2_policy.learner`: its files, the configuration against
the catalog's row, the learner driver tiny on the CPU with the family's
widths shrunk (control flow, not speed), the reference seeing a wrong
program, and the two counts behind its shares of a peak."""

import importlib
import json
import os

import numpy as np
import pytest

from perfbench import common, flops, flops_lfm2, manifest, peaks
from perfbench.drivers import learner as learner_driver

CELL = "lfm2_policy.learner"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# What the family's table of widths is shrunk to, and the configuration
# keys that state the same sizes to the reference and the counts. At the
# published widths the 532M parameters with their gradients and
# optimizer state are 6 GB, which tier-1 must not allocate.
SMALL_FAMILY = dict(
    d_model=32, num_heads=4, kv_heads=2, head_dim=8, dense_width=48,
    expert_width=10, num_experts=16, experts_per_token=3,
    layer_period=("full_attention", "conv"),
)
SMALL_CONFIG = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=48, moe_intermediate_size=10,
    published_num_experts=16, num_experts=4, expert_share=[0, 4],
    num_experts_per_tok=3, num_hidden_layers=3, layers_run=[1, 2, 3],
    memory_len=7, unroll_length=9, batch_size=4, frame_shape=[8, 8, 4],
    program_argv=["--model", "lfm2", "--num_layers", "3",
                  "--memory_len", "7", "--expert_share", "0/4",
                  "--remat", "all", "--total_steps", "36"],
)
CONV, ATTENTION = "conv", "full_attention"
# The catalog row's `config`, copied here so that the test does not
# need the guide's file (it is checked against it where that is there).
PUBLISHED_CONFIG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [CONV, CONV, ATTENTION] + [CONV, CONV, CONV, ATTENTION] * 4
    + [CONV, CONV, ATTENTION, CONV, CONV],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}
REDUCED = {"num_hidden_layers": 5, "num_experts": 8}


def _config_file():
    with open(os.path.join(
        manifest.HERE, "configs", "lfm2_8b_policy.json"
    )) as f:
        return json.load(f)


@pytest.fixture
def tiny(monkeypatch):
    from torchbeast_tpu.models import lfm2

    monkeypatch.setattr(
        lfm2, "PUBLISHED", dict(lfm2.PUBLISHED, **SMALL_FAMILY)
    )
    cell = manifest.load_cell(CELL)
    return cell._replace(
        config=dict(cell.config, **SMALL_CONFIG),
        traffic=dict(cell.traffic, reference_rows=2, warmup_steps=2),
    )


def test_cell_loads_with_all_its_files():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config_name == "lfm2_8b_policy"
    assert cell.traffic_name == "learner"
    assert cell.traffic == manifest.load_cell("ouro_policy.learner").traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_frames_per_s", "peak_hbm_gib", "setup_s",
    }
    assert {m["name"] for m in cell.per_layer} == {
        "update_device_ms.learn", "device_idle_pct.learn",
        "mfu_pct.lfm2", "hbm_bw_pct.lfm2",
    }
    importlib.import_module("perfbench.reference." + cell.config["reference"])
    # The other transformer cells' metrics are their own still.
    for other in (
        "olmoe_policy.learner", "mellum2_policy.learner",
        "ouro_policy.learner", "kanana2_policy.learner",
        "nemotron3_policy.learner", "qwen3next_policy.learner",
    ):
        assert not {"mfu_pct.lfm2", "hbm_bw_pct.lfm2"} & {
            m["name"] for m in manifest.load_cell(other).per_layer
        }
    # One configuration, one cell, two metrics and four list entries,
    # each AFTER what the benchmark held (Qwen3-Next's were its last).
    # Not "the list's last": the next configuration is appended after
    # these.
    benchmark = manifest.load_benchmark()

    def follows(names, new, old):
        return names.count(new) == 1 and names.index(new) > names.index(old)

    assert follows(
        [c["name"] for c in benchmark["configs"]],
        cell.config_name, "qwen3next_80b_policy",
    )
    assert follows(
        [w["name"] for w in benchmark["workloads"]],
        CELL, "qwen3next_policy.learner",
    )
    metrics = [m["name"] for m in benchmark["per_layer"]]
    assert follows(metrics, "mfu_pct.lfm2", "hbm_bw_pct.qwen3next")
    assert follows(metrics, "hbm_bw_pct.lfm2", "mfu_pct.lfm2")
    listed = [
        m["name"] for m in benchmark["end_to_end"] + benchmark["per_layer"]
        if CELL in m.get("workloads", [])
    ]
    assert listed == [
        "learn_frames_per_s", "peak_hbm_gib", "update_device_ms.learn",
        "device_idle_pct.learn", "mfu_pct.lfm2", "hbm_bw_pct.lfm2",
    ]
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if CELL in m.get("workloads", []):
            lists = m["workloads"]
            assert "qwen3next_policy.learner" not in lists or follows(
                lists, CELL, "qwen3next_policy.learner"
            )
    entry = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "4x a chip's rows" in entry["why"]


def test_config_equals_the_catalog_row_outside_reduced():
    """Every key of the catalog's row under its own key; depth and the
    experts held the things cut, and stated as cut beside the published
    counts and the deployment. No width, head count, tap count, router
    width or experts a token differs from the row; `layer_types` is the
    published list whole, and `layers_run` names the layers of it that
    run."""
    config = _config_file()
    for key, value in PUBLISHED_CONFIG.items():
        assert config[key] == REDUCED.get(key, value), key
        if key in REDUCED:
            assert config["published_" + key] == value
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows if r["name"] == "LFM2-8B-A1B")
        assert row["config"] == PUBLISHED_CONFIG
        assert row["source_url"] == config["source"]
    entry = next(
        c for c in manifest.load_benchmark()["configs"]
        if c["name"] == config["name"]
    )
    assert entry["reduced"] == list(REDUCED)
    assert sorted(config["reduced_why"]) == sorted(entry["reduced"])
    assert entry["source"] == config["source"]
    # Published layers 1-5: the last leading dense layer and one period
    # `A c c c`; a quarter of the experts.
    assert config["layers_run"] == [1, 2, 3, 4, 5]
    assert flops_lfm2.layers_run(config) == [
        (CONV, True), (ATTENTION, False), (CONV, False), (CONV, False),
        (CONV, False),
    ]
    assert config["expert_share"] == [0, 4]
    assert config["num_experts"] * 4 == config["published_num_experts"]
    assert "four chips" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert config["unroll_length"] == 255 and config["memory_len"] == 4095
    assert config["batch_size"] == 16
    assert config["bias_update_rate"] == 0.001
    for key in (
        "expert_bias", "gate_sum_floor", "final_norm", "episode_ends",
        "rope_positions", "memory_len", "observation_encoder", "heads",
        "optimizer_and_precision", "side_inputs_start_at_zero",
        "learning_rate_schedule", "unroll_length_and_batch_size",
        "initialisation", "matmul_precision", "unused_keys",
    ):
        assert key in config["assumed"], key
    assert "GiB" in config["fit"]


def test_published_table_equals_the_file():
    from torchbeast_tpu.models import lfm2

    config = PUBLISHED_CONFIG
    assert lfm2.PUBLISHED == {
        "d_model": config["hidden_size"],
        "num_layers": config["num_hidden_layers"],
        "layer_types": tuple(config["layer_types"]),
        "layer_period": tuple(config["layer_types"][2:6]),
        "num_dense_layers": config["num_dense_layers"],
        "num_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "conv_kernel": config["conv_L_cache"],
        "conv_bias": config["conv_bias"],
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "num_experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "renormalise": config["norm_topk_prob"],
        "routed_scaling": config["routed_scaling_factor"],
        "use_expert_bias": config["use_expert_bias"],
        "norm_eps": config["norm_eps"],
        "rope_theta": config["rope_theta"],
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
    assert list(model.layers()) == flops_lfm2.layers_run(file)
    assert model.held_experts() == (0, file["num_experts"])
    assert model.bias_update_rate == file["bias_update_rate"]
    tail = Recurrent(((2, 2048),))
    assert model.layer_caches() == (
        tail, (file["memory_len"], 8, 64), tail, tail, tail,
    )
    assert model.remat is True
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


FAULTS = [
    None, "gate_applied_after_the_taps", "episode_end_leaves_the_tail",
    "gates_not_renormalised",
]


@pytest.mark.parametrize("fault", FAULTS)
def test_reference_sees_a_wrong_program(tiny, fault, monkeypatch):
    """The driver's comparison (the system's loss against the
    reference's, over the reference's scale, held to the driver's
    tolerance) on what the cell's traffic leaves out: states an actor
    carried. The program as it is passes; one that applies the gate `B
    *` AFTER the taps (C * B * conv(u) for C * conv(B * u)), one whose
    episode ends leave the convolutions' tails and taps uncut, one
    whose gates are the chosen scores as they are: each is seen."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import lfm2_policy as reference
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.models import lfm2

    config = tiny.config
    _, params, _, batch, state, _ = learner_driver.build(
        tiny, 7, jax.devices()[:1]
    )
    # After some training, not as seeded: operators and experts that
    # carry a larger part of the residual stream than lecun-normal
    # weights give them, and biases that choose.
    inner = dict(params["params"])
    for name in ("block_0", "block_2"):
        block = dict(inner[name])
        block["out_proj"] = {"kernel": 4.0 * block["out_proj"]["kernel"]}
        inner[name] = block
    for name in ("block_1", "block_2"):
        moe = dict(inner[name]["moe"])
        moe["w_down"] = 6.0 * moe["w_down"]
        moe["e_score_correction_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), moe["e_score_correction_bias"].shape
        )
        inner[name] = dict(inner[name], moe=moe)
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
    # written. The cell's batch ends an episode at 10% of its steps,
    # ~100 ends in the rows compared; of this one's 40 steps a third
    # end one, so that what an end does is as large a part of the loss.
    batch = dict(batch, done=jax.random.bernoulli(
        jax.random.PRNGKey(3), 0.35, batch["done"].shape
    ))
    inputs = {
        k: batch[k] for k in ("frame", "reward", "done", "last_action")
    }
    assert 10 <= int(batch["done"].sum()) <= 20
    jitted = jax.jit(lambda p, x, s: build_model()[0].apply(
        p, x, s, sample_action=False
    ))
    _, warm = jitted(params, inputs, state)
    assert all(np.any(leaf) for leaf in jax.tree_util.tree_leaves(warm))

    if fault == "episode_end_leaves_the_tail":
        right = lfm2.conv_over_episodes
        monkeypatch.setattr(
            lfm2, "conv_over_episodes",
            lambda inputs, tail, done, taps, bias: right(
                inputs, tail, jnp.zeros_like(done), taps, bias
            ),
        )
    elif fault == "gates_not_renormalised":
        monkeypatch.setattr(
            lfm2, "PUBLISHED", dict(lfm2.PUBLISHED, renormalise=False)
        )
    model, hp = build_model()

    def system_loss(params, batch, state):
        if fault == "gate_applied_after_the_taps":
            import flax.linen as nn

            def gate_later(next_fun, args, kwargs, context):
                out = next_fun(*args, **kwargs)
                if context.module.name == "in_proj":
                    # [B | C | u] -> [1 | C * B | u]: the taps then run
                    # over u alone and B gates their sum.
                    gate_in, gate_out, u = jnp.split(out, 3, axis=-1)
                    return jnp.concatenate(
                        [jnp.ones_like(gate_in), gate_out * gate_in, u], -1
                    )
                return out

            with nn.intercept_methods(gate_later):
                return learner_lib.compute_loss(
                    model, params, batch, state, hp
                )[0]
        return learner_lib.compute_loss(model, params, batch, state, hp)[0]

    system = jax.jit(system_loss)
    got = float(system(params, batch, warm))
    held = learner_lib.compute_loss(model, params, batch, warm, hp)[1][
        "moe_held_assignments"
    ]
    assert float(held) > 20  # of 240: the experts held have work
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
    parts = flops_lfm2.forward_flops_per_step(config)
    rows = config["batch_size"]
    tokens, d = 256 * rows, 2048
    assert parts["projection"] == tokens * 2 * 28224 * d
    assert parts["extras"] == tokens * 2 * 7 * d
    # Four conv operators: in_proj 2048 x 6144, three taps over 2048
    # channels, out_proj 2048 x 2048.
    assert parts["conv_in_proj"] == 4 * tokens * 2 * 2048 * 6144
    assert parts["conv_taps"] == 4 * tokens * 2 * 3 * 2048
    assert parts["conv_out_proj"] == 4 * tokens * 2 * 2048 * 2048
    # One attention layer of 32 heads of 64 on 8: q and o 2048 x 2048, k
    # and v 2048 x 512; query t of the 256 has 4,095 - t cached slots
    # inside its band and t + 1 steps of the unroll: 4,096 keys each, a
    # head counted at 64.
    assert parts["qkvo"] == tokens * 2 * 2048 * (2048 + 512 + 512 + 2048)
    assert flops_lfm2.cache_pairs(256, 4095) == 256 * 4095 - 32640
    assert flops_lfm2.unroll_pairs(256, 4095) == 32896
    assert parts["cache_leg"] == rows * (256 * 4095 - 32640) * 4 * 32 * 64
    assert parts["unroll_leg"] == rows * 32896 * 4 * 32 * 64
    # One dense SwiGLU of 7168; four MoE layers: the router over the
    # published 32; 8 of 32 held, ONE assignment a token on average,
    # three matrices of 2048 x 1792.
    assert parts["dense_mlp"] == tokens * 3 * 2 * d * 7168
    assert parts["router"] == 4 * tokens * 2 * d * 32
    assert parts["experts"] == 4 * tokens * 3 * 2 * 2048 * 1792
    assert parts["heads"] == tokens * 2 * d * 7
    total = sum(parts.values())
    shares = {k: v / total for k, v in parts.items()}
    # Of the trunk's multiply-adds (all but the projection, the extras
    # and the heads) the four conv LAYERS owe 79%, their operators'
    # projections the largest single term of the step.
    trunk = total - parts["projection"] - parts["extras"] - parts["heads"]
    conv_layers = (
        parts["conv_in_proj"] + parts["conv_taps"] + parts["conv_out_proj"]
        + parts["dense_mlp"] + 3 * parts["experts"] // 4
        + 3 * parts["router"] // 4
    )
    assert round(conv_layers / trunk, 2) == 0.79
    operators = shares["conv_in_proj"] + shares["conv_out_proj"]
    assert operators == max(
        operators, shares["projection"], shares["dense_mlp"],
        shares["experts"],
        shares["qkvo"] + shares["cache_leg"] + shares["unroll_leg"],
    )
    assert round(operators, 2) == 0.28
    assert round(shares["projection"], 2) == 0.24
    assert round(shares["experts"], 2) == round(shares["dense_mlp"], 2) == 0.18
    assert round(total / tokens / 1e9, 3) == 0.481  # GFLOP a token
    # Forward x3 but for the projection (no input gradient) and the
    # cache leg (dP and dq, nothing for the cached keys): x2.
    assert flops_lfm2.train_flops_per_step(config) == (
        3 * total - parts["projection"] - parts["cache_leg"]
    ) == 5_305_610_010_624
    # All the experts on one chip: 4 times the held experts' work.
    whole = dict(config, num_experts=32)
    assert flops_lfm2.forward_flops_per_step(whole)["experts"] == (
        4 * parts["experts"]
    )


def test_flops_by_hand_at_a_small_size():
    """The tiny cell: 40 tokens of width 32; the dense conv layer, one
    attention layer of 4 heads of 8 on 2 over 7 slots, one conv MoE
    layer; 4 of 16 experts of 10 held under 3 a token."""
    config = dict(_config_file(), **SMALL_CONFIG)
    parts = flops_lfm2.forward_flops_per_step(config)
    tokens, d, rows = 40, 32, 4
    assert parts["projection"] == tokens * 2 * 256 * d
    assert parts["conv_in_proj"] == 2 * tokens * 2 * d * 3 * d
    assert parts["conv_taps"] == 2 * tokens * 2 * 3 * d
    assert parts["conv_out_proj"] == 2 * tokens * 2 * d * d
    assert parts["qkvo"] == tokens * 2 * d * (32 + 16 + 16 + 32)
    # Query t of the 10 sees 7 - t cached slots (t < 7) and min(t, 7) +
    # 1 steps of the unroll.
    assert flops_lfm2.cache_pairs(10, 7) == 7 + 6 + 5 + 4 + 3 + 2 + 1
    assert flops_lfm2.unroll_pairs(10, 7) == 1 + 2 + 3 + 4 + 5 + 6 + 7 + 8 * 3
    assert parts["cache_leg"] == rows * 28 * 4 * 4 * 8
    assert parts["dense_mlp"] == tokens * 3 * 2 * d * 48
    assert parts["router"] == 2 * tokens * 2 * d * 16
    assert parts["experts"] == 2 * (tokens * 3 * 4 // 16) * 3 * 2 * d * 10


def test_param_count_is_the_programs(tiny):
    import jax

    _, params, *_ = learner_driver.build(tiny, 3, jax.devices()[:1])
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert flops_lfm2.param_count(tiny.config) == count


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
    assert flops_lfm2.param_count(config) == count == 532_101_383
    assert config["param_count"] == count
    # By hand, as ISSUE 53 has them.
    operator = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense = 3 * 2048 * 7168
    outside = 2048 * 32 + 32  # the router and its biases
    expert = 3 * 2048 * 1792
    norms = 2 * 2048
    assert (operator, attention, dense, outside + 8 * expert) == (
        flops_lfm2.conv_operator_param_count(config),
        flops_lfm2.attention_operator_param_count(config),
        flops_lfm2.ffn_param_count(config, True),
        flops_lfm2.ffn_param_count(config, False),
    )
    assert (expert, 8 * expert) == (11_010_048, 88_080_384)
    assert operator + dense + norms == 60_827_648
    assert attention + outside + 8 * expert + norms == 98_635_936
    assert operator + outside + 8 * expert + norms == 104_933_408
    assert count == (
        28224 * 2048 + 2048 + 7 * 2048 + 2048 + 60_827_648 + 98_635_936
        + 3 * 104_933_408 + 2048 + 2048 * 7 + 7
    )
    # 16 held (two chips a layer) would leave no room at 16 bytes each.
    assert (count + 4 * 8 * expert) * 16 > 14.1e9
    # The carried state: four tails [2, B, 2048] and one window of keys
    # and values for eight key/value heads of 64 with its validity
    # column.
    state_bytes = sum(
        4 * int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state)
    )
    assert state_bytes == 4 * rows * (
        4 * 2 * 2048 + 4095 * (2 * 8 * 64 + 1)
    ) == flops_lfm2.state_bytes(config)
    assert 4 * 2 * 2048 == 16_384  # bytes a row and conv layer
    assert flops_lfm2.least_bytes_per_step(config) == (
        6 * 4 * count + 2 * state_bytes
    )


@pytest.mark.parametrize("metric,want", [
    ("mfu_pct.lfm2", lambda c: 100 * flops_lfm2.train_flops_per_step(c)),
    ("hbm_bw_pct.lfm2", lambda c: (
        100 * flops_lfm2.least_bytes_per_step(c)
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
    # 0.1-1 s a step on one chip must read as a share under 100 (the
    # MXU's peak would do the counted operations in 0.03 s).
    from perfbench import readers

    for step_s in (0.1, 1.0):
        facts = {"values": {"steps_per_s": 1 / step_s, "chips": 1,
                            "peak_flops": 197e12}}
        assert 0 < readers.read_metric(spec, facts) < 100
