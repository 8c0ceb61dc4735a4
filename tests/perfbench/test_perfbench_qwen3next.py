"""The cell `qwen3next_policy.learner`: its files, the configuration
against the catalog's row, the learner driver tiny on the CPU with the
family's widths shrunk (control flow, not speed), the reference seeing a
wrong program, and the two counts behind its shares of a peak."""

import importlib
import json
import os

import numpy as np
import pytest

from perfbench import common, flops, flops_qwen3next, manifest, peaks
from perfbench.drivers import learner as learner_driver

CELL = "qwen3next_policy.learner"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# What the family's table of widths is shrunk to, and the configuration
# keys that state the same sizes to the reference and the counts. At the
# published widths the 606M parameters with their gradients and
# optimizer state are 7 GB, which tier-1 must not allocate.
SMALL_FAMILY = dict(
    d_model=32, attention_interval=2, num_heads=4, kv_heads=2, head_dim=16,
    delta_key_heads=2, delta_value_heads=4, delta_key_dim=6,
    delta_value_dim=5, chunk_size=4, num_experts=16, experts_per_token=3,
    expert_width=10, shared_width=12,
)
SMALL_CONFIG = dict(
    hidden_size=32, full_attention_interval=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=6, linear_value_head_dim=5,
    published_num_experts=16, num_experts=4, expert_share=[0, 4],
    num_experts_per_tok=3, moe_intermediate_size=10,
    shared_expert_intermediate_size=12, num_hidden_layers=2, memory_len=7,
    unroll_length=9, batch_size=4, frame_shape=[8, 8, 4],
    program_argv=["--model", "qwen3next", "--num_layers", "2",
                  "--memory_len", "7", "--expert_share", "0/4",
                  "--remat", "all", "--total_steps", "36"],
)
# The catalog row's `config`, copied here so that the test does not
# need the guide's file (it is checked against it where that is there).
PUBLISHED_CONFIG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 4, "num_experts": 32}


def _config_file():
    with open(os.path.join(
        manifest.HERE, "configs", "qwen3next_80b_policy.json"
    )) as f:
        return json.load(f)


@pytest.fixture
def tiny(monkeypatch):
    from torchbeast_tpu.models import qwen3next

    monkeypatch.setattr(
        qwen3next, "PUBLISHED", dict(qwen3next.PUBLISHED, **SMALL_FAMILY)
    )
    cell = manifest.load_cell(CELL)
    return cell._replace(
        config=dict(cell.config, **SMALL_CONFIG),
        traffic=dict(cell.traffic, reference_rows=2, warmup_steps=2),
    )


def test_cell_loads_with_all_its_files():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config_name == "qwen3next_80b_policy"
    assert cell.traffic_name == "learner"
    assert cell.traffic == manifest.load_cell("ouro_policy.learner").traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_frames_per_s", "peak_hbm_gib", "setup_s",
    }
    assert {m["name"] for m in cell.per_layer} == {
        "update_device_ms.learn", "device_idle_pct.learn",
        "mfu_pct.qwen3next", "hbm_bw_pct.qwen3next",
    }
    importlib.import_module("perfbench.reference." + cell.config["reference"])
    # The other transformer cells' metrics are their own still.
    for other in (
        "olmoe_policy.learner", "mellum2_policy.learner",
        "ouro_policy.learner", "kanana2_policy.learner",
        "nemotron3_policy.learner",
    ):
        assert not {"mfu_pct.qwen3next", "hbm_bw_pct.qwen3next"} & {
            m["name"] for m in manifest.load_cell(other).per_layer
        }
    # One configuration, one cell, two metrics and four list entries,
    # each AFTER what the benchmark held (Nemotron-3's were its last).
    # Not "the list's last": the next configuration is appended after
    # these.
    benchmark = manifest.load_benchmark()

    def follows(names, new, old):
        return names.count(new) == 1 and names.index(new) > names.index(old)

    assert follows(
        [c["name"] for c in benchmark["configs"]],
        cell.config_name, "nemotron3_super_policy",
    )
    assert follows(
        [w["name"] for w in benchmark["workloads"]],
        CELL, "nemotron3_policy.learner",
    )
    metrics = [m["name"] for m in benchmark["per_layer"]]
    assert follows(metrics, "mfu_pct.qwen3next", "hbm_bw_pct.nemotron3")
    assert follows(metrics, "hbm_bw_pct.qwen3next", "mfu_pct.qwen3next")
    listed = [
        m["name"] for m in benchmark["end_to_end"] + benchmark["per_layer"]
        if CELL in m.get("workloads", [])
    ]
    assert listed == [
        "learn_frames_per_s", "peak_hbm_gib", "update_device_ms.learn",
        "device_idle_pct.learn", "mfu_pct.qwen3next", "hbm_bw_pct.qwen3next",
    ]
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if CELL in m.get("workloads", []):
            lists = m["workloads"]
            assert "nemotron3_policy.learner" not in lists or follows(
                lists, CELL, "nemotron3_policy.learner"
            )


def test_config_equals_the_catalog_row_outside_reduced():
    """Every key of the catalog's row under its own key; depth and the
    experts held the things cut, and stated as cut beside the published
    counts and the deployment. No width, head size, state size, router
    width or experts a token differs from the row."""
    config = _config_file()
    for key, value in PUBLISHED_CONFIG.items():
        assert config[key] == REDUCED.get(key, value), key
        if key in REDUCED:
            assert config["published_" + key] == value
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(
            r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct"
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
    # One whole period, and a sixteenth of the experts.
    assert config["num_hidden_layers"] == config["full_attention_interval"]
    assert config["expert_share"] == [0, 16]
    assert config["num_experts"] * 16 == config["published_num_experts"]
    assert "sixteen chips" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert config["unroll_length"] == 255 and config["memory_len"] == 4095
    assert (config["chunk_size"], config["router_aux_loss_coef"]) == (
        64, 0.001
    )
    for key in (
        "chunk_size", "initialisation", "router_aux_loss_coef",
        "l2_norm_eps", "norms", "multi_token_prediction", "matmul_precision",
        "episode_ends", "rope_positions", "memory_len",
        "observation_encoder", "heads", "optimizer_and_precision",
        "side_inputs_start_at_zero", "learning_rate_schedule",
        "unroll_length_and_batch_size", "unused_keys",
    ):
        assert key in config["assumed"], key
    assert "GiB" in config["fit"]


def test_published_table_equals_the_file():
    from torchbeast_tpu.models import qwen3next

    config = PUBLISHED_CONFIG
    assert qwen3next.PUBLISHED == {
        "d_model": config["hidden_size"],
        "num_layers": config["num_hidden_layers"],
        "attention_interval": config["full_attention_interval"],
        "num_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rotary_factor": config["partial_rotary_factor"],
        "rope_theta": config["rope_theta"],
        "delta_key_heads": config["linear_num_key_heads"],
        "delta_value_heads": config["linear_num_value_heads"],
        "delta_key_dim": config["linear_key_head_dim"],
        "delta_value_dim": config["linear_value_head_dim"],
        "conv_kernel": config["linear_conv_kernel_dim"],
        "chunk_size": _config_file()["chunk_size"],
        "num_experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["shared_expert_intermediate_size"],
        "renormalise": config["norm_topk_prob"],
        "rms_norm_eps": config["rms_norm_eps"],
    }
    # What the family does not write, because the config makes it a
    # no-op.
    assert config["decoder_sparse_step"] == 1 and not config["mlp_only_layers"]
    assert config["rope_scaling"] is None and not config["use_sliding_window"]
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
    assert model.held_experts() == (0, file["num_experts"])
    assert model.aux_loss_weight == file["router_aux_loss_coef"]
    assert model.layer_caches() == (
        Recurrent(((32, 128, 128), (3, 8192))), None,
    ) * 3 + ((file["memory_len"], 2, 256), None)
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
    None, "solve_left_out", "reset_ignored_inside_a_chunk",
    "gate_applied_before_the_norm", "shared_expert_gate_dropped",
    "rope_on_every_column",
]


@pytest.mark.parametrize("fault", FAULTS)
def test_reference_sees_a_wrong_program(tiny, fault, monkeypatch):
    """The driver's comparison (the system's loss against the
    reference's, over the reference's scale, held to the driver's
    tolerance) on what the cell's traffic leaves out: states an actor
    carried. The program as it is passes; one whose chunks leave the
    triangular solve out (W = I), one whose L and intra-chunk weights
    ignore an episode end (the one matrix of decays both are laid over;
    the states between chunks still reset), one that gates before the
    norm (Nemotron-3's order), one without the shared expert's token
    gate, one with RoPE on all of a head's columns: each is seen."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import qwen3next_policy as reference
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.models import qwen3next

    config = tiny.config
    _, params, _, batch, state, _ = learner_driver.build(
        tiny, 7, jax.devices()[:1]
    )
    # After some training, not as seeded: norms whose scales have moved
    # (zero-centred ones from zero, the gated one from one), so that a
    # norm in the wrong place shows, and decays at which the carried
    # state is a large part of a DeltaNet layer's output.
    inner = dict(params["params"])
    block = dict(inner["block_0"])
    block["gate_norm"] = block["gate_norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), block["gate_norm"].shape
    )
    block["dt_bias"] = jnp.full_like(block["dt_bias"], -1.0)
    block["A_log"] = jnp.full_like(block["A_log"], -1.0)
    # A mixer and shared experts that carry a larger part of the
    # residual stream than lecun-normal weights give them, and token
    # gates away from a half.
    block["out_proj"] = {"kernel": 3.0 * block["out_proj"]["kernel"]}
    inner["block_0"] = block
    # Sharper attention (a query twice as long) that carries more too.
    attend = dict(inner["block_2"])
    attend["q_norm"] = {"scale": attend["q_norm"]["scale"] + 1.5}
    attend["o"] = {"kernel": 4.0 * attend["o"]["kernel"]}
    inner["block_2"] = attend
    for name in ("block_1", "block_3"):
        moe = dict(inner[name]["moe"])
        for leaf in ("shared_down", "shared_expert_gate"):
            moe[leaf] = {"kernel": 4.0 * moe[leaf]["kernel"]}
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

    if fault == "solve_left_out":
        monkeypatch.setattr(
            qwen3next, "unit_lower_inverse",
            lambda L: jnp.broadcast_to(jnp.eye(L.shape[-1]), L.shape),
        )
    elif fault == "reset_ignored_inside_a_chunk":
        right = qwen3next.reaches
        monkeypatch.setattr(
            qwen3next, "reaches", lambda ends: right(jnp.zeros_like(ends))
        )
    elif fault == "gate_applied_before_the_norm":
        right = qwen3next.normed_then_gated
        monkeypatch.setattr(
            qwen3next, "normed_then_gated",
            lambda o, z, scale, eps: right(
                o * jax.nn.silu(z), jnp.full_like(z, 1.2784645),  # silu: 1
                scale, eps,
            ),
        )
    elif fault == "rope_on_every_column":
        monkeypatch.setattr(
            qwen3next, "PUBLISHED",
            dict(qwen3next.PUBLISHED, rotary_factor=1.0),
        )
    model, hp = build_model()

    def system_loss(params, batch, state):
        if fault == "shared_expert_gate_dropped":
            import flax.linen as nn

            def open_gate(next_fun, args, kwargs, context):
                out = next_fun(*args, **kwargs)
                if context.module.name == "shared_expert_gate":
                    return jnp.full_like(out, 30.0)  # its sigmoid is 1
                return out

            with nn.intercept_methods(open_gate):
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
    parts = flops_qwen3next.forward_flops_per_step(config)
    rows = config["batch_size"]
    tokens, d = 256 * rows, 2048
    assert parts["projection"] == tokens * 2 * 28224 * d
    assert parts["extras"] == tokens * 2 * 7 * d
    # Three DeltaNet layers: in_proj_qkvz 2048 x 12,288 and in_proj_ba
    # 2048 x 64, a 4-tap convolution over 8,192 channels, the
    # recurrence's three products a value head over a [128, 128] state,
    # out_proj 4096 x 2048.
    assert parts["delta_in_proj"] == 3 * tokens * 2 * 2048 * (12288 + 64)
    assert parts["delta_conv"] == 3 * tokens * 2 * 4 * 8192
    assert parts["delta_scan"] == 3 * tokens * 3 * 2 * 32 * 128 * 128
    assert parts["delta_out_proj"] == 3 * tokens * 2 * 4096 * 2048
    # One attention layer: q with its gate 2048 x 8,192, k and v 2048 x
    # 512, o 4096 x 2048; query t of the 256 has 4,095 - t cached slots
    # inside its band and t + 1 steps of the unroll: 4,096 keys each.
    assert parts["qkvo"] == tokens * 2 * 2048 * (8192 + 512 + 512 + 4096)
    assert flops_qwen3next.cache_pairs(256, 4095) == 256 * 4095 - 32640
    assert flops_qwen3next.unroll_pairs(256, 4095) == 32896
    assert parts["cache_leg"] == rows * (256 * 4095 - 32640) * 4 * 16 * 256
    assert parts["unroll_leg"] == rows * 32896 * 4 * 16 * 256
    # Four MoE parts: the router over the published 512 and the shared
    # expert's gate; 32 of 512 held, 10 / 16 of an assignment a token
    # on average, three matrices of 2048 x 512; the shared SwiGLU of 512.
    assert parts["router"] == 4 * tokens * 2 * d * 513
    assert parts["experts"] == 4 * (tokens * 10 // 16) * 3 * 2 * 2048 * 512
    assert parts["shared"] == 4 * tokens * 3 * 2 * d * 512
    assert parts["heads"] == tokens * 2 * d * 7
    total = sum(parts.values())
    shares = {k: v / total for k, v in parts.items()}
    # The three DeltaNet layers owe the most, the scan itself little of
    # it: its cost is the chunked form's, which is not owed.
    delta = sum(v for k, v in shares.items() if k.startswith("delta"))
    assert 0.40 < delta < 0.45
    assert round(shares["delta_scan"], 3) == 0.019
    attention = shares["qkvo"] + shares["cache_leg"] + shares["unroll_leg"]
    assert 0.23 < attention < 0.26
    assert round(shares["projection"], 2) == 0.23
    moe_parts = shares["router"] + shares["experts"] + shares["shared"]
    assert 0.09 < moe_parts < 0.11
    assert round(total / tokens / 1e9, 3) == 0.498  # GFLOP a token
    # Forward x3 but for the projection (no input gradient) and the
    # cache leg (dP and dq, nothing for the cached keys): x2.
    assert flops_qwen3next.train_flops_per_step(config) == (
        3 * total - parts["projection"] - parts["cache_leg"]
    ) == 5_383_875_723_264
    # All the experts on one chip: 16 times the held experts' work.
    whole = dict(config, num_experts=512)
    assert flops_qwen3next.forward_flops_per_step(whole)["experts"] == (
        16 * parts["experts"]
    )


def test_param_count_is_the_programs(tiny):
    import jax

    _, params, *_ = learner_driver.build(tiny, 3, jax.devices()[:1])
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert flops_qwen3next.param_count(tiny.config) == count


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
    assert flops_qwen3next.param_count(config) == count == 605_711_431
    assert config["param_count"] == count
    # By hand, as ISSUE 46 has them.
    delta = (
        2048 * 12288 + 2048 * 64 + 4 * 8192 + 2 * 32 + 128 + 4096 * 2048
    )
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    outside = 2048 * 512 + 3 * 2048 * 512 + 2048
    expert = 3 * 2048 * 512
    assert (delta, attention, outside + 32 * expert) == (
        flops_qwen3next.delta_mixer_param_count(config),
        flops_qwen3next.attention_mixer_param_count(config),
        flops_qwen3next.moe_param_count(config),
    )
    assert (delta, attention, outside, expert) == (
        33_718_464, 27_263_488, 4_196_352, 3_145_728
    )
    period = 3 * delta + attention + 4 * (outside + 32 * expert)
    assert period == 547_857_472
    assert count == (
        28224 * 2048 + 2048 + 7 * 2048 + 2048 + period + 4 * 2 * 2048
        + 2048 + 2048 * 7 + 7
    )
    # The carried state: three matrix states [32, B, 128, 128] with conv
    # tails [3, B, 8192], and one window of keys and values for two
    # key/value heads of 256 with its validity column.
    state_bytes = sum(
        4 * int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state)
    )
    assert state_bytes == 4 * rows * (
        3 * (32 * 128 * 128 + 3 * 8192) + 4095 * (2 * 2 * 256 + 1)
    ) == flops_qwen3next.state_bytes(config)
    assert 4 * 3 * (32 * 128 * 128 + 3 * 8192) == 6_586_368
    assert flops_qwen3next.least_bytes_per_step(config) == (
        6 * 4 * count + 2 * state_bytes
    )


@pytest.mark.parametrize("metric,want", [
    ("mfu_pct.qwen3next",
     lambda c: 100 * flops_qwen3next.train_flops_per_step(c)),
    ("hbm_bw_pct.qwen3next", lambda c: (
        100 * flops_qwen3next.least_bytes_per_step(c)
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
