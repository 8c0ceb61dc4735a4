"""The cell `kanana2_policy.learner`: its files, the configuration
against the catalog's row, the learner driver tiny on the CPU with the
family's widths shrunk (control flow, not speed), the reference seeing a
wrong program, and the two counts behind its shares of a peak."""

import importlib
import json
import os

import numpy as np
import pytest

from perfbench import common, flops, flops_kanana2, manifest, peaks
from perfbench.drivers import learner as learner_driver

CELL = "kanana2_policy.learner"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# What the family's table of widths is shrunk to, and the configuration
# keys that state the same sizes to the reference and the counts. At the
# published widths the 568M parameters with their gradients and
# optimizer state are 6.8 GB and the caches 1.5 GB, which tier-1 must
# not allocate.
SMALL_FAMILY = dict(
    d_model=48, num_heads=4, latent_rank=24, nope_head_dim=16,
    rope_head_dim=8, value_head_dim=12, mlp_width=64, num_experts=16,
    experts_per_token=3, expert_width=20,
)
SMALL_CONFIG = dict(
    hidden_size=48, num_attention_heads=4, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=12,
    intermediate_size=64, published_n_routed_experts=16, n_routed_experts=2,
    num_experts_per_tok=3, moe_intermediate_size=20, num_hidden_layers=3,
    memory_len=7, unroll_length=3, batch_size=4, frame_shape=[8, 8, 4],
    program_argv=["--model", "kanana2", "--num_layers", "3",
                  "--memory_len", "7", "--expert_share", "0/8",
                  "--remat", "all", "--total_steps", "12"],
)
# The catalog row's `config`, copied here so that the test does not
# need the guide's file (it is checked against it where that is there).
PUBLISHED_CONFIG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256,
}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 16}


def _config_file():
    with open(os.path.join(
        manifest.HERE, "configs", "kanana2_30b_policy.json"
    )) as f:
        return json.load(f)


@pytest.fixture
def tiny(monkeypatch):
    from torchbeast_tpu.models import kanana2

    monkeypatch.setattr(
        kanana2, "PUBLISHED", dict(kanana2.PUBLISHED, **SMALL_FAMILY)
    )
    cell = manifest.load_cell(CELL)
    return cell._replace(
        config=dict(cell.config, **SMALL_CONFIG),
        traffic=dict(cell.traffic, reference_rows=2, warmup_steps=2),
    )


def test_cell_loads_with_all_its_files():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config_name == "kanana2_30b_policy"
    assert cell.traffic_name == "learner"
    assert cell.traffic == manifest.load_cell("ouro_policy.learner").traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "learn_frames_per_s", "peak_hbm_gib", "setup_s",
    }
    assert {m["name"] for m in cell.per_layer} == {
        "update_device_ms.learn", "device_idle_pct.learn",
        "mfu_pct.kanana2", "hbm_bw_pct.kanana2",
    }
    importlib.import_module("perfbench.reference." + cell.config["reference"])
    assert cell.config["program_argv"] == [
        "--model", "kanana2", "--num_layers", "5", "--memory_len", "4095",
        "--expert_share", "0/8", "--remat", "all", "--total_steps", "2560",
    ]
    # The other transformer cells' metrics are their own still.
    for other in (
        "olmoe_policy.learner", "mellum2_policy.learner",
        "ouro_policy.learner",
    ):
        assert not {"mfu_pct.kanana2", "hbm_bw_pct.kanana2"} & {
            m["name"] for m in manifest.load_cell(other).per_layer
        }
    # One configuration, one cell, two metrics and four list entries.
    benchmark = manifest.load_benchmark()
    assert [c["name"] for c in benchmark["configs"]][-1] == cell.config_name
    assert [w["name"] for w in benchmark["workloads"]][-1] == CELL
    assert [m["name"] for m in benchmark["per_layer"]][-2:] == [
        "mfu_pct.kanana2", "hbm_bw_pct.kanana2",
    ]


def test_config_equals_the_catalog_row_outside_reduced():
    """Every key of the catalog's row under its own key; depth and the
    routed experts held the two things cut, and stated as cut beside the
    published counts and the deployment."""
    config = _config_file()
    for key, value in PUBLISHED_CONFIG.items():
        assert config[key] == REDUCED.get(key, value), key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(
            r for r in rows if r["name"] == "kanana-2-30b-a3b-instruct-2601"
        )
        assert row["config"] == PUBLISHED_CONFIG
        assert row["source_url"] == config["source"]
    entry = next(
        c for c in manifest.load_benchmark()["configs"]
        if c["name"] == config["name"]
    )
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert sorted(config["reduced_why"]) == sorted(entry["reduced"])
    assert entry["source"] == config["source"]
    assert config["published_n_routed_experts"] == 128
    assert config["published_num_hidden_layers"] == 48
    assert config["expert_share"] == [0, 8]
    assert "eight chips share each layer" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert (config["batch_size"], config["unroll_length"]) == (32, 80)
    assert (config["memory_len"], config["bias_update_rate"]) == (4095, 0.001)
    for key in (
        "selection_bias_rule", "memory_len", "observation_encoder", "heads",
        "optimizer_and_precision", "side_inputs_start_at_zero",
        "learning_rate_schedule", "matmul_precision", "unused_keys",
    ):
        assert key in config["assumed"], key
    assert "GiB" in config["fit"]


def test_published_table_equals_the_file():
    from torchbeast_tpu.models import kanana2

    config = PUBLISHED_CONFIG
    assert kanana2.PUBLISHED == {
        "d_model": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "latent_rank": config["kv_lora_rank"],
        "nope_head_dim": config["qk_nope_head_dim"],
        "rope_head_dim": config["qk_rope_head_dim"],
        "value_head_dim": config["v_head_dim"],
        "num_layers": config["num_hidden_layers"],
        "dense_layers": config["first_k_dense_replace"],
        "mlp_width": config["intermediate_size"],
        "num_experts": config["n_routed_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "shared_experts": config["n_shared_experts"],
        "renormalise": config["norm_topk_prob"],
        "routed_scaling": config["routed_scaling_factor"],
        "rms_norm_eps": config["rms_norm_eps"],
        "rope_theta": config["rope_theta"],
    }
    # What the family does not write, because the config makes it a
    # no-op: a query bottleneck, rope scaling, selection by groups.
    assert config["q_lora_rank"] is None and config["rope_scaling"] is None
    assert config["n_group"] == config["topk_group"] == 1
    assert config["qk_head_dim"] == 128 + 64
    # The file's argv builds the cut the file states.
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast

    file = _config_file()
    flags = monobeast.make_parser().parse_args(file["program_argv"])
    model, _ = monobeast._init_model_and_params(
        flags, 6, 32, (84, 84, 4), init_params=False
    )
    assert model.num_layers == file["num_hidden_layers"]
    assert model.held_experts() == (0, file["n_routed_experts"])
    assert model.layer_caches() == ((file["memory_len"], 1, (512, 64)),) * 5
    assert model.bias_update_rate == file["bias_update_rate"]
    assert model.remat is True
    # One update's frames: the linear decay is at 0 from the second
    # update on (the file's `assumed.learning_rate_schedule` says why).
    hp = monobeast.hparams_from_flags(monobeast.make_parser().parse_args(
        file["program_argv"] + ["--unroll_length", "80", "--batch_size", "32"]
    ))
    assert learner_lib.updates_horizon(hp) == 1


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


class _Through:
    """A module's namespace with some names replaced."""

    def __init__(self, module, **replaced):
        self._module = module
        self.__dict__.update(replaced)

    def __getattr__(self, name):
        return getattr(self._module, name)


@pytest.mark.parametrize("fault", [
    None, "cached_rope_key_rotated_twice", "bias_added_into_the_gates",
    "shared_expert_dropped", "routed_scaling_factor_dropped",
])
def test_reference_sees_a_wrong_program(tiny, fault, monkeypatch):
    """The driver's comparison (the system's loss against the
    reference's, over the reference's scale, held to the driver's
    tolerance) on what the cell's traffic leaves out: caches an actor
    filled and selection biases that have moved. The program as it is
    passes; one that rotates the cached rope keys at write and at read,
    one whose gates are the biased scores, one without its shared
    expert, one without `routed_scaling_factor`: each is seen."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from perfbench.reference import kanana2_policy as reference
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu.models import kanana2, moe

    config = tiny.config
    _, params, _, batch, state, _ = learner_driver.build(
        tiny, 7, jax.devices()[:1]
    )
    # The selection biases after some training, not their zeros.
    inner = dict(params["params"])
    for layer in (1, 2):
        block = dict(inner[f"block_{layer}"])
        block["moe"] = dict(
            block["moe"],
            e_score_correction_bias=0.5 * jax.random.normal(
                jax.random.PRNGKey(layer), (16,)
            ),
        )
        inner[f"block_{layer}"] = block
    params = {"params": inner}

    def build_model():
        flags = monobeast.make_parser().parse_args(
            config["program_argv"][:-4]  # no --remat: modules as they are
            + ["--unroll_length", "3", "--batch_size", "4"]
        )
        model, _ = monobeast._init_model_and_params(
            flags, config["num_actions"], 4, (8, 8, 4), init_params=False
        )
        return model, monobeast.hparams_from_flags(flags)

    # Caches an actor would hold: one unroll in, by the program as it
    # is written.
    inputs = {
        k: batch[k] for k in ("frame", "reward", "done", "last_action")
    }
    _, warm = build_model()[0].apply(
        params, inputs, state, sample_action=False
    )
    assert all(float(entry[2].sum()) > 0 for entry in warm)

    if fault == "cached_rope_key_rotated_twice":
        right = kanana2.rope_pairs

        def wrong(x, positions, theta, time_axis=1):
            y = right(x, positions, theta, time_axis)
            # The cache leg's call: as if the key had been cached
            # rotated, and were rotated again where it is read.
            return right(y, positions, theta, 0) if time_axis == 0 else y

        monkeypatch.setattr(kanana2, "rope_pairs", wrong)
    elif fault == "bias_added_into_the_gates":
        # top_k over score + bias hands back the biased scores of the
        # chosen; the gates must be gathered from the scores alone.
        chosen = []

        def top_k(x, k):
            values, idx = jax.lax.top_k(x, k)
            chosen.append(values)
            return values, idx

        monkeypatch.setattr(moe, "jax", _Through(
            jax, lax=_Through(jax.lax, top_k=top_k)
        ))
        monkeypatch.setattr(moe, "jnp", _Through(
            jnp, take_along_axis=lambda a, idx, axis: chosen.pop()
        ))
    elif fault == "routed_scaling_factor_dropped":
        monkeypatch.setattr(
            kanana2, "PUBLISHED", dict(kanana2.PUBLISHED, routed_scaling=1.0)
        )
    model, hp = build_model()

    def dropped(next_fun, args, kwargs, context):
        out = next_fun(*args, **kwargs)
        if context.module.name == "shared_down":
            return jnp.zeros_like(out)
        return out

    def system_loss(params, batch, state):
        if fault == "shared_expert_dropped":
            with nn.intercept_methods(dropped):
                return learner_lib.compute_loss(
                    model, params, batch, state, hp
                )[0]
        return learner_lib.compute_loss(model, params, batch, state, hp)[0]

    got = float(system_loss(params, batch, warm))
    want, scale = map(
        float, reference.loss_and_scale(params, batch, warm, config)
    )
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
    parts = flops_kanana2.forward_flops_per_step(config)
    tokens, d = 81 * 32, 2048
    assert parts["projection"] == tokens * 2 * 28224 * d
    assert parts["extras"] == tokens * 2 * 7 * d
    # q 2048 x 6144, kv_a 2048 x 576, kv_b 512 x 8192 (this unroll's
    # tokens alone), o 4096 x 2048: five layers.
    per_token_qkvo = 2 * (2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    assert per_token_qkvo == 52_690_944
    assert parts["qkvo"] == 5 * tokens * per_token_qkvo
    # q_nope into the latent's space and the combine out of it: 32
    # heads x 128 x 512 each.
    assert parts["absorb"] == 5 * tokens * 2 * 2 * 32 * 128 * 512
    # Query t of the 81 has 4,095 - t cached slots inside its band and
    # t + 1 steps of the unroll: 4,096 keys each.
    assert flops_kanana2.cache_pairs(81, 4095) == 81 * 4095 - 3240 == 328_455
    assert flops_kanana2.unroll_pairs(81, 4095) == 3321
    from perfbench.flops_olmoe import band_keys

    assert band_keys(81, 4095) == 328_455 + 3321 == 81 * 4096
    # One 576-wide key and a 512-wide combine for all 32 heads; the
    # decompressed form would be 192 + 128 a head AND kv_b on every
    # cached slot (1.10 TFLOP a layer): 0.73 here.
    assert parts["cache_leg"] == 5 * 32 * 328_455 * 2 * 32 * (576 + 512)
    assert round(parts["cache_leg"] / 5 / 1e12, 2) == 0.73
    assert parts["unroll_leg"] == 5 * 32 * 3321 * 2 * 32 * (192 + 128)
    assert parts["mlp"] == tokens * 3 * 2 * d * 6144
    # Four MoE layers: the router over the published 128; 16 of 128
    # held, 6 / 8 of an assignment a token on average; the shared
    # SwiGLU of 2 x 768 for every token.
    assert parts["router"] == 4 * tokens * 2 * d * 128
    assert parts["experts"] == 4 * (tokens * 6 // 8) * 3 * 2 * d * 768
    assert parts["shared"] == 4 * tokens * 3 * 2 * d * 1536
    assert parts["heads"] == tokens * 2 * d * 7
    shares = {k: v / sum(parts.values()) for k, v in parts.items()}
    # The cache leg is most of the forward pass, the experts held 1.4%.
    assert round(shares["cache_leg"], 2) == 0.70
    assert round(shares["experts"], 3) == 0.014
    # Forward x3 but for the projection (no input gradient) and the
    # cache leg (dP and dq, nothing for the cached latents): x2.
    assert flops_kanana2.train_flops_per_step(config) == (
        3 * sum(parts.values()) - parts["projection"] - parts["cache_leg"]
    ) == 11_736_472_485_888
    assert round(2 * parts["cache_leg"] / 11_736_472_485_888, 2) == 0.62
    # All the experts on one chip: eight times the held experts' work.
    whole = dict(config, n_routed_experts=128)
    assert flops_kanana2.forward_flops_per_step(whole)["experts"] == (
        8 * parts["experts"]
    )


def test_param_count_is_the_programs(tiny):
    import jax

    _, params, *_ = learner_driver.build(tiny, 3, jax.devices()[:1])
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert flops_kanana2.param_count(tiny.config) == count


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
    assert flops_kanana2.param_count(config) == count == 568_124_423
    # By hand, as ISSUE 38 has them.
    attention = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
    assert attention == flops_kanana2.attention_param_count(config)
    assert attention == 26_345_984
    dense = attention + 2 * 2048 + 3 * 2048 * 6144
    sparse = (
        attention + 2 * 2048 + 2048 * 128 + 128
        + 16 * 3 * 2048 * 768 + 3 * 2048 * 1536
    )
    assert (dense, sparse) == (64_098_816, 111_547_008)
    assert count == (
        28224 * 2048 + 2048 + 7 * 2048 + 2048 + dense + 4 * sparse + 2048
        + 2048 * 7 + 7
    )
    # The carried state: five caches of a latent [4095, 32, 1, 512], a
    # rope key [4095, 32, 1, 64] and a validity column. As keys and
    # values of 32 heads of 192 and 128 it would be 17.8 times that.
    state_bytes = sum(
        4 * int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state)
    )
    assert state_bytes == 5 * 4 * 4095 * 32 * (576 + 1) == 1_512_201_600
    assert flops_kanana2.latent_cache_bytes(config) == (
        5 * 4 * 4095 * 32 * 576
    )
    assert flops_kanana2.least_bytes_per_step(config) == (
        6 * 4 * count + 2 * 5 * 4 * 4095 * 32 * 576
    ) == 16_654_147_752


@pytest.mark.parametrize("metric,want", [
    ("mfu_pct.kanana2", lambda c: 100 * flops_kanana2.train_flops_per_step(c)),
    ("hbm_bw_pct.kanana2", lambda c: (
        100 * flops_kanana2.least_bytes_per_step(c)
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
    # MXU's peak would do the counted operations in 0.06 s).
    from perfbench import readers

    for step_s in (0.1, 1.0):
        facts = {"values": {"steps_per_s": 1 / step_s, "chips": 1,
                            "peak_flops": 197e12}}
        assert 0 < readers.read_metric(spec, facts) < 100
