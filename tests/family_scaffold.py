"""The one scaffold under tier-1's tests of flax modules: a module is
traced, never run op by op, and a toy family is built and compiled ONCE
a process.

THE RULE (ISSUE 45 found it, ISSUE 49 made it every file's): run
eagerly, a flax module is one XLA compile an op (a toy family 1,300 of
them, 79% of a case), and the suite's persistent cache keeps no compile
under 0.5 s. So every `init`, `apply` and gradient of a module goes
through `jax.jit`:

- `init(module, rngs, *args)` and `apply(module, **static)` serve ANY
  flax module (a layer, a block, a whole net), memoised for the life of
  the process by the module's fields and traced once an input shape. A
  gradient is `jax.jit(jax.grad(...))` around `module.apply` in the
  case itself. A plain `jnp` function under test is called through
  `jax.jit` too, once a shape.
- For the twelve policy families: the toy batch (`inputs`,
  `learner_batch`), `build(family, **overrides) -> (model, params)`,
  `expert_layer(family, held)`, `warm_state`, `reference_config`, and
  jitted callables for the four programs the cases run again and again
  (`forward`, `loss_and_grads`, `reference_forward`,
  `reference_loss_and_grads`), with the three comparisons several
  families make in the same words (`assert_agrees_with_the_reference`,
  `assert_stepwise_acting_equals_the_batch_forward`,
  `assert_state_table_acting_equals_the_batch_forward`). They are
  callers of `init` and `apply`, not a second copy.
- Memoised parameters are READ-ONLY: a case that alters them builds a
  new tree around the leaves it replaces (`dict(inner, ...)`), never
  writes into one.
- A case whose trace must be its own (it monkeypatches a rule that is
  read at trace time) jits a function of its own, or asks for
  `loss_and_grads.__wrapped__(model)`; a case that must run eagerly (it
  hands `DeviceStateTable` an `act_fn` the table jits itself, or two
  XLA programs round further apart than its tolerance: ouro's remat
  case) calls `model.apply` or passes `jit=False`, and says so in one
  line.

THE RULE FOR THE NEXT FAMILY (a `model_config` PR): one `Family` entry
below (its class, its `SMALL` table, its reference, `perturb`: the
values for what the family starts at zero or one, and `experts` if it
can hold a share of a layer's experts: the shares test is then an id of
`tests/test_families_shares.py::test_the_expert_shares_add_up_to_the_
uncut_layer`, not a copy); one file `tests/test_<family>.py` with the
cases that are the family's own, on this scaffold, building no layer by
hand; one file `tests/test_chip_compile_<family>.py` with its
whole-cell compile (fixtures: `tests/chip_fixtures.py`); one id in each
parametrised test of `tests/test_families.py`, `tests/test_families_
remat.py`, `tests/test_families_shares.py`, `tests/test_monobeast_
families.py::test_train_family_through_main` and `tests/test_
polybeast_families.py::test_polybeast_train_family`. No edit to
another family's file. WHAT A FAMILY COSTS
tier-1 (CPU-s in the driver's command, the builder's junit file, PR
49; Qwen3-Next, the dearest): 706, where it was 927: its own files 288
(215 + 73), its whole-cell compile 110, its ids in the three
`test_families*` files 52, its case through `main` 13 and through
`train` 78, and `tests/perfbench/`'s file 165 (a `benchmark` issue's).
"""

import dataclasses
import functools
from types import ModuleType
from typing import Any, Callable, Optional

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from perfbench.reference import (
    granite4_policy,
    kanana2_policy,
    lfm2_policy,
    ling3_policy,
    mellum2_policy,
    nemotron3_policy,
    olmoe_policy,
    ouro_policy,
    phi4flash_policy,
    qwen3next_policy,
    trinity_policy,
    xing4_policy,
)
from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.models import (
    Granite4Net,
    Kanana2Net,
    Lfm2Net,
    Ling3Net,
    Mellum2Net,
    Nemotron3Net,
    OLMoENet,
    OuroNet,
    Phi4FlashNet,
    Qwen3NextNet,
    TrinityNet,
    Xing4Net,
    granite4,
    kanana2,
    lfm2,
    ling3,
    mellum2,
    moe,
    nemotron3,
    olmoe,
    ouro,
    phi4flash,
    qwen3next,
    trinity,
    xing4,
)
from torchbeast_tpu.runtime.state_table import DeviceStateTable

B, A = 2, 4
FRAME = (8, 8, 1)
_LOSS_COSTS = {
    "discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
}


def inputs(seed, done_steps=(), t=6, rows=B):
    rng = np.random.default_rng(seed)
    done = np.zeros((t, rows), bool)
    for step, row in done_steps:
        done[step, row] = True
    return {
        "frame": jnp.asarray(
            rng.integers(0, 256, (t, rows) + FRAME, dtype=np.uint8)
        ),
        "reward": jnp.asarray(rng.standard_normal((t, rows)), jnp.float32),
        "done": jnp.asarray(done),
        "last_action": jnp.asarray(rng.integers(0, A, (t, rows))),
    }


def learner_batch(seed, done_steps, t=6):
    rng = np.random.default_rng(seed + 100)
    lead = (t, B)
    return dict(
        inputs(seed, done_steps, t=t),
        episode_return=jnp.asarray(rng.standard_normal(lead), jnp.float32),
        episode_step=jnp.zeros(lead, jnp.int32),
        action=jnp.asarray(rng.integers(0, A, lead)),
        policy_logits=jnp.asarray(
            rng.standard_normal(lead + (A,)), jnp.float32
        ),
        baseline=jnp.asarray(rng.standard_normal(lead), jnp.float32),
    )


def forward_stats(model, params, rows, done_steps, t):
    """The update's stats from the forward alone (`learner.compute_
    loss`, no gradient) on the toy learner batch side by side until it
    is `rows` wide, from empty states: enough tokens for a window of
    the sorted rows to have rungs, through the interpreted kernels."""
    batch = {
        k: jnp.concatenate([v] * (rows // B), axis=1)
        for k, v in learner_batch(3, done_steps, t=t).items()
    }
    hp = learner_lib.HParams(batch_size=rows, unroll_length=t - 1)
    jitted = jax.jit(lambda p: learner_lib.compute_loss(
        model, p, batch, model.initial_state(rows), hp
    ))
    _, stats = jitted(params)
    return stats


def with_zeroed(params, blocks, leaves=("router",)):
    """`params` with the named leaves of each named block's `moe` at
    zero: a router of zeros sends every token to experts 0 .. K-1
    (ties go to the first)."""
    inner = dict(params["params"])
    for name in blocks:
        block = dict(inner[name])
        block["moe"] = dict(block["moe"], **jax.tree_util.tree_map(
            jnp.zeros_like, {leaf: block["moe"][leaf] for leaf in leaves}
        ))
        inner[name] = block
    return {"params": inner}


class Through:
    """A module's namespace with some names replaced: what a case lays
    over a family module's own `nn` or `jax` to plant a fault there and
    nowhere else (`monkeypatch.setattr(family, "nn", Through(nn, ...))`)."""

    def __init__(self, module, **replaced):
        self._module = module
        self.__dict__.update(replaced)

    def __getattr__(self, name):
        return getattr(self._module, name)


def _normal(seed, shape, scale):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape)


def _with_extras(inner):
    """The side inputs' projection, which the family starts at zero."""
    assert not np.any(inner["extras"]["kernel"])
    kernel = _normal(7, inner["extras"]["kernel"].shape, 0.3)
    return dict(inner, extras=dict(inner["extras"], kernel=kernel))


def _with_selection_bias(block, seed):
    assert not np.any(block["moe"]["e_score_correction_bias"])
    bias = _normal(
        seed, block["moe"]["e_score_correction_bias"].shape, 0.1
    )
    return dict(block, moe=dict(block["moe"], e_score_correction_bias=bias))


def _perturb_mellum2(model, params):
    return {"params": _with_extras(params["params"])}


def _perturb_ouro(model, params):
    # Norm scales start at one and the gate's bias at zero: move them, so
    # that a norm left out or applied twice, or a gate misread, shows.
    flat, unravel = jax.flatten_util.ravel_pytree(params)
    return unravel(flat + _normal(7, flat.shape, 0.2))


def _perturb_kanana2(model, params):
    inner = _with_extras(params["params"])
    for layer in range(1, model.num_layers):
        name = f"block_{layer}"
        inner[name] = _with_selection_bias(inner[name], layer)
    return {"params": inner}


def _perturb_nemotron3(model, params):
    # And D and the norms, which start at one.
    inner = _with_extras(params["params"])
    for layer, letter in enumerate(model.pattern()):
        name = f"block_{layer}"
        if letter == "E":
            inner[name] = _with_selection_bias(inner[name], layer)
        elif letter == "M":
            block = dict(inner[name])
            for i, leaf in enumerate(("D", "gate_norm")):
                block[leaf] = block[leaf] + _normal(
                    10 * layer + i, block[leaf].shape, 0.3
                )
            inner[name] = block
    return {"params": inner}


def _perturb_qwen3next(model, params):
    # And the zero-centred norms' scales, which start at zero, and the
    # gated norm's, which starts at one; a seed a leaf moved.
    inner = _with_extras(params["params"])
    seeds = iter(range(1, 1000))

    def moved(tree):
        out = {}
        for name, leaf in sorted(tree.items()):
            if isinstance(leaf, dict):
                out[name] = leaf if name == "moe" else moved(leaf)
            elif name in ("scale", "gate_norm"):
                out[name] = leaf + _normal(next(seeds), leaf.shape, 0.3)
            else:
                out[name] = leaf
        return out

    for name in sorted(inner):
        if name.startswith("block_") or name == "final_norm":
            inner[name] = moved(inner[name])
    return {"params": inner}


def _perturb_lfm2(model, params):
    # And every norm's scale (a layer's two, q_norm and k_norm, the
    # final one), which starts at one; a seed a leaf moved.
    inner = _with_extras(params["params"])
    seeds = iter(range(1, 1000))

    def moved(norm):
        scale = norm["scale"]
        return {"scale": scale + _normal(next(seeds), scale.shape, 0.3)}

    inner["final_norm"] = moved(inner["final_norm"])
    for name in sorted(n for n in inner if n.startswith("block_")):
        block = dict(inner[name])
        if "moe" in block:
            block = _with_selection_bias(block, next(seeds))
        for leaf in sorted(block):
            if leaf.endswith("norm"):
                block[leaf] = moved(block[leaf])
        inner[name] = block
    return {"params": inner}


def _perturb_phi4flash(model, params):
    # And everything else the family starts at zero or one: every bias
    # of a projection, every norm's scale and bias, the scan's skip `D`;
    # a seed a leaf moved.
    inner = _with_extras(params["params"])
    seeds = iter(range(1, 1000))

    def moved(tree):
        return {
            name: moved(leaf) if isinstance(leaf, dict)
            else leaf + _normal(next(seeds), leaf.shape, 0.3)
            if name in ("bias", "scale", "D") else leaf
            for name, leaf in sorted(tree.items())
        }

    for name in sorted(inner):
        if name.startswith("block_") or name == "final_norm":
            inner[name] = moved(inner[name])
    return {"params": inner}


def _perturb_xing4(model, params):
    # Kanana-2's, and the stream maps: `a`, which starts at 0.01, at
    # 0.5, 0.4 and 1.0, and H_res's off-diagonal logits, which start at
    # -12, around -4. The maps then move with the token, and H_res is
    # far enough from the identity, and near enough to a matrix that
    # falls apart, that twenty Sinkhorn steps have not converged (rows
    # 1e-2 from 1): a map computed from the wrong stream shows, and so
    # does a step left out.
    inner = _perturb_kanana2(model, params)["params"]
    n = model.streams
    for layer in range(model.num_layers):
        name = f"block_{layer}"
        block = dict(inner[name])
        for i, maps in enumerate(("attn_hc", "mlp_hc")):
            b = block[maps]["b"]
            res = jnp.where(
                jnp.eye(n, dtype=bool), 0.0,
                -4.0 + _normal(10 * layer + i, (n, n), 0.5),
            ).reshape(-1)
            block[maps] = dict(
                block[maps], a=jnp.asarray([0.5, 0.4, 1.0]),
                b=b.at[2 * n :].set(res),
            )
        inner[name] = block
    return {"params": inner}


# Trinity's: the side inputs, the selection biases, and every norm's
# scale (a layer's four, q_norm and k_norm, the final one), which starts
# at one: a post-norm left out, or applied to the sum, then shows.
_perturb_trinity = _perturb_lfm2


def _perturb_ling3(model, params):
    # The side inputs, the selection biases, every norm's scale (a
    # layer's two, the latent's, the KDA output norm's, the final one),
    # which start at one; and a KDA layer's `dt_bias` up by 4, so that
    # its log-decays spread over (-5, 0) and some sit at the floor (at
    # its init nearly every channel's is ~0 and a decay misapplied
    # would not show); a seed a leaf moved.
    inner = _with_extras(params["params"])
    seeds = iter(range(1, 1000))

    def moved(tree):
        out = {}
        for name, leaf in sorted(tree.items()):
            if name == "moe":
                out[name] = dict(
                    leaf, e_score_correction_bias=_normal(
                        next(seeds), leaf["e_score_correction_bias"].shape,
                        0.1,
                    ),
                )
            elif isinstance(leaf, dict):
                out[name] = moved(leaf)
            elif name in ("scale", "gate_norm"):
                out[name] = leaf + _normal(next(seeds), leaf.shape, 0.3)
            elif name == "dt_bias":
                out[name] = leaf + 4.0 + _normal(next(seeds), leaf.shape, 1.0)
            else:
                out[name] = leaf
        return out

    for name in sorted(inner):
        if name.startswith("block_") or name == "final_norm":
            inner[name] = moved(inner[name])
    return {"params": inner}


def _perturb_granite4(model, params):
    # The side inputs, and what starts at one: every norm's scale (a
    # layer's two, the final one), the gated norm's and the skip `D`; a
    # seed a leaf moved. A norm left out, or a multiplier, then shows.
    inner = _with_extras(params["params"])
    seeds = iter(range(1, 1000))

    def moved(leaf):
        return leaf + _normal(next(seeds), leaf.shape, 0.3)

    inner["final_norm"] = {"scale": moved(inner["final_norm"]["scale"])}
    for name in sorted(n for n in inner if n.startswith("block_")):
        block = dict(inner[name])
        for leaf in sorted(block):
            if leaf in ("D", "gate_norm"):
                block[leaf] = moved(block[leaf])
            elif leaf.endswith("norm"):
                block[leaf] = {"scale": moved(block[leaf]["scale"])}
        inner[name] = block
    return {"params": inner}


def _config_olmoe(model):
    return {
        "num_attention_heads": model.num_heads,
        "num_experts": model.num_experts,
        "num_experts_per_tok": model.experts_per_token,
        "num_hidden_layers": model.num_layers,
        "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "load_balance_weight": 0.01,
    }


YARN_CONFIG = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782,
}


def _config_mellum2(model):
    return {
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.kv_heads,
        "head_dim": model.head_dim,
        "sliding_window": model.sliding_window,
        "layer_types": list(mellum2.PUBLISHED["layer_period"]) * 7,
        "num_hidden_layers": model.num_layers,
        "published_num_experts": model.num_experts,
        "num_experts": model.num_experts // model.expert_share[1],
        "expert_share": list(model.expert_share),
        "num_experts_per_tok": model.experts_per_token,
        "norm_topk_prob": True,
        "rms_norm_eps": 1e-6,
        "rope_parameters": {
            "full_attention": YARN_CONFIG,
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 500000,
            },
        },
        "load_balance_weight": 0.001,
    }


def _config_ouro(model):
    return {
        "num_attention_heads": model.num_heads,
        "head_dim": model.head_dim,
        "num_hidden_layers": model.num_layers,
        "total_ut_steps": model.passes,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
    }


def _config_kanana2(model):
    return {
        "num_attention_heads": model.num_heads,
        "kv_lora_rank": model.latent_rank, "q_lora_rank": None,
        "qk_nope_head_dim": model.nope_head_dim,
        "qk_rope_head_dim": model.rope_head_dim,
        "qk_head_dim": model.nope_head_dim + model.rope_head_dim,
        "v_head_dim": model.value_head_dim,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1e6,
        "rms_norm_eps": 1e-6, "num_hidden_layers": model.num_layers,
        "first_k_dense_replace": 1,
        "published_n_routed_experts": model.num_experts,
        "n_routed_experts": model.num_experts // model.expert_share[1],
        "expert_share": list(model.expert_share),
        "num_experts_per_tok": model.experts_per_token,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 2.448, "bias_update_rate": 0.001,
    }


def _config_xing4(model):
    factor, original, fast, slow, mscale, mscale_all_dim = model.yarn
    low, high = model.res_clamp
    return dict(
        _config_kanana2(model),
        q_lora_rank=model.query_rank, rope_theta=model.rope_theta,
        rope_scaling={
            "type": "yarn", "factor": factor, "beta_fast": fast,
            "beta_slow": slow, "mscale": mscale,
            "mscale_all_dim": mscale_all_dim,
            "original_max_position_embeddings": original,
        },
        first_k_dense_replace=model.dense_layers,
        published_num_hidden_layers=model.published_layers,
        routed_scaling_factor=model.routed_scaling,
        hc_mult=model.streams, hc_sinkhorn_iters=model.sinkhorn_iters,
        hc_eps=model.hc_eps, mhc_h_res_clamp_min=low,
        mhc_h_res_clamp_max=high,
    )


def _config_trinity(model):
    held = model.held_experts()
    return {
        "hidden_size": model.d_model, "mup_enabled": True,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.kv_heads, "head_dim": model.head_dim,
        "sliding_window": model.sliding_window,
        "global_attn_every_n_layers": len(model.layer_period),
        "layer_types": list(model.layer_period) * 16,
        "num_hidden_layers": model.num_layers,
        "published_num_hidden_layers": model.published_layers,
        "num_dense_layers": model.dense_layers,
        "published_num_experts": model.num_experts,
        "num_experts": held[1] if held else model.num_experts,
        "expert_share": list(model.expert_share),
        "num_experts_per_tok": model.experts_per_token,
        "num_shared_experts": model.shared_experts,
        "score_func": "sigmoid", "route_norm": model.renormalise,
        "route_scale": model.routed_scaling, "n_group": 1, "topk_group": 1,
        "load_balance_coeff": model.bias_update_rate,
        "rms_norm_eps": model.rms_norm_eps, "rope_theta": model.rope_theta,
    }


def _config_nemotron3(model):
    heads, groups, query_heads, kv_heads = model.held_mixers()
    held = model.held_experts()
    return {
        "hybrid_override_pattern": model.pattern(),
        "num_hidden_layers": model.num_layers,
        "mamba_num_heads": heads, "mamba_head_dim": model.mamba_head_dim,
        "n_groups": groups, "ssm_state_size": model.state_size,
        "conv_kernel": model.conv_kernel, "use_conv_bias": True,
        "mamba_proj_bias": False, "mamba_hidden_act": "silu",
        "num_attention_heads": query_heads,
        "num_key_value_heads": kv_heads, "head_dim": model.head_dim,
        "attention_bias": False,
        "published_n_routed_experts": model.num_experts,
        "n_routed_experts": held[1] if held else model.num_experts,
        "expert_share": list(model.expert_share),
        "mixer_share": list(model.mixer_share),
        "num_experts_per_tok": model.experts_per_token,
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 5.0, "n_shared_experts": 1,
        "mlp_hidden_act": "relu2", "mlp_bias": False,
        "bias_update_rate": 0.001, "layer_norm_epsilon": 1e-5,
    }


def _config_granite4(model):
    return {
        "hidden_size": model.d_model,
        "layer_types": list(model.pattern()),
        "num_hidden_layers": model.num_layers,
        "mamba_n_heads": model.mamba_heads,
        "mamba_d_head": model.mamba_head_dim,
        "mamba_n_groups": model.mamba_groups,
        "mamba_d_state": model.state_size,
        "mamba_d_conv": model.conv_kernel, "mamba_expand": 2,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.kv_heads, "attention_bias": False,
        "position_embedding_type": "nope",
        "shared_intermediate_size": model.mlp_width, "hidden_act": "silu",
        "num_local_experts": 0, "normalization_function": "rmsnorm",
        "embedding_multiplier": model.input_scale,
        "attention_multiplier": model.attention_multiplier,
        "residual_multiplier": model.residual_multiplier,
        "logits_scaling": 1.0 / model.logits_scale,
        "rms_norm_eps": model.rms_norm_eps,
    }


def _config_ling3(model):
    held = model.held_experts()
    whole = model.num_layers == model.published_layers
    return {
        "num_hidden_layers": model.num_layers,
        # A cut: the last leading dense layer (published layer 1),
        # then whole periods from a period's first layer (6 on).
        "layers_run": list(range(model.num_layers)) if whole else [
            model.dense_layers - 1
        ] + [
            model.layer_group_size + i for i in range(model.num_layers - 1)
        ],
        "layer_group_size": model.layer_group_size,
        "first_k_dense_replace": model.dense_layers,
        "num_attention_heads": model.num_heads, "head_dim": model.head_dim,
        "short_conv_kernel_size": model.conv_kernel,
        "kda_safe_gate": model.safe_gate,
        "kda_lower_bound": model.gate_lower_bound,
        "num_kv_heads_for_linear_attn": 0, "linear_silu": True,
        "group_norm_size": 1,
        "kv_lora_rank": model.latent_rank, "q_lora_rank": None,
        "qk_nope_head_dim": model.nope_head_dim,
        "qk_rope_head_dim": model.rope_head_dim,
        "rotary_dim": model.rope_head_dim,
        "v_head_dim": model.value_head_dim, "rope_theta": model.rope_theta,
        "gated_attention_proj_granularity_type": "head_wise",
        "published_num_experts": model.num_experts,
        "num_experts": held[1] if held else model.num_experts,
        "expert_share": list(model.expert_share),
        "num_experts_per_tok": model.experts_per_token,
        "n_group": model.n_group, "topk_group": model.topk_group,
        "norm_topk_prob": True, "score_function": "sigmoid",
        "moe_router_enable_expert_bias": True,
        "routed_scaling_factor": model.routed_scaling,
        "bias_update_rate": model.bias_update_rate, "rms_norm_eps": 1e-6,
    }


def _config_qwen3next(model):
    held = model.held_experts()
    return {
        "num_hidden_layers": model.num_layers,
        "full_attention_interval": model.attention_interval,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.kv_heads, "head_dim": model.head_dim,
        "partial_rotary_factor": model.rotary_factor,
        "rope_theta": model.rope_theta,
        "linear_num_key_heads": model.delta_key_heads,
        "linear_num_value_heads": model.delta_value_heads,
        "linear_key_head_dim": model.delta_key_dim,
        "linear_value_head_dim": model.delta_value_dim,
        "linear_conv_kernel_dim": model.conv_kernel,
        "published_num_experts": model.num_experts,
        "num_experts": held[1] if held else model.num_experts,
        "expert_share": list(model.expert_share),
        "num_experts_per_tok": model.experts_per_token,
        "norm_topk_prob": True, "hidden_act": "silu",
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "rms_norm_eps": 1e-6, "router_aux_loss_coef": 0.001,
    }


def _config_lfm2(model):
    held = model.held_experts()
    kinds = model.layers()
    return {
        # The layers as the toy runs them: its cut IS its model.
        "layer_types": [kind for kind, _ in kinds],
        "num_dense_layers": sum(dense for _, dense in kinds),
        "layers_run": list(range(len(kinds))),
        "num_hidden_layers": model.num_layers,
        "conv_L_cache": model.conv_kernel, "conv_bias": model.conv_bias,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.kv_heads,
        "rope_theta": model.rope_theta, "norm_eps": model.norm_eps,
        "published_num_experts": model.num_experts,
        "num_experts": held[1] if held else model.num_experts,
        "expert_share": list(model.expert_share),
        "num_experts_per_tok": model.experts_per_token,
        "norm_topk_prob": model.renormalise,
        "use_expert_bias": model.use_expert_bias,
        "routed_scaling_factor": model.routed_scaling,
        "bias_update_rate": model.bias_update_rate,
    }


def _config_phi4flash(model):
    return {
        "hidden_size": model.d_model,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.num_key_value_heads,
        "intermediate_size": model.intermediate_size,
        "layer_norm_eps": model.layer_norm_eps,
        "sliding_window": model.sliding_window,
        "mlp_bias": model.mlp_bias,
        "published_num_hidden_layers": model.published_layers,
        "layers_run": list(model.published_indices()),
        "num_hidden_layers": model.num_layers,
        "d_state": model.d_state, "d_conv": model.d_conv,
        "expand": model.expand, "dt_rank": model.dt_rank,
    }


def _swiglu_shared(x, p):
    return (
        jax.nn.silu(x @ p["shared_gate"]["kernel"])
        * (x @ p["shared_up"]["kernel"])
    ) @ p["shared_down"]["kernel"]


def _relu2_shared(x, p):
    return jnp.square(jax.nn.relu(x @ p["shared_up"]["kernel"])) @ (
        p["shared_down"]["kernel"]
    )


def _token_gated_shared(x, p):
    return jax.nn.sigmoid(
        x @ p["shared_expert_gate"]["kernel"]
    ) * _swiglu_shared(x, p)


@dataclasses.dataclass(frozen=True)
class Experts:
    """A family's expert layer alone, at toy size (`expert_layer`):
    `fields` are `moe.DroplessMoE`'s, less `held`; `uncut` is laid over
    them for the layer that `shares` shares add up to
    (`test_families_shares.py`); `config(E, K, first, count)` is what the
    reference's `_experts` reads of the share that holds `count` of `E`
    experts from `first`; `shared(x, params)` the shared expert by
    hand, which every chip computes alike; `leaves` the layer's
    parameters by name; `tol` the family's rtol and atol."""

    fields: dict
    shares: int
    config: Callable
    leaves: tuple
    tol: float
    uncut: Any = dataclasses.field(default_factory=dict)
    shared: Optional[Callable] = None


_SWIGLU_EXPERTS = ("router", "w_down", "w_gate", "w_up")
_SWIGLU_SHARED = ("shared_down", "shared_gate", "shared_up")


def _experts_config(published, held, **rest):
    def config(E, K, first, count):
        return {
            published: E, held: count,
            "expert_share": [first // count, E // count],
            "num_experts_per_tok": K, "norm_topk_prob": True, **rest,
        }

    return config


@dataclasses.dataclass(frozen=True)
class Family:
    """One policy family at toy size: `net(num_actions=A, **small)` is
    the model every case of the family starts from (`small` is also
    what a case lays over the family's `PUBLISHED` table), `reference`
    the plain implementation it is held to, `perturb(model, params)`
    the values for what the family starts at zero or one."""

    net: type
    module: ModuleType  # holds `PUBLISHED`
    reference: ModuleType
    small: dict  # with num_layers and memory_len
    config: Callable  # model -> the reference's config, less the costs
    perturb: Optional[Callable] = None
    t: int = 6
    # The episode end inside warm_state's first unroll.
    warm_end: Any = (2, 1)
    # Of a family that can hold a share of each layer's experts.
    experts: Optional[Experts] = None


FAMILIES = {
    # 8 experts of 32, top 2, over a 4-slot cache (T=6 evicts).
    "olmoe": Family(
        OLMoENet, olmoe, olmoe_policy,
        dict(
            d_model=64, num_heads=4, num_layers=2, num_experts=8,
            experts_per_token=2, expert_width=32, memory_len=4,
        ),
        _config_olmoe,
    ),
    # 4 query heads on 2 key/value heads of 16, a window of 4 keys (3
    # slots), 8 experts of 24, top 2; the full layer's cache is 9 slots.
    "mellum2": Family(
        Mellum2Net, mellum2, mellum2_policy,
        dict(
            d_model=48, num_heads=4, kv_heads=2, head_dim=16,
            sliding_window=4, num_experts=8, experts_per_token=2,
            expert_width=24, num_layers=4, memory_len=9,
        ),
        _config_mellum2, _perturb_mellum2,
        # The shares: 64 experts, top 8, the published counts; held
        # (0, 16), (16, 16), (32, 16), (48, 16).
        experts=Experts(
            dict(d_ff=8, num_experts=8, top_k=2, renormalise=True),
            shares=4,
            config=_experts_config(
                "published_num_experts", "num_experts",
                load_balance_weight=0.001,
            ),
            leaves=_SWIGLU_EXPERTS, tol=1e-5,
            uncut=dict(num_experts=64, top_k=8),
        ),
    ),
    # 4 heads of 16, a SwiGLU of 96, 2 layers run 3 times over 5-slot
    # caches (T=6 evicts on the way).
    "ouro": Family(
        OuroNet, ouro, ouro_policy,
        dict(
            d_model=64, num_heads=4, head_dim=16, mlp_width=96,
            num_layers=2, passes=3, memory_len=5,
        ),
        _config_ouro, _perturb_ouro, warm_end=(4, 1),
    ),
    # 4 heads of 16 + 8 (values of 12) over a latent of 24, a dense
    # SwiGLU of 64, 16 routed experts of 20, top 3, two shared experts
    # (one SwiGLU of 40); the dense layer and two MoE layers, 9 slots.
    "kanana2": Family(
        Kanana2Net, kanana2, kanana2_policy,
        dict(
            d_model=48, num_heads=4, latent_rank=24, nope_head_dim=16,
            rope_head_dim=8, value_head_dim=12, mlp_width=64,
            num_experts=16, experts_per_token=3, expert_width=20,
            shared_experts=2, num_layers=3, memory_len=9,
        ),
        _config_kanana2, _perturb_kanana2,
        # The shares: 32 experts (the interpreted grouped kernels are
        # slow over 128), top 6, the same router and biases; held (0, 4),
        # (4, 4), ... (28, 4), the shared expert COUNTED ONCE.
        experts=Experts(
            dict(
                d_ff=8, num_experts=16, top_k=3, aux_loss_weight=0.0,
                renormalise=True, scoring="sigmoid", selection_bias=True,
                bias_update_rate=0.001, routed_scaling=2.448,
                shared_width=12,
            ),
            shares=8,
            config=_experts_config(
                "published_n_routed_experts", "n_routed_experts",
                scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
                topk_group=1, routed_scaling_factor=2.448,
            ),
            leaves=tuple(sorted(
                _SWIGLU_EXPERTS + _SWIGLU_SHARED
                + ("e_score_correction_bias",)
            )),
            tol=1e-5, uncut=dict(num_experts=32, top_k=6),
            shared=_swiglu_shared,
        ),
    ),
    # One attention layer of 4 query heads of 8 on 2 key/value heads,
    # one latent MoE layer (16 experts of 10 in a latent of 12, top 3, a
    # shared expert of 20), one Mamba-2 layer of 8 heads of 4 in 4
    # groups over a state of 6, scanned in chunks of 4 steps: the 11
    # steps of an unroll are two whole chunks and one padded.
    "nemotron3": Family(
        Nemotron3Net, nemotron3, nemotron3_policy,
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8, mamba_heads=8,
            mamba_head_dim=4, mamba_groups=4, state_size=6, chunk_size=4,
            num_experts=16, experts_per_token=3, expert_width=10,
            latent_width=12, shared_width=20, layer_period="*EM",
            layer_pattern="MEM*EMM", num_layers=3, memory_len=5,
        ),
        _config_nemotron3, _perturb_nemotron3, t=11,
        # The shares: eight of 16 experts (the interpreted grouped
        # kernels are slow over 512; two held under three a token, so a
        # share's experts see the window of the sorted rows that can be
        # theirs, models/moe.py), each LIFTED OUT OF THE LATENT by the
        # one `latent_up` every chip holds, the shared expert COUNTED
        # ONCE; `latent_down` is applied on every chip alike and is no
        # part of the sum.
        experts=Experts(
            dict(
                d_ff=8, num_experts=16, top_k=3, aux_loss_weight=0.0,
                renormalise=True, scoring="sigmoid", selection_bias=True,
                bias_update_rate=0.001, routed_scaling=5.0,
                shared_width=12, gated=False, activation="relu2",
                latent_width=10,
            ),
            shares=8,
            config=_experts_config(
                "published_n_routed_experts", "n_routed_experts",
                n_group=1, topk_group=1, routed_scaling_factor=5.0,
                n_shared_experts=1, mlp_hidden_act="relu2", mlp_bias=False,
            ),
            leaves=(
                "e_score_correction_bias", "latent_down", "latent_up",
                "router", "shared_down", "shared_up", "w_down", "w_up",
            ),
            tol=2e-5, shared=_relu2_shared,
        ),
    ),
    # One Gated DeltaNet layer of 4 value heads of 5 on 2 key heads of 6,
    # scanned in chunks of 4 steps (the 11 steps of an unroll are two
    # whole chunks and one padded), one gated attention layer of 4 query
    # heads of 16 on 2 key/value heads, RoPE on a head's first 4
    # columns; 16 experts of 10, top 3, a gated shared expert of 12.
    "qwen3next": Family(
        Qwen3NextNet, qwen3next, qwen3next_policy,
        dict(
            d_model=32, attention_interval=2, num_heads=4, kv_heads=2,
            head_dim=16, delta_key_heads=2, delta_value_heads=4,
            delta_key_dim=6, delta_value_dim=5, chunk_size=4,
            num_experts=16, experts_per_token=3, expert_width=10,
            shared_width=12, num_layers=2, memory_len=5,
        ),
        _config_qwen3next, _perturb_qwen3next, t=11,
        # The shares: four of 16 experts (four held, no fewer than the
        # three a token chooses: the cell's path), the shared expert
        # UNDER ITS TOKEN GATE, COUNTED ONCE.
        experts=Experts(
            dict(
                d_ff=8, num_experts=16, top_k=3, aux_loss_weight=0.001,
                renormalise=True, shared_width=12, shared_token_gate=True,
            ),
            shares=4,
            config=_experts_config(
                "published_num_experts", "num_experts",
                hidden_act="silu", router_aux_loss_coef=0.001,
            ),
            leaves=tuple(sorted(
                _SWIGLU_EXPERTS + _SWIGLU_SHARED + ("shared_expert_gate",)
            )),
            tol=2e-5, shared=_token_gated_shared,
        ),
    ),
    # The leading dense layer (a gated short convolution of 3 taps over a
    # SwiGLU of 48), then one period cut to `A c`: 4 query heads of 8 on
    # 2 key/value heads over a cache of 5 slots, a conv layer; 16
    # experts of 10, top 3, chosen under a bias.
    "lfm2": Family(
        Lfm2Net, lfm2, lfm2_policy,
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8, dense_width=48,
            expert_width=10, num_experts=16, experts_per_token=3,
            layer_period=("full_attention", "conv"), num_layers=3,
            memory_len=5,
        ),
        _config_lfm2, _perturb_lfm2,
        # The shares: 32 experts, top 4, the published counts; held (0, 8),
        # (8, 8), (16, 8), (24, 8): a quarter each, no shared expert.
        experts=Experts(
            dict(
                d_ff=8, num_experts=16, top_k=3, aux_loss_weight=0.0,
                renormalise=True, gate_sum_floor=1e-6, scoring="sigmoid",
                selection_bias=True, bias_update_rate=0.001,
            ),
            shares=4,
            config=_experts_config(
                "published_num_experts", "num_experts",
                use_expert_bias=True, routed_scaling_factor=1.0,
            ),
            leaves=tuple(sorted(
                _SWIGLU_EXPERTS + ("e_score_correction_bias",)
            )),
            tol=1e-5, uncut=dict(num_experts=32, top_k=4),
        ),
    ),
    # Published layers 14-19, one pair of each stage: 8 query heads of 4
    # on 4 key heads (4 query pairs, 2 key pairs, values of 8); a
    # sliding window of 4 keys (3 slots) and a full cache of 5; Mamba-1
    # states of [4, 64] (T=6: one chunk of the `lax.scan`).
    "phi4flash": Family(
        Phi4FlashNet, phi4flash, phi4flash_policy,
        dict(
            d_model=32, num_heads=8, num_key_value_heads=4,
            intermediate_size=48, sliding_window=4, d_state=4, dt_rank=2,
            num_layers=6, memory_len=5,
        ),
        _config_phi4flash, _perturb_phi4flash,
    ),
    # Kanana-2's toy with a query bottleneck of 20 and YaRN over 64
    # original positions (factor 4: of the rope part's four frequencies
    # one is kept, one blended, two divided), inside 4 streams mixed by 20
    # Sinkhorn steps; the dense layer and two MoE layers of a model of
    # 40 layers and two leading dense ones, 9 slots.
    "xing4": Family(
        Xing4Net, xing4, xing4_policy,
        dict(
            d_model=48, num_heads=4, latent_rank=24, query_rank=20,
            nope_head_dim=16, rope_head_dim=8, value_head_dim=12,
            mlp_width=64, num_experts=16, experts_per_token=3,
            expert_width=20, shared_experts=1,
            yarn=(4.0, 64, 4.0, 1.0, 1.0, 1.0), num_layers=3, memory_len=9,
        ),
        _config_xing4, _perturb_xing4,
        # The shares: all 64 experts, top 4, scaled by 2, one shared
        # expert COUNTED ONCE; held (0, 8), (8, 8), ... (56, 8).
        experts=Experts(
            dict(
                d_ff=8, num_experts=16, top_k=3, aux_loss_weight=0.0,
                renormalise=True, scoring="sigmoid", selection_bias=True,
                bias_update_rate=0.001, routed_scaling=2.0,
                shared_width=8,
            ),
            shares=8,
            config=_experts_config(
                "published_n_routed_experts", "n_routed_experts",
                scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
                topk_group=1, routed_scaling_factor=2.0,
            ),
            leaves=tuple(sorted(
                _SWIGLU_EXPERTS + _SWIGLU_SHARED
                + ("e_score_correction_bias",)
            )),
            tol=1e-5, uncut=dict(num_experts=64, top_k=4),
            shared=_swiglu_shared,
        ),
    ),
    # One leading dense layer (a SwiGLU of 64) and one period cut to
    # `s F`: 4 query heads of 16 on 2 key/value heads; two sliding layers
    # of 4 keys (3 slots: shorter than T=6 and than the full layer's 9
    # slots, so the window BITES), one full layer without positions; the
    # gate, four norms a layer; 16 experts of 20, top 3, and one shared
    # expert; the encoder's output times sqrt(48).
    "trinity": Family(
        TrinityNet, trinity, trinity_policy,
        dict(
            d_model=48, num_heads=4, kv_heads=2, head_dim=16,
            sliding_window=4, mlp_width=64, num_experts=16,
            experts_per_token=3, expert_width=20, input_scale=48 ** 0.5,
            layer_period=("sliding_attention", "full_attention"),
            dense_layers=1, num_layers=3, memory_len=9,
        ),
        _config_trinity, _perturb_trinity,
        # The shares: 64 of the 128 experts (the interpreted grouped
        # kernels are slow over 128), top 8, scaled by 2.826, one shared
        # expert COUNTED ONCE; held (0, 8), (8, 8), ... (56, 8): as many
        # held as a token chooses, the cell's side of `window_rungs`.
        experts=Experts(
            dict(
                d_ff=8, num_experts=16, top_k=3, aux_loss_weight=0.0,
                renormalise=True, scoring="sigmoid", selection_bias=True,
                bias_update_rate=0.001, routed_scaling=2.826,
                shared_width=8,
            ),
            shares=8,
            config=_experts_config(
                "published_num_experts", "num_experts",
                score_func="sigmoid", route_norm=True, route_scale=2.826,
                n_group=1, topk_group=1, num_shared_experts=1,
            ),
            leaves=tuple(sorted(
                _SWIGLU_EXPERTS + _SWIGLU_SHARED
                + ("e_score_correction_bias",)
            )),
            tol=1e-5, uncut=dict(num_experts=64, top_k=8),
            shared=_swiglu_shared,
        ),
    ),
    # Two Mamba-2 layers of 8 heads of 8 on ONE B/C group over a state
    # of 6, scanned in chunks of 4 steps (the 11 steps of an unroll are
    # two whole chunks and one padded), around one attention layer of 4
    # query heads of 8 on 2 key/value heads without positions, its
    # scores times 1/16 (not 8^-0.5); a SwiGLU of 48 after every mixer;
    # multipliers 3, 1/16, 0.3, 1/4.
    "granite4": Family(
        Granite4Net, granite4, granite4_policy,
        dict(
            d_model=32, num_heads=4, kv_heads=2, head_dim=8, mamba_heads=8,
            mamba_head_dim=8, state_size=6, chunk_size=4, mlp_width=48,
            input_scale=3.0, attention_multiplier=1 / 16,
            residual_multiplier=0.3, logits_scale=0.25,
            layer_period=("mamba", "attention", "mamba"),
            layer_types=("mamba", "attention", "mamba") * 2,
            num_layers=3, memory_len=5,
        ),
        _config_granite4, _perturb_granite4, t=11,
    ),
    # The leading dense layer (KDA over a SwiGLU of 48; ONE such layer
    # published, so that it is published layer 0, KDA under a period of
    # two) and one period cut to `K M`: KDA of 4 heads of 8 scanned in chunks of 4 steps
    # built from sub-blocks of 2 (the 11 steps of an unroll are two
    # whole chunks and one padded), the latent layer Kanana-2's toy with
    # a gate a head over a cache of 5 slots; 16 experts of 10 in 4
    # groups of 4, top 3 among the 2 best groups', a shared expert of 12.
    "ling3": Family(
        Ling3Net, ling3, ling3_policy,
        dict(
            d_model=32, layer_group_size=2, dense_layers=1, num_heads=4,
            head_dim=8, chunk_size=4, sub_chunk=2, latent_rank=12, nope_head_dim=8,
            rope_head_dim=4, value_head_dim=6, mlp_width=48,
            num_experts=16, experts_per_token=3, expert_width=10,
            shared_width=12, n_group=4, topk_group=2, num_layers=3,
            memory_len=5,
        ),
        _config_ling3, _perturb_ling3, t=11,
        # The shares: 64 experts in 8 groups of 8, top 8 among the 4
        # best groups', the published counts but for the experts (the
        # interpreted grouped kernels are slow over 512); held (0, 4),
        # (4, 4), ... (60, 4): a group is two shares, the shared expert
        # COUNTED ONCE.
        experts=Experts(
            dict(
                d_ff=8, num_experts=16, top_k=3, aux_loss_weight=0.0,
                renormalise=True, scoring="sigmoid", selection_bias=True,
                bias_update_rate=0.001, routed_scaling=2.5, n_group=4,
                topk_group=2, shared_width=12,
            ),
            shares=16,
            config=_experts_config(
                "published_num_experts", "num_experts",
                score_function="sigmoid",
                moe_router_enable_expert_bias=True,
                routed_scaling_factor=2.5, n_group=8, topk_group=4,
            ),
            leaves=tuple(sorted(
                _SWIGLU_EXPERTS + _SWIGLU_SHARED
                + ("e_score_correction_bias",)
            )),
            tol=1e-5,
            uncut=dict(num_experts=64, top_k=8, n_group=8, topk_group=4),
            shared=_swiglu_shared,
        ),
    ),
}


def family_of(model) -> Family:
    # By the class itself: `Xing4Net` is a `Kanana2Net`.
    return next(f for f in FAMILIES.values() if type(model) is f.net)


@functools.lru_cache(maxsize=None)
def _init(module, jit):
    return jax.jit(module.init) if jit else module.init


def init(module, rngs, *args, jit=True):
    """`module.init(rngs, *args)` of ANY flax module as one traced
    program, traced once a module and shape of `args` (a second call
    runs the compiled program and gives the same tree: read-only, as
    `build`'s is)."""
    return _init(module, jit)(rngs, *args)


def compile_requests(monkeypatch):
    """A list that grows by one with every program handed to XLA (what
    the persistent cache then answers is counted too): what the pins of
    the rule read (tests/test_family_scaffold.py, test_learner_setup.py)."""
    from jax._src import compiler

    seen, compile_or_get_cached = [], compiler.compile_or_get_cached

    def counted(*args, **kwargs):
        seen.append(1)
        return compile_or_get_cached(*args, **kwargs)

    monkeypatch.setattr(compiler, "compile_or_get_cached", counted)
    return seen


@functools.lru_cache(maxsize=None)
def _apply(module, jit, static):
    def run(variables, *args, rngs=None):
        return module.apply(variables, *args, rngs=rngs, **dict(static))

    return jax.jit(run) if jit else run


def apply(module, jit=True, **static):
    """Jitted `(variables, *args, rngs=None) -> module.apply(variables,
    *args, rngs=rngs, **static)` of ANY flax module, made once a module
    and `static` (hashable: `mutable=("losses",)`, a tuple) and traced
    once an input shape; `jit=False` the same callable eager, for the
    case that says why."""
    return _apply(module, jit, tuple(sorted(static.items())))


def init_params(model, batch, jit=True):
    """The family's parameters from the keys every case uses."""
    rows = batch["done"].shape[1]
    return init(
        model,
        {"params": jax.random.PRNGKey(0), "action": jax.random.PRNGKey(1)},
        batch, model.initial_state(rows), jit=jit,
    )


@functools.lru_cache(maxsize=None)
def _build(family, overrides):
    toy = FAMILIES[family]
    model = toy.net(num_actions=A, **dict(toy.small, **dict(overrides)))
    params = init_params(model, inputs(0, t=toy.t))
    if toy.perturb is not None:
        params = toy.perturb(model, params)
    return model, params


def build(family, **overrides):
    """(model, params) of the toy `family` with `overrides` laid over
    its `small` table: built once a process, the parameters read-only."""
    return _build(family, tuple(sorted(overrides.items())))


def expert_layer(family, held=None, tokens=40, seed=0, **overrides):
    """(layer, x, params): the family's expert layer alone
    (`Family.experts`) holding `held` (first, count) of its experts,
    `tokens` rows of 16 columns and the layer's first parameters."""
    spec = FAMILIES[family].experts
    layer = moe.DroplessMoE(**dict(spec.fields, held=held, **overrides))
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, 16))
    params = init(layer, jax.random.PRNGKey(seed + 1), x)
    assert tuple(sorted(params["params"])) == spec.leaves
    return layer, x, params


def forward(model, jit=True):
    """Jitted `(params, inputs, state) -> (outputs, new state)`, for a
    [T, B] batch and for a T=1 act step alike (a trace a shape).
    `jit=False`: the same callable eager, for the case that says why."""
    return apply(model, jit, sample_action=False)


@functools.lru_cache(maxsize=None)
def loss_and_grads(model, jit=True):
    """Jitted `(params, batch, state) -> (loss, stats, grads)` of
    `learner.compute_loss`, `PARAM_STEPS_KEY` still among the stats."""

    def run(params, batch, state):
        t, rows = batch["done"].shape
        hp = learner_lib.HParams(batch_size=rows, unroll_length=t - 1)
        (loss, stats), grads = jax.value_and_grad(
            lambda p: learner_lib.compute_loss(model, p, batch, state, hp),
            has_aux=True,
        )(params)
        return loss, stats, grads

    return jax.jit(run) if jit else run


@functools.lru_cache(maxsize=None)
def reference_bias_steps(model):
    """Jitted `(params, batch, state) -> the steps the reference's rule
    gives the selection biases`, a layer an item (kanana2, nemotron3)."""
    reference, config = family_of(model).reference, reference_config(model)
    return jax.jit(lambda params, batch, state: reference.bias_steps(
        params, batch, state, config
    ))


def reference_config(model):
    """What `perfbench/reference/<family>_policy.py` reads of `model`."""
    return dict(
        family_of(model).config(model), memory_len=model.memory_len,
        num_actions=A, **_LOSS_COSTS,
    )


@functools.lru_cache(maxsize=None)
def reference_forward(model):
    """Jitted `(params, batch, state) -> (logits, baseline, new state,
    the family's fourth)` of the plain reference, its config closed
    over."""
    reference, config = family_of(model).reference, reference_config(model)
    return jax.jit(lambda params, batch, state: reference.forward(
        params, batch, state, config
    ))


@functools.lru_cache(maxsize=None)
def reference_loss_and_grads(model):
    """Jitted `(params, batch, state) -> (loss, scale, grads)` of the
    plain reference (`loss` is `loss_and_scale(...)[0]` in every one)."""
    reference, config = family_of(model).reference, reference_config(model)

    def run(params, batch, state):
        (loss, scale), grads = jax.value_and_grad(
            lambda p: reference.loss_and_scale(p, batch, state, config),
            has_aux=True,
        )(params)
        return loss, scale, grads

    return jax.jit(run)


def warm_state(model, params, seed, unrolls=1, rows=B):
    """What an actor would hold `unrolls` unrolls in, an episode end in
    the first (`Family.warm_end`)."""
    toy = family_of(model)
    state = model.initial_state(rows)
    for i in range(unrolls):
        ends = [toy.warm_end] if i == 0 else ()
        _, state = forward(model)(
            params, inputs(seed + i, ends, t=toy.t, rows=rows), state
        )
    return state


def flat(tree):
    return jax.flatten_util.ravel_pytree(tree)[0]


def assert_agrees_with_the_reference(
    model, params, state, batch, rtol, atol
):
    """Outputs, new state, the loss (within `rtol` of its scale) and
    its gradients (within `rtol` of the largest) against the plain
    reference's; hands back what the family's own assertions read:
    (stats, grads, the reference's grads, the reference's fourth)."""
    out, new_state = forward(model)(params, batch, state)
    logits, baseline, ref_state, fourth = reference_forward(model)(
        params, batch, state
    )
    np.testing.assert_allclose(out.policy_logits, logits, rtol, atol)
    np.testing.assert_allclose(out.baseline, baseline, rtol, atol)
    leaves, ref_leaves = (
        jax.tree_util.tree_leaves(s) for s in (new_state, ref_state)
    )
    assert len(leaves) == len(ref_leaves)
    for got, want in zip(leaves, ref_leaves):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol, atol)

    loss, stats, grads = loss_and_grads(model)(params, batch, state)
    ref_loss, scale, ref_grads = reference_loss_and_grads(model)(
        params, batch, state
    )
    assert abs(float(loss) - float(ref_loss)) <= rtol * float(scale)
    np.testing.assert_allclose(
        flat(grads), flat(ref_grads), rtol=0,
        atol=rtol * float(jnp.max(jnp.abs(flat(ref_grads)))),
    )
    return stats, grads, ref_grads, fourth


def assert_stepwise_acting_equals_the_batch_forward(
    model, params, state, batch
):
    """The learner's [T, B] forward and the actor's T=1 forwards from
    `state` give the same logits and leave the same state; hands back
    that state. Tolerance: a softmax over another number of masked
    keys, f32."""
    full, full_state = forward(model)(params, batch, state)
    logits = []
    for t in range(batch["done"].shape[0]):
        step = {k: v[t : t + 1] for k, v in batch.items()}
        out, state = forward(model)(params, step, state)
        logits.append(out.policy_logits[0])
    np.testing.assert_allclose(
        np.stack(logits), full.policy_logits, rtol=2e-4, atol=2e-5
    )
    for got, want in zip(
        jax.tree_util.tree_leaves(state),
        jax.tree_util.tree_leaves(full_state),
    ):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    return full_state


class _SameTree:
    """A tree as a cache key: that object, not an equal one."""

    def __init__(self, tree):
        self.tree = tree

    def __hash__(self):
        return id(self.tree)

    def __eq__(self, other):
        return self.tree is other.tree


@functools.lru_cache(maxsize=None)
def _state_table(model, params, rows):
    """A `DeviceStateTable` of `rows` slots around `model` and the tree
    of `params`, made once: the table jits `act` itself, so a second
    table would be a second trace of the family's act step. `act` is
    eager for that reason."""

    def act(ctx, env_outputs, agent_state):
        out, new_state = model.apply(
            params.tree, env_outputs, agent_state, sample_action=False
        )
        return {"logits": out.policy_logits}, new_state

    return DeviceStateTable(
        model.initial_state(1), num_slots=rows, act_fn=act, batch_dim=1
    )


def state_table(model, params, rows):
    """The one `DeviceStateTable` of `rows` slots a model and tree of
    parameters (that tree: hand in the same object again). It keeps
    what its last user left: reset the slots a case reads."""
    return _state_table(model, _SameTree(params), rows)


def assert_state_table_acting_equals_the_batch_forward(
    model, params, batch, shapes=None
):
    """Three actors' slots in a `DeviceStateTable` whose rows hold the
    family's state (a pytree the table knows nothing of); the rows
    arrive in another order every step. Every step's logits equal the
    batch forward's from an empty state over the same inputs, and what
    the table holds at the end is what that forward leaves (every
    slot's items of `shapes`, where given). Hands back the table: one a
    model and tree of parameters, every slot reset before the first
    step, whatever an earlier case left in it."""
    rows = 3
    full, full_state = forward(model)(
        params, batch, model.initial_state(rows)
    )
    table = state_table(model, params, rows)
    table.reset(list(range(rows)))
    orders = [[0, 1, 2], [2, 0, 1], [1, 2, 0], [2, 1, 0], [0, 2, 1], [1, 0, 2]]
    for t, order in enumerate(orders):
        step = {
            k: np.asarray(v[t : t + 1])[:, order] for k, v in batch.items()
        }
        out = table.step(
            np.asarray(order, np.int32), np.ones(rows, bool), step
        )
        np.testing.assert_allclose(
            table.fetch(out, rows)["logits"][0],
            np.asarray(full.policy_logits)[t][order],
            rtol=2e-4, atol=2e-5,
        )
    for slot in range(rows):
        held = table.read_slot(slot)
        if shapes is not None:
            assert [
                [np.shape(leaf) for leaf in item] for item in held
            ] == shapes
        for item, want_item in zip(held, full_state):
            for got, want in zip(item, want_item):
                np.testing.assert_allclose(
                    got, np.asarray(want)[:, slot : slot + 1],
                    rtol=2e-4, atol=2e-5,
                )
    return table
