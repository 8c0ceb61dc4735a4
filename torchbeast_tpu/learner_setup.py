"""Flags -> HParams -> model and initial parameters, for every driver.

The one place that says which flags describe a learner
(`add_learner_arguments`), how they become `learner.HParams`
(`hparams_from_flags`) and how they become a model and its initial
parameters (`init_model_and_params`). monobeast, polybeast and anakin
call it and declare only what is their own; the benchmark's learner
driver and `chip_smoke.py` reach it through monobeast's names. It sits
below the drivers and imports none of them.

A policy family is added in `models/<family>.py` and one registry line
(`models/__init__.py`): `--model`'s choices are the registry's names, a
flag that sets a field of the family's module (`FAMILY_FIELD_FLAGS`) is
passed when the class declares the field and refused otherwise, and
"memory is a KV cache" is an attribute of the class.
"""

import glob
import logging
import os

import jax
import numpy as np

from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import models
from torchbeast_tpu import precision as precision_lib
from torchbeast_tpu.models import stats as model_stats
from torchbeast_tpu.telemetry import device_scopes

log = logging.getLogger(__name__)

# Flags that set a field of the family's module, each with what a family
# that does not take it is told. A family takes one when its class
# declares the field and does not refuse it (`models.takes_flag`), so
# `{families}` is read from the registry, not typed here.
_FAMILY_FIELD_REFUSALS = {
    "num_layers": (
        "--num_layers is a positive depth or window of --model {families}"
    ),
    "memory_len": (
        "--memory_len is a positive depth or window of --model {families}"
    ),
    "num_experts": (
        "--num_experts applies to --model {families} only (the conv/MLP "
        "families have no MoE formulation)"
    ),
    "trunk_channels": (
        "--trunk_channels applies to --model {families} only (the knob "
        "widens the ResNet conv trunk)"
    ),
    "expert_share": (
        "--expert_share i/n (share i of the n chips that divide each "
        "layer's experts) applies to --model {families} only"
    ),
    "mixer_share": (
        "--mixer_share i/n (share i of the n chips that divide each "
        "mixer's heads) applies to --model {families} only"
    ),
}
FAMILY_FIELD_FLAGS = tuple(_FAMILY_FIELD_REFUSALS)


class _Declarations:
    """`add_argument` over the flags one driver takes: every flag
    (`only=None`) or a subset, with that driver's own keyword arguments
    laid over the one declaration."""

    def __init__(self, parser, only, overrides):
        self._parser = parser
        self._only = only
        self._overrides = overrides or {}
        self.declared = set()

    def add_argument(self, flag, **kwargs):
        self.declared.add(flag)
        if self._only is None or flag in self._only:
            self._parser.add_argument(
                flag, **{**kwargs, **self._overrides.get(flag, {})}
            )


def add_learner_arguments(parser, *, model_default,
                          num_actors_default=None, only=None,
                          overrides=None):
    """Declare the flags that describe a learner, each once.

    monobeast and polybeast take all of them and differ in the two
    defaults that are arguments here; anakin takes the subset `only`
    names, with its own defaults in `overrides` ({flag: add_argument
    keyword arguments}). What a driver adds beside these is its own.
    """
    parser = _Declarations(parser, only, overrides)
    parser.add_argument("--env", type=str, default="PongNoFrameskip-v4",
                        help="Gym environment (or Mock / Counting).")
    parser.add_argument("--mode", default="train",
                        choices=["train", "test"])
    parser.add_argument("--xpid", default=None, help="Experiment id.")
    # Training settings.
    parser.add_argument("--savedir", default="~/logs/torchbeast_tpu",
                        help="Root dir for experiment data.")
    parser.add_argument("--num_actors", type=int,
                        default=num_actors_default,
                        help="Sync trainer: parallel environments (= the "
                             "acting batch). Async driver: actor loops "
                             "(default: one per server).")
    parser.add_argument("--total_steps", type=int, default=100000,
                        help="Total environment frames to train for.")
    parser.add_argument("--batch_size", type=int, default=8,
                        help="Learner batch size.")
    parser.add_argument("--unroll_length", type=int, default=80,
                        help="The unroll length (time dimension).")
    parser.add_argument("--model", default=model_default,
                        choices=list(models.MODEL_NAMES),
                        help="Model family (models/__init__.py; the "
                             "reference's Mono used shallow, its Poly "
                             "deep; mlp for tiny frames).")
    parser.add_argument("--use_lstm", action="store_true",
                        help="Use LSTM in the agent model.")
    parser.add_argument("--precision", default="f32",
                        choices=["f32", "bf16_compute", "bf16_train"],
                        help="Precision policy (torchbeast_tpu/"
                             "precision.py): f32 everywhere; "
                             "bf16_compute flips trunk compute to "
                             "bfloat16; bf16_train additionally makes "
                             "params/activations bf16-RESIDENT (f32 "
                             "master in the optimizer state, f32 "
                             "accumulate), stages the batch's float "
                             "leaves as bf16, and stores the RMSprop "
                             "second moment bf16 — the HBM-roofline "
                             "policy.")
    parser.add_argument("--model_dtype", default=None,
                        choices=["float32", "bfloat16"],
                        help="DEPRECATED alias: bfloat16 maps to "
                             "--precision bf16_compute (with a "
                             "warning); conflicts with an explicit "
                             "bf16_train.")
    parser.add_argument("--factored_opt_state", action="store_true",
                        help="Opt-in factored RMSprop second moment "
                             "(row/col EMAs for matrices, Adafactor-"
                             "style O(n+m) state; an approximation — "
                             "not torch-parity).")
    parser.add_argument("--trunk_channels", default="",
                        help="Opt-in deep-trunk widths as a comma list "
                             "(e.g. 32,64,64). Default: the reference's "
                             "16/32/32. A 16-channel conv fills 16 of an "
                             "MXU tile's 128 output lanes — wider trunks "
                             "buy capacity at far under proportional "
                             "step-time. Deep model only.")
    parser.add_argument("--sequence_parallel", type=int, default=0,
                        help="Shard the transformer's unroll (time) axis "
                             "over N devices: in-unroll attention runs as "
                             "ring attention over a `seq` mesh axis "
                             "(model=transformer only; pick unroll_length "
                             "so T+1 is divisible by N — short/acting "
                             "forwards fall back to dense with the same "
                             "params).")
    parser.add_argument("--pipeline_parallel", type=int, default=0,
                        help="Run the pipelined_mlp / "
                             "pipelined_transformer tower as a GPipe "
                             "pipeline over N devices (a `pipe` mesh "
                             "axis; stage params one-per-chip, "
                             "activations rotate via ppermute).")
    parser.add_argument("--pipeline_microbatches", type=int, default=0,
                        help="Microbatch count M for the GPipe schedule "
                             "(0, the default, means one per pipeline "
                             "device). Bubble "
                             "fraction is (P-1)/(M+P-1) per pass — raise "
                             "M to amortize it; the learner batch must "
                             "divide into M microbatches.")
    parser.add_argument("--num_layers", type=int, default=0,
                        help="Depth of --model transformer, olmoe, "
                             "mellum2, ouro, kanana2, nemotron3, "
                             "qwen3next, lfm2, phi4flash, xing4, "
                             "trinity, granite4 or ling3 (0: "
                             "the family's own, 2 and the published 16, "
                             "28, 48, 48, 88, 48, 24, 32, 40, 32, 40 and "
                             "42; "
                             "mellum2 in whole "
                             "periods of 4; ouro runs the layers it has "
                             "4 times a step; kanana2: its leading "
                             "dense layer and the MoE layers after it, "
                             "2 or more; nemotron3 in whole periods of "
                             "11, *EMEMEMEMEM; qwen3next in whole "
                             "periods of 4, three Gated DeltaNet layers "
                             "and one gated attention layer; lfm2 as 1 + "
                             "4k: its last leading dense layer, then "
                             "whole periods of one attention layer and "
                             "three gated short convolutions; phi4flash "
                             "as whole pairs around its stage boundary, "
                             "an even number from 6: 6 is published "
                             "layers 14-19; xing4: one leading dense "
                             "layer and the MoE layers after it, 2 or "
                             "more, or all 40 with both dense layers; "
                             "trinity as 1 + 4k: one leading dense "
                             "sliding layer, then whole periods of three "
                             "sliding layers and one full, or all 32 "
                             "with both dense layers; granite4 in whole "
                             "periods of 10, MMMMM*MMMM: nine Mamba-2 "
                             "layers and one attention layer; ling3 as "
                             "1 + 6k: one leading dense layer, then whole "
                             "periods of five Kimi Delta Attention "
                             "layers and one latent attention layer, or "
                             "all 42 with both dense layers).")
    parser.add_argument("--memory_len", type=int, default=0,
                        help="Steps of its own past a transformer, "
                             "olmoe, mellum2, ouro, kanana2, nemotron3, "
                             "qwen3next, lfm2, phi4flash, xing4, trinity, "
                             "granite4 or ling3 "
                             "policy attends over, carried as the "
                             "rolling KV cache (0: the family's own, 64, "
                             "128, 4095, 255, 4095, 4095, 4095, 4095, 4095, "
                             "4095, 4095, 4095 and 1023; "
                             "mellum2, trinity: "
                             "its full layers' cache, the sliding "
                             "layers carry min(memory_len, 1023; "
                             "trinity: 2047); ouro: "
                             "every one of its 4 x num_layers caches; "
                             "kanana2, xing4: a latent and a rope key a "
                             "slot; ling3: its latent layers' likewise, "
                             "the Kimi Delta Attention layers carry a "
                             "matrix state; "
                             "nemotron3, granite4: its attention layers', "
                             "the Mamba-2 layers carry a state instead; "
                             "qwen3next: its attention layers', the "
                             "DeltaNet layers carry a matrix state; "
                             "lfm2: its attention layers', the conv "
                             "layers carry two values; phi4flash: its "
                             "full layer's, which the cross layers read "
                             "too; a sliding layer carries min(memory_len, "
                             "511), a Mamba layer a state).")
    parser.add_argument("--expert_share", default="",
                        help="--model mellum2, kanana2, nemotron3, "
                             "qwen3next, lfm2, xing4, trinity or ling3: "
                             "'i/n' holds share i of the n chips that "
                             "divide each layer's 64 (128, 512, 512, 32, "
                             "64, 128, 512 routed) "
                             "experts (0/4: experts 0..15). The layer "
                             "routes over all of them and adds its own "
                             "experts' part of the sum (kanana2, "
                             "nemotron3, qwen3next, xing4, trinity, "
                             "ling3: and the shared "
                             "expert); "
                             "nothing stands in for the other chips. "
                             "Where the router chooses by groups (ling3: "
                             "8 groups of 64, a token's 8 among its 4 "
                             "best groups') it does so over all the "
                             "experts, and a share is whole groups or a "
                             "group is whole shares (0/64: eight of "
                             "group 0's). Empty: all.")
    parser.add_argument("--mixer_share", default="",
                        help="--model nemotron3: 'i/n' holds share i of "
                             "the n chips that divide each mixer's "
                             "heads (0/4: 32 of the 128 Mamba-2 heads "
                             "with 2 of their 8 B/C groups, 8 of the 32 "
                             "query heads with the key/value head they "
                             "read). The partial out_proj / o result "
                             "goes on; nothing stands in for the other "
                             "chips. Empty: all.")
    parser.add_argument("--num_experts", type=int, default=0,
                        help="Replace the transformer's FFN with a top-2 "
                             "mixture of N experts (model=transformer "
                             "only; adds a sown load-balance loss).")
    parser.add_argument("--expert_parallel", type=int, default=0,
                        help="Shard the MoE experts over N devices (an "
                             "`expert` mesh axis; dispatch/combine become "
                             "XLA all-to-alls). Needs --num_experts "
                             "divisible by N.")
    parser.add_argument("--sp_strategy", default="ring",
                        choices=["ring", "ulysses"],
                        help="Sequence-parallel strategy: ring rotates "
                             "K/V blocks via ppermute (best for huge T); "
                             "ulysses re-shards to full-sequence x "
                             "heads/N via two all-to-alls (needs "
                             "num_heads divisible by N).")
    parser.add_argument("--ring_schedule", default="contiguous",
                        choices=["contiguous", "zigzag"],
                        help="Ring attention block schedule: zigzag "
                             "balances causal work (~2x fewer busiest-"
                             "device FLOPs; needs T+1 divisible by 2N).")
    parser.add_argument("--num_learner_devices", type=int, default=1,
                        help="Width of the DATA-parallel axis over N "
                             "local chips: params replicated, each "
                             "learner batch sharded over a `data` mesh "
                             "axis with an ICI grad all-reduce "
                             "(batch_size divisible by N). Plain DP in "
                             "the sync trainer; the async driver "
                             "composes it with SP/EP/TP/PP on one mesh "
                             "(with --expert_parallel K the learner "
                             "consumes N x K chips).")
    parser.add_argument("--device_split", default="",
                        help="Sebulba device split (runtime/placement."
                             "py; README 'Device split'): 'auto' pins 1 "
                             "of every 4 devices to inference, "
                             "'inf=K,learn=rest' (or learn=M) pins "
                             "exactly; the update compiles over the "
                             "learner devices as a DP mesh (batch_size "
                             "divisible by their count). Async driver: "
                             "each inference device is a slice with its "
                             "own batcher and pinned DeviceStateTable, "
                             "actors hash statically to slices, slices "
                             "serve snapshots published device-to-"
                             "device (--replica_refresh_updates, "
                             "--max_policy_lag per slice), both "
                             "runtimes. Sync trainer: the acting forward "
                             "is pinned to the first inference device. "
                             "Empty = time-shared; a single-device "
                             "process degrades to it with a warning.")
    parser.add_argument("--fleet", default=None,
                        help="Multi-host Sebulba fleet membership "
                             "(fleet/topology.py; README 'Fleet'): "
                             "'host=<rank>/<n>,coord=<host:port>' names "
                             "this host's rank, the fleet size and the "
                             "coordination endpoint (jax.distributed "
                             "rendezvous; port+1 carries the control "
                             "plane: heartbeats, policy snapshots, "
                             "param sync). Composes with --device_split "
                             "per host; forced-CPU hosts compose by "
                             "synchronous parameter averaging. Unset = "
                             "single-host. Async driver only: the sync "
                             "trainer rejects it.")
    parser.add_argument("--min_live_hosts", type=int, default=1,
                        help="Fleet degradation floor (--fleet runs): "
                             "losing a host marks the fleet DEGRADED "
                             "while at least this many stay live; below "
                             "it the WHOLE fleet checkpoints and exits "
                             "instead of wedging the survivors. No "
                             "effect in the sync trainer.")
    parser.add_argument("--transformer_remat", action="store_true",
                        help="DEPRECATED spelling of --remat with the "
                             "transformer blocks stage at 'all' "
                             "(conflicts with an explicit --remat).")
    parser.add_argument("--remat", default=None,
                        help="Rematerialization plan over the model's "
                             "remat-able stages (runtime/remat_plan.py: "
                             "the ResNet trunk's per-stage none/front/"
                             "all, the block remat of the families "
                             "whose class has the `blocks` lever "
                             "(transformer, pipelined_transformer, "
                             "mellum2, ouro, kanana2, nemotron3, "
                             "qwen3next, lfm2, phi4flash, xing4, trinity, "
                             "granite4, ling3; not olmoe), the "
                             "LSTM scan): 'auto' picks the "
                             "minimum-recompute plan whose XLA-measured "
                             "peak fits --hbm_budget_gb; 'all'/'none' "
                             "force every stage; 'stage0=front,"
                             "stage1=all,core=none' pins per stage. "
                             "Default: the static pre-planner defaults "
                             "(trunk all-remat, transformer per "
                             "--transformer_remat, LSTM scan saved). "
                             "The chosen plan is logged and exported "
                             "as the learner.remat_plan telemetry "
                             "static.")
    parser.add_argument("--hbm_budget_gb", type=float, default=0.0,
                        help="HBM envelope for --remat auto, in GiB "
                             "covering one live update dispatch "
                             "(params + optimizer state + staged "
                             "[K, T+1, B] stack + XLA temps). 0 = the "
                             "device's reported limit, else the "
                             "15.75 GiB v5e default.")
    parser.add_argument("--superstep_k", type=int, default=1,
                        help="Learner superstep: fuse K SGD updates "
                             "into ONE lax.scan dispatch over a "
                             "[K, T+1, B, ...] batch stack (schedules "
                             "tick per-update inside the scan; stats "
                             "come back [K]-stacked, one host sync per "
                             "K updates). Bit-identical to K sequential "
                             "dispatches. Sync trainer: num_actors/"
                             "batch_size must divide by K. Async "
                             "driver: rollouts drain into a host arena "
                             "staged as one transfer, both runtimes. "
                             "1 = per-update dispatch.")
    parser.add_argument("--seed", type=int, default=1234)
    parser.add_argument("--env_seed", type=int, default=None,
                        help="Base seed for stochastic envs; env i draws "
                             "from env_seed+i, so actors stay decorrelated "
                             "but the run reproduces (with --serial_envs "
                             "and a fixed --seed, end-to-end). Multi-host "
                             "runs offset it per host so no two hosts "
                             "share a stream. Default: OS entropy per "
                             "env.")
    parser.add_argument("--checkpoint_interval_s", type=int, default=600,
                        help="Seconds between checkpoints (reference: 10min).")
    parser.add_argument("--learner_stall_timeout_s", type=float,
                        default=300.0,
                        help="Learner stall watchdog: no update "
                             "dispatch within this deadline transitions "
                             "health to DEGRADED and dumps thread-stack "
                             "diagnostics; dispatches resuming recovers "
                             "it. 0 disables the watchdog.")
    # Loss settings.
    parser.add_argument("--entropy_cost", type=float, default=0.0006)
    parser.add_argument("--entropy_cost_final", type=float, default=None,
                        help="Linearly anneal the entropy cost from "
                             "--entropy_cost to this value over "
                             "total_steps (default: constant). "
                             "High-early/low-late exploration escapes "
                             "compliance traps like the Memory probe's "
                             "(lstm_learning.md 4/4b).")
    parser.add_argument("--baseline_cost", type=float, default=0.5)
    parser.add_argument("--discounting", type=float, default=0.99)
    parser.add_argument("--reward_clipping", default="abs_one",
                        choices=["abs_one", "none"])
    parser.add_argument("--loss", default="vtrace",
                        choices=["vtrace", "impact"],
                        help="Objective family: IMPALA V-trace (the "
                             "default) or the IMPACT clipped "
                             "target-network surrogate (ops/impact.py) "
                             "— lag-tolerant, unlocks --replay_reuse. "
                             "Under impact the default "
                             "--replica_refresh_updates of the async "
                             "driver relaxes ~10x (the surrogate "
                             "absorbs the extra lag).")
    parser.add_argument("--impact_clip", type=float, default=0.2,
                        help="IMPACT surrogate clip epsilon "
                             "(--loss impact).")
    parser.add_argument("--replay_reuse", type=int, default=1,
                        help="Consume each collected batch K' times "
                             "(--loss impact; 1 = on-policy). The "
                             "schedule clock scales with it.")
    parser.add_argument("--target_refresh_updates", type=int, default=8,
                        help="Refresh the IMPACT target network every "
                             "N optimizer updates (--loss impact).")
    # Optimizer settings.
    parser.add_argument("--learning_rate", type=float, default=4.8e-4)
    parser.add_argument("--alpha", type=float, default=0.99,
                        help="RMSProp smoothing constant.")
    parser.add_argument("--momentum", type=float, default=0.0)
    parser.add_argument("--epsilon", type=float, default=0.01,
                        help="RMSProp epsilon.")
    parser.add_argument("--grad_norm_clipping", type=float, default=40.0)
    # Misc.
    parser.add_argument("--num_test_episodes", type=int, default=10)
    parser.add_argument("--profile_dir", default=None,
                        help="If set, capture a jax.profiler trace here.")
    unknown = (set(only or ()) | set(overrides or ())) - parser.declared
    if unknown:
        raise ValueError(f"not learner flags: {sorted(unknown)}")


def device_time_account(profile_dir, texts=(), stats=None, strict=True):
    """The by-scope account (telemetry/device_scopes.py) of the newest
    trace `jax.profiler` left under `profile_dir`: one account a
    program in it, `texts` the compiled programs' whose ops it joins
    on, and the sown counters among an update's `stats`."""
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    profile = jax.profiler.ProfileData.from_file(found[-1])
    counters = {
        name: float(value) for name, value in (stats or {}).items()
        if model_stats.gauge_name(name) and np.ndim(value) == 0
    }
    return device_scopes.account(
        device_scopes.plain_trace(profile),
        [device_scopes.read_program_text(text) for text in texts if text],
        counters=counters, strict=strict,
    )


def stop_profile(flags, tele, update_step, stats):
    """What `--profile_dir` ends a run with, for both drivers: the
    trace stopped, its account logged as a table, a program a table,
    and on the run's last telemetry line (`device_scopes`). A
    profiling run is a short run: the flag traces all of it. Never
    raises: a run's shutdown goes on without its account."""
    try:
        jax.profiler.stop_trace()
    except RuntimeError:
        return  # start_trace itself failed; don't mask the cause
    try:
        text = getattr(update_step, "compiled_text", lambda: None)()
        report = device_time_account(
            flags.profile_dir, [text], stats, strict=False
        )
    except Exception:  # noqa: BLE001
        log.exception("No device time account of %s", flags.profile_dir)
        return
    log.info("Device time by scope:\n%s", device_scopes.render(report))
    tele.set_static("device_scopes", report)


def hparams_from_flags(flags) -> learner_lib.HParams:
    policy = precision_lib.resolve_flags(flags)
    return learner_lib.HParams(
        discounting=flags.discounting,
        baseline_cost=flags.baseline_cost,
        entropy_cost=flags.entropy_cost,
        entropy_cost_final=getattr(flags, "entropy_cost_final", None),
        reward_clipping=flags.reward_clipping,
        learning_rate=flags.learning_rate,
        rmsprop_alpha=flags.alpha,
        rmsprop_eps=flags.epsilon,
        rmsprop_momentum=flags.momentum,
        grad_norm_clipping=flags.grad_norm_clipping,
        total_steps=flags.total_steps,
        unroll_length=flags.unroll_length,
        batch_size=flags.batch_size,
        opt_state_dtype=policy.opt_state_dtype,
        param_dtype=policy.param_dtype,
        opt_factored=getattr(flags, "factored_opt_state", False),
        loss=getattr(flags, "loss", "vtrace"),
        impact_clip=getattr(flags, "impact_clip", 0.2),
        replay_reuse=max(1, getattr(flags, "replay_reuse", 1) or 1),
    )


def dummy_env_outputs(t, batch_size, frame_shape, frame_dtype):
    """The env-output schema every acting/learning path consumes —
    ONE definition (model init dummies and polybeast's inference
    prewarm both build from it, so schema drift breaks both loudly
    instead of silently desynchronizing a compiled signature)."""
    return {
        "frame": np.zeros(
            (t, batch_size) + tuple(frame_shape), frame_dtype
        ),
        "reward": np.zeros((t, batch_size), np.float32),
        "done": np.ones((t, batch_size), bool),
        "last_action": np.zeros((t, batch_size), np.int32),
    }


def initial_params(model, rngs, env_outputs, agent_state):
    """`model.init` as ONE traced program: run op by op, a flax module
    is an XLA compile an op (some hundreds for a transformer family).
    Every driver's first parameters come from here."""
    init = jax.jit(model.init)
    return init(rngs, env_outputs, agent_state)


def _make_1d_mesh(n: int, axis: str, flag_name: str):
    """A 1-D device mesh over the first n devices, with the consistent
    too-few-devices error every parallelism flag shares."""
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"--{flag_name} {n} but only {len(devices)} devices are "
            "visible"
        )
    return Mesh(np.asarray(devices[:n]), (axis,))


def _check_family_takes(model_name, field, valid=True):
    """Refuse `--<field>` for a family whose class does not take it (or
    a value the field cannot hold), naming the families that do."""
    if valid and models.takes_flag(model_name, field):
        return
    raise ValueError(_FAMILY_FIELD_REFUSALS[field].format(
        families=" or ".join(models.families_taking(field))
    ))


def init_model_and_params(flags, num_actions, batch_size, frame_shape,
                          frame_dtype=np.uint8, moe_mesh=None,
                          seq_mesh=None, pipe_mesh=None, unmeshed=False,
                          init_params=True):
    """Build the model + initial params from flags.

    `unmeshed=True` strips every mesh binding from the constructed model
    (same flags, same param tree — meshes only select compute paths /
    add sharding constraints, never parameters). The async driver uses
    this for its ACTING model on multi-host runs, where the learner
    model's constraints reference global-mesh devices a host-local
    inference jit cannot touch.

    moe_mesh / seq_mesh: optional externally-built meshes with an
    `expert` / `seq` axis — the async driver passes its composite
    (data x expert|seq) learner mesh here so the model's sharding
    constraints/shard_maps reference the SAME mesh the update step is
    jitted over (two different meshes in one program is an XLA error).
    A composite seq_mesh also sets the model's batch_axis to "data".
    When None, the flags build 1-D meshes.
    """
    import jax.numpy as jnp

    policy = precision_lib.resolve_flags(flags)
    dtype = policy.compute_dtype
    extra = {}
    # EVERY family threads head_dtype now (ISSUE 13 closed the
    # transformer gap: models/transformer.py, transformer_pp.py, and
    # pipelined.py grew the kwarg) — bf16_train no longer silently
    # falls back to bf16-trunk-only anywhere.
    if policy.head_dtype != jnp.float32:
        extra["head_dtype"] = policy.head_dtype
    seq_par = getattr(flags, "sequence_parallel", 0)
    if (
        getattr(flags, "ring_schedule", "contiguous") != "contiguous"
        and not (seq_par and seq_par > 1)
    ):
        raise ValueError(
            "--ring_schedule only takes effect with --sequence_parallel "
            "> 1 (no ring attention runs without a seq mesh)"
        )
    if (
        getattr(flags, "sp_strategy", "ring") != "ring"
        and not (seq_par and seq_par > 1)
    ):
        raise ValueError(
            "--sp_strategy only takes effect with --sequence_parallel "
            "> 1 (no sequence-parallel attention runs without a seq mesh)"
        )
    if seq_par and seq_par > 1:
        if flags.model != "transformer":
            raise ValueError(
                "--sequence_parallel needs --model transformer (the "
                "conv+LSTM families have no sequence-sharded formulation)"
            )
        ring_schedule = getattr(flags, "ring_schedule", "contiguous")
        sp_strategy = getattr(flags, "sp_strategy", "ring")
        if sp_strategy == "ulysses":
            if ring_schedule != "contiguous":
                raise ValueError(
                    "--ring_schedule applies to --sp_strategy ring only"
                )
            # num_heads divisibility is validated AFTER create_model below,
            # against the heads the model is actually constructed with.
            divisor = seq_par
        else:
            divisor = 2 * seq_par if ring_schedule == "zigzag" else seq_par
        if (flags.unroll_length + 1) % divisor != 0:
            # The learner forward sees T = unroll_length + 1 steps; if the
            # mesh doesn't divide it, the model would silently fall back
            # to dense attention — the opposite of what the flag asks for.
            raise ValueError(
                f"--sequence_parallel {seq_par} "
                f"({ring_schedule}) requires unroll_length+1 divisible "
                f"by {divisor} (got {flags.unroll_length + 1})"
            )
        if seq_mesh is not None:
            extra["mesh"] = seq_mesh
            extra["batch_axis"] = "data"
        elif getattr(flags, "expert_parallel", 0) > 1:
            # SP x EP on one (data=1, model=1, seq, expert) mesh: the
            # attention shard_maps use `seq`, the MoE constraints use
            # `expert` (parallel/mesh.py; parity pinned by
            # tests/test_composite_mesh.py).
            from torchbeast_tpu.parallel import create_mesh

            ep = flags.expert_parallel
            extra["mesh"] = create_mesh(
                seq_par * ep,
                expert_parallelism=ep,
                seq_parallelism=seq_par,
            )
            extra["batch_axis"] = "data"
        else:
            extra["mesh"] = _make_1d_mesh(
                seq_par, "seq", "sequence_parallel"
            )
        extra["ring_schedule"] = ring_schedule
        extra["sp_strategy"] = sp_strategy
    num_experts = getattr(flags, "num_experts", 0)
    expert_par = getattr(flags, "expert_parallel", 0)
    pipe_par = getattr(flags, "pipeline_parallel", 0)
    if expert_par and not num_experts:
        raise ValueError("--expert_parallel needs --num_experts")
    if (pipe_par or 0) > 1 and (
        (seq_par or 0) > 1 or (expert_par or 0) > 1
    ):
        # SP and EP compose on one multi-axis mesh (above); the GPipe
        # shard_map's own ring schedule does not — its stage rotation
        # would need interleaving with the attention/MoE collectives.
        raise ValueError(
            "--pipeline_parallel cannot combine with "
            "--sequence_parallel or --expert_parallel (the pipeline "
            "schedule owns its mesh; SP x EP do compose with each other "
            "and with data parallelism)"
        )
    pipelined_models = ("pipelined_mlp", "pipelined_transformer")
    # The stage-count kwarg differs by family: the MLP's tower depth is
    # num_stages, the transformer's is its layer count.
    stage_kwarg = (
        "num_layers" if flags.model == "pipelined_transformer"
        else "num_stages"
    )
    if pipe_par and pipe_par > 1:
        if flags.model not in pipelined_models:
            raise ValueError(
                "--pipeline_parallel needs --model pipelined_mlp or "
                "pipelined_transformer (the other families have no "
                "stage-uniform tower to pipeline)"
            )
        if pipe_mesh is not None:
            # Composite (data x pipe) mesh from the async driver: each
            # data group runs its own GPipe; microbatch rows shard over
            # `data` (parallel/pp.py batch_axis).
            extra["mesh"] = pipe_mesh
            extra["batch_axis"] = "data"
        else:
            extra["mesh"] = _make_1d_mesh(
                pipe_par, "pipe", "pipeline_parallel"
            )
        # Stage-count default differs by family: the MLP tower's depth is
        # a pipeline artifact (one stage per device, as documented); the
        # transformer's depth is an ARCHITECTURE choice, so it defaults
        # to the model's own num_layers — deriving it from the device
        # count would silently change the net (and break checkpoint
        # compatibility with non-pipelined runs).
        if flags.model == "pipelined_transformer":
            default_stages = models.PipelinedTransformerNet.num_layers
        else:
            default_stages = pipe_par
        n_stages = getattr(flags, "pipeline_stages", 0) or default_stages
        if n_stages % pipe_par != 0:
            raise ValueError(
                f"--pipeline_stages {n_stages} must be a multiple of "
                f"--pipeline_parallel {pipe_par}"
            )
        extra[stage_kwarg] = n_stages
        n_mb = getattr(flags, "pipeline_microbatches", 0)
        if n_mb < 0:
            raise ValueError(
                f"--pipeline_microbatches {n_mb} must be >= 0 "
                "(0 means the default: one microbatch per pipeline "
                "device)"
            )
        if n_mb:
            extra["n_microbatches"] = n_mb
        # The learner batch must divide into microbatches (default: one
        # per pipe device) or every training forward would silently take
        # the models' sequential fallback — the opposite of what the
        # flag asks for. (Acting/eval batches fall back by design.)
        from torchbeast_tpu.parallel.pp import can_pipeline

        if flags.model == "pipelined_transformer":
            pipelined_quantity, what = flags.batch_size, "batch_size"
        else:  # pipelined_mlp microbatches over flattened T*B tokens
            pipelined_quantity = (flags.unroll_length + 1) * flags.batch_size
            what = "(unroll_length+1)*batch_size"
        if not can_pipeline(
            extra["mesh"], pipelined_quantity,
            n_microbatches=extra.get("n_microbatches"),
            batch_axis=extra.get("batch_axis"),
        ):
            from torchbeast_tpu.parallel.pp import (
                default_n_microbatches,
            )

            m_eff = default_n_microbatches(
                extra["mesh"], "pipe", extra.get("n_microbatches")
            )
            raise ValueError(
                f"--pipeline_parallel {pipe_par} requires {what} "
                f"(= {pipelined_quantity}) divisible by the microbatch "
                f"count ({m_eff}; --pipeline_microbatches overrides the "
                "one-per-device default), and each microbatch's rows by "
                "the data axis when composing with DP — otherwise the "
                "learner step would silently run the sequential fallback"
            )
    elif flags.model in pipelined_models:
        # No mesh, but the requested tower depth still applies — a
        # silently different stage count would make checkpoints
        # shape-incompatible with a later pipelined run.
        n_stages = getattr(flags, "pipeline_stages", 0)
        if n_stages:
            extra[stage_kwarg] = n_stages
        log.info(
            "--model %s without --pipeline_parallel: the stage tower "
            "runs sequentially on one device", flags.model,
        )
    if num_experts:
        _check_family_takes(flags.model, "num_experts")
        extra["num_experts"] = num_experts
        if expert_par and expert_par > 1:
            if num_experts % expert_par != 0:
                raise ValueError(
                    f"--num_experts {num_experts} not divisible by "
                    f"--expert_parallel {expert_par}"
                )
            if moe_mesh is not None:
                extra["moe_mesh"] = moe_mesh
            elif "expert" in getattr(
                extra.get("mesh"), "shape", {}
            ):
                # The SP x EP composite mesh built above carries the
                # `expert` axis — MoE constraints use the same mesh.
                extra["moe_mesh"] = extra["mesh"]
            else:
                extra["moe_mesh"] = _make_1d_mesh(
                    expert_par, "expert", "expert_parallel"
                )
    if getattr(flags, "transformer_remat", False):
        if flags.model not in ("transformer", "pipelined_transformer"):
            raise ValueError(
                "--transformer_remat applies to the transformer families "
                "only (the conv trunk already remats by default, "
                "models/resnet.py `remat`)"
            )
        # The actual remat kwarg comes from the plan below (the flag is
        # the deprecated spelling of `--remat` blocks=all).
    for flag in ("num_layers", "memory_len"):
        value = getattr(flags, flag, 0)
        if value:
            _check_family_takes(flags.model, flag, valid=value > 0)
            extra[flag] = value
    for flag in ("expert_share", "mixer_share"):
        value = getattr(flags, flag, "")
        if value:
            _check_family_takes(flags.model, flag)
            try:
                share, of = (int(part) for part in value.split("/"))
            except ValueError:
                raise ValueError(
                    f"--{flag} {value!r} must be 'i/n', two ints"
                ) from None
            extra[flag] = (share, of)
    trunk_channels = getattr(flags, "trunk_channels", "")
    if trunk_channels:
        _check_family_takes(flags.model, "trunk_channels")
        try:
            widths = tuple(int(c) for c in trunk_channels.split(","))
        except ValueError:
            widths = ()
        if len(widths) != 3 or any(w < 1 for w in widths):
            raise ValueError(
                f"--trunk_channels {trunk_channels!r} must be three "
                "positive comma-separated ints (e.g. 32,64,64)"
            )
        extra["trunk_channels"] = widths
    if unmeshed:
        for key in ("mesh", "moe_mesh", "batch_axis"):
            extra.pop(key, None)
    # Rematerialization plan (--remat, runtime/remat_plan.py): resolves
    # the per-stage remat kwargs — the static pre-planner defaults when
    # the flag is unset, or the cost-model auto-tuner against
    # --hbm_budget_gb. Candidate models for `auto` build UNMESHED (the
    # mesh only adds sharding constraints; the per-chip envelope is the
    # conservative planning target) with the same family kwargs.
    from torchbeast_tpu.runtime import remat_plan as remat_plan_lib

    plan_extra = {
        k: v for k, v in extra.items()
        if k not in ("mesh", "moe_mesh", "batch_axis")
    }
    plan = remat_plan_lib.resolve_from_flags(
        flags, hparams_from_flags(flags), num_actions, frame_shape,
        frame_dtype, policy,
        build_model=lambda kw: models.create_model(
            flags.model, num_actions=num_actions,
            use_lstm=flags.use_lstm, dtype=dtype,
            **{**plan_extra, **kw},
        ),
    )
    extra.update(
        remat_plan_lib.model_kwargs(flags.model, plan.assignment)
    )
    model = models.create_model(
        flags.model, num_actions=num_actions, use_lstm=flags.use_lstm,
        dtype=dtype, **extra,
    )
    if not init_params:
        # Caller only wants the model object (e.g. polybeast's unmeshed
        # acting twin — its param tree is identical to the meshed
        # model's, so re-initializing would be pure waste).
        return model, None
    if (
        seq_par
        and seq_par > 1
        and extra.get("sp_strategy") == "ulysses"
        and model.num_heads % seq_par != 0
    ):
        # Validated against the CONSTRUCTED model (not the class default,
        # which would silently diverge if a num_heads flag/kwarg is ever
        # added): an indivisible head count makes the model fall back to
        # dense attention — the opposite of what the flag asks for.
        raise ValueError(
            f"--sp_strategy ulysses requires num_heads "
            f"({model.num_heads}) divisible by --sequence_parallel "
            f"{seq_par} (heads are the sharded resource)"
        )
    params = initial_params(
        model,
        {
            "params": jax.random.PRNGKey(flags.seed),
            "action": jax.random.PRNGKey(flags.seed + 1),
        },
        dummy_env_outputs(1, batch_size, frame_shape, frame_dtype),
        model.initial_state(batch_size),
    )
    # bf16_train: params are bf16-RESIDENT from here on — every
    # consumer (acting, learner, checkpoint templates) sees bf16; the
    # f32 master materializes inside optimizer.init (learner.
    # _bf16_resident_params). Cross-precision checkpoint resume fails
    # loudly at the template match, by design.
    params = precision_lib.cast_params(params, policy)
    return model, params
