"""Single-host IMPALA trainer (the reference MonoBeast's role,
/root/reference/torchbeast/monobeast.py), re-designed TPU-first.

Architecture difference, deliberate: the reference forks actor processes
that each run the policy on CPU against a shared-memory model the learner
overwrites in place (monobeast.py:128-191, 295). On TPU, per-actor host
inference would starve the chip, so acting is *centrally batched*: env
processes only step environments; every env step is one jitted `[1, B]`
policy call on the TPU, and every unroll ends in one jitted update step. No
weight copies at all — actor and learner share the same on-device params
pytree. Policy lag is exactly zero by default (strictly stronger than the
reference's queue-backpressure guarantee); `--overlap_collect` trades it
for lag exactly 1 so the update chain hides behind env stepping.

Run:  python -m torchbeast_tpu.monobeast --env Mock --total_steps 20000
"""

import argparse
import functools
import logging
import os
import time

import jax
import numpy as np

from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import precision as precision_lib
from torchbeast_tpu import telemetry
from torchbeast_tpu.envs import create_env
from torchbeast_tpu.envs.vec import ProcessEnvPool, SerialEnvPool
from torchbeast_tpu.models import create_model
from torchbeast_tpu.rollout import (
    PipelinedRolloutCollector,
    RolloutCollector,
)
from torchbeast_tpu.utils import (
    FileWriter,
    Timings,
    load_checkpoint,
    save_checkpoint,
)
from torchbeast_tpu.utils.backend import log_backend

log = logging.getLogger("torchbeast_tpu.monobeast")


def _configure_logging():
    """Called from main(), NOT at import: importing this module (as
    every test does, and as polybeast does for its shared helpers) must
    not mutate global logging state."""
    logging.basicConfig(
        format=(
            "[%(levelname)s:%(process)d %(module)s:%(lineno)d "
            "%(asctime)s] %(message)s"
        ),
        level=logging.INFO,
    )


def make_parser():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--env", type=str, default="PongNoFrameskip-v4",
                        help="Gym environment (or Mock / Counting).")
    parser.add_argument("--mode", default="train",
                        choices=["train", "test"])
    parser.add_argument("--xpid", default=None, help="Experiment id.")
    # Training settings.
    parser.add_argument("--savedir", default="~/logs/torchbeast_tpu",
                        help="Root dir for experiment data.")
    parser.add_argument("--num_actors", type=int, default=8,
                        help="Parallel environments (= acting batch).")
    parser.add_argument("--total_steps", type=int, default=100000,
                        help="Total environment frames to train for.")
    parser.add_argument("--batch_size", type=int, default=8,
                        help="Learner batch size.")
    parser.add_argument("--vtrace_impl", default="associative",
                        choices=["sequential", "associative", "pallas"],
                        help="V-trace backward recursion: "
                             "lax.associative_scan (O(log T) depth, the "
                             "default), lax.scan (the reference's "
                             "T-dependent-steps formulation), or the "
                             "fused Pallas kernel (vs + advantages in "
                             "one VMEM pass; TPU-compiled, interpreted "
                             "elsewhere).")
    parser.add_argument("--unroll_length", type=int, default=80,
                        help="The unroll length (time dimension).")
    parser.add_argument("--model", default="shallow",
                        choices=["shallow", "deep", "mlp", "pipelined_mlp", "transformer", "pipelined_transformer", "olmoe"],
                        help="Model family (Mono used shallow; Poly deep; "
                             "mlp for tiny frames).")
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
                             "step-time (benchmarks/mfu_ablation.py "
                             "measures the scaling). Deep model only.")
    parser.add_argument("--serial_envs", action="store_true",
                        help="Step envs in-process (tests/cheap envs).")
    parser.add_argument("--attention_impl", default="dense",
                        choices=["dense", "pallas"],
                        help="Transformer attention implementation: XLA "
                             "dense ops, or the fused Pallas kernel "
                             "(single-chip; compiled on TPU, interpreted "
                             "elsewhere).")
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
    parser.add_argument("--pipeline_stages", type=int, default=0,
                        help="Total tower depth (pipelined_mlp stages / "
                             "pipelined_transformer layers). Default: "
                             "one stage per pipeline device for the MLP; "
                             "the model's own num_layers for the "
                             "transformer. A multiple k*N runs k looped "
                             "passes.")
    parser.add_argument("--num_layers", type=int, default=0,
                        help="Depth of --model transformer or olmoe "
                             "(0: the family's own, 2 and the published "
                             "16).")
    parser.add_argument("--memory_len", type=int, default=0,
                        help="Steps of its own past a transformer or "
                             "olmoe policy attends over, carried as the "
                             "rolling KV cache (0: the family's own, 64 "
                             "and 128).")
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
                        help="Data-parallel learner over N local chips: "
                             "params replicated, each learner batch "
                             "sharded over a `data` mesh axis with an "
                             "ICI grad all-reduce (batch_size divisible "
                             "by N). Composing DP with SP/EP/TP/PP "
                             "lives in the async driver (polybeast).")
    parser.add_argument("--device_split", default="",
                        help="Sebulba device split (runtime/placement."
                             "py): 'auto' or 'inf=K,learn=rest|M'. In "
                             "the sync trainer the split pins the "
                             "acting forward to the first inference "
                             "device (policy params re-placed there "
                             "device-to-device at each rebind) and "
                             "compiles the learner update over a DP "
                             "mesh of the learner devices — collect "
                             "and learn stop contending for one chip's "
                             "compute. Empty = time-shared; a single-"
                             "device process degrades to it with a "
                             "warning. The full per-slice serving "
                             "split (pinned slot tables, snapshot "
                             "publication) lives in the async driver.")
    parser.add_argument("--fleet", default=None,
                        help="Multi-host Sebulba fleet membership "
                             "(fleet/topology.py): 'host=<rank>/<n>,"
                             "coord=<host:port>'. The sync trainer is "
                             "single-host by design — the flag is "
                             "declared for driver parity and rejected "
                             "when set; fleet runs live in the async "
                             "driver (polybeast --fleet).")
    parser.add_argument("--min_live_hosts", type=int, default=1,
                        help="Fleet degradation floor (--fleet runs; "
                             "async driver). Declared for driver "
                             "parity; no effect in the sync trainer.")
    parser.add_argument("--transformer_remat", action="store_true",
                        help="DEPRECATED spelling of --remat with the "
                             "transformer blocks stage at 'all' "
                             "(conflicts with an explicit --remat).")
    parser.add_argument("--remat", default=None,
                        help="Rematerialization plan over the model's "
                             "remat-able stages (runtime/remat_plan.py: "
                             "the ResNet trunk's per-stage none/front/"
                             "all, the transformer families' block "
                             "remat, the LSTM scan): 'auto' picks the "
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
    parser.add_argument("--opt_impl", default="xla",
                        choices=["xla", "pallas"],
                        help="Optimizer-tail implementation: 'xla' "
                             "composes the optax chain; 'pallas' runs "
                             "grad-clip finalize -> torch-RMSprop/"
                             "momentum -> f32 master write -> bf16 "
                             "narrowing cast as ONE VMEM-resident "
                             "kernel per leaf (ops/pallas_opt.py; "
                             "TPU-compiled, interpreted elsewhere; "
                             "identical numerics, pinned by test).")
    parser.add_argument("--overlap_collect", action="store_true",
                        help="Act on params that are one dispatched "
                             "unroll-batch behind the learner head, so "
                             "the update chain always hides behind env "
                             "stepping and no act blocks on it. Default "
                             "off = zero policy lag: the first act of "
                             "each unroll waits for the update chain "
                             "(the reference's actors lag by queue "
                             "depth, so either mode is stricter than "
                             "the reference).")
    parser.add_argument("--pipelined_collect", dest="pipelined_collect",
                        action="store_true", default=True,
                        help="Lag-1 pipelined rollout collection "
                             "(default): per env step only the action "
                             "crosses device->host; logits/baseline "
                             "materialize one tick behind (overlapped "
                             "with env stepping) and agent state never "
                             "leaves the device. Identical batches to "
                             "the synchronous schedule.")
    parser.add_argument("--no_pipelined_collect", dest="pipelined_collect",
                        action="store_false",
                        help="Synchronous collection: materialize every "
                             "policy result on host before stepping "
                             "envs (debugging / host-policy baselines).")
    parser.add_argument("--superstep_k", type=int, default=1,
                        help="Learner superstep: fuse K SGD updates "
                             "into ONE lax.scan dispatch over a "
                             "[K, T+1, B, ...] batch stack (schedules "
                             "tick per-update inside the scan; stats "
                             "come back [K]-stacked so the host syncs "
                             "once per K updates). Bit-identical to K "
                             "sequential dispatches. Requires "
                             "num_actors/batch_size divisible by K "
                             "(each collect dispatches whole "
                             "supersteps). 1 = today's per-update "
                             "dispatch.")
    parser.add_argument("--seed", type=int, default=1234)
    parser.add_argument("--env_seed", type=int, default=None,
                        help="Base seed for stochastic envs; env i draws "
                             "from env_seed+i, so actors stay decorrelated "
                             "but the run reproduces (with --serial_envs "
                             "and a fixed --seed, end-to-end). Default: OS "
                             "entropy per env.")
    parser.add_argument("--max_env_restarts", type=int, default=10,
                        help="Supervision budget for process-pool env "
                             "workers: a crashed worker respawns with a "
                             "fresh env, its slot emitting an episode "
                             "boundary. 0 = fail fast. (--serial_envs "
                             "has no workers to supervise.)")
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
                             "— lag-tolerant, unlocks --replay_reuse.")
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
    telemetry.add_arguments(parser)
    return parser


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
        vtrace_impl=getattr(flags, "vtrace_impl", "associative"),
        opt_state_dtype=policy.opt_state_dtype,
        param_dtype=policy.param_dtype,
        opt_factored=getattr(flags, "factored_opt_state", False),
        opt_impl=getattr(flags, "opt_impl", "xla"),
        loss=getattr(flags, "loss", "vtrace"),
        impact_clip=getattr(flags, "impact_clip", 0.2),
        replay_reuse=max(1, getattr(flags, "replay_reuse", 1) or 1),
    )


def _make_pool(flags, num_envs):
    # functools.partial (not a lambda): ProcessEnvPool pickles the factory
    # into spawn-context workers.
    env_seed = getattr(flags, "env_seed", None)
    env_fns = [
        functools.partial(
            create_env, flags.env,
            seed=None if env_seed is None else env_seed + i,
        )
        for i in range(num_envs)
    ]
    if flags.serial_envs:
        return SerialEnvPool(env_fns)
    return ProcessEnvPool(env_fns, max_restarts=flags.max_env_restarts)


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


def _probe_env(flags):
    """One throwaway env instance -> (num_actions, frame shape/dtype)."""
    from torchbeast_tpu.envs import num_actions_of
    from torchbeast_tpu.envs.environment import Environment

    probe = create_env(flags.env)
    n = num_actions_of(probe)
    frame = Environment(probe).initial()["frame"]
    if hasattr(probe, "close"):
        probe.close()
    return int(n), frame.shape, frame.dtype


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


def _init_model_and_params(flags, num_actions, batch_size, frame_shape,
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
    attention_impl = getattr(flags, "attention_impl", "dense")
    if attention_impl != "dense":
        if flags.model != "transformer":
            raise ValueError(
                "--attention_impl applies to --model transformer only"
            )
        extra["attention_impl"] = attention_impl
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
        if attention_impl != "dense":
            # In _Block the ring branch wins whenever T divides the seq
            # axis, so the fused kernel would silently only serve the
            # T=1 acting path — reject instead of surprising the user.
            raise ValueError(
                "--attention_impl pallas and --sequence_parallel are "
                "mutually exclusive (the ring path replaces the fused "
                "kernel on the learner forward)"
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
            from torchbeast_tpu.models import PipelinedTransformerNet

            default_stages = PipelinedTransformerNet.num_layers
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
        logging.getLogger(__name__).info(
            "--model %s without --pipeline_parallel: the stage tower "
            "runs sequentially on one device", flags.model,
        )
    if num_experts:
        if flags.model != "transformer":
            raise ValueError(
                "--num_experts applies to --model transformer only (the "
                "conv/MLP families have no MoE formulation)"
            )
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
            if flags.model not in ("transformer", "olmoe") or value < 0:
                raise ValueError(
                    f"--{flag} is a positive depth or window of --model "
                    "transformer or olmoe"
                )
            extra[flag] = value
    trunk_channels = getattr(flags, "trunk_channels", "")
    if trunk_channels:
        if flags.model != "deep":
            raise ValueError(
                "--trunk_channels applies to --model deep only (the "
                "knob widens the ResNet conv trunk)"
            )
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
        build_model=lambda kw: create_model(
            flags.model, num_actions=num_actions,
            use_lstm=flags.use_lstm, dtype=dtype,
            **{**plan_extra, **kw},
        ),
    )
    extra.update(
        remat_plan_lib.model_kwargs(flags.model, plan.assignment)
    )
    model = create_model(
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
    dummy = dummy_env_outputs(1, batch_size, frame_shape, frame_dtype)
    state = model.initial_state(batch_size)
    params = model.init(
        {
            "params": jax.random.PRNGKey(flags.seed),
            "action": jax.random.PRNGKey(flags.seed + 1),
        },
        dummy,
        state,
    )
    # bf16_train: params are bf16-RESIDENT from here on — every
    # consumer (acting, learner, checkpoint templates) sees bf16; the
    # f32 master materializes inside optimizer.init (learner.
    # _bf16_resident_params). Cross-precision checkpoint resume fails
    # loudly at the template match, by design.
    params = precision_lib.cast_params(params, policy)
    return model, params


def train(flags):
    if flags.num_actors % flags.batch_size != 0:
        raise ValueError(
            "num_actors must be a multiple of batch_size in the sync trainer "
            f"(got {flags.num_actors} vs {flags.batch_size})"
        )
    superstep_k = getattr(flags, "superstep_k", 1)
    if superstep_k < 1:
        raise ValueError(f"--superstep_k must be >= 1, got {superstep_k}")
    if getattr(flags, "fleet", None):
        raise ValueError(
            "--fleet needs the async driver (polybeast): the sync "
            "trainer is single-host by design"
        )
    if (flags.num_actors // flags.batch_size) % superstep_k != 0:
        # Each collect's sub-batches must split into whole supersteps —
        # a fixed-K scan cannot consume a partial group, and carrying
        # sub-batches across collects would silently change policy lag.
        raise ValueError(
            f"--superstep_k {superstep_k} must divide the "
            f"{flags.num_actors // flags.batch_size} learner sub-batches "
            "per collect (num_actors / batch_size)"
        )
    n_dev = getattr(flags, "num_learner_devices", 1)
    if n_dev > 1:
        # Pure flag predicates — reject BEFORE any side effects
        # (FileWriter dir, env probe, model init).
        if any(
            (getattr(flags, f, 0) or 0) > 1
            for f in ("sequence_parallel", "expert_parallel",
                      "pipeline_parallel")
        ):
            raise ValueError(
                "--num_learner_devices in the sync trainer is plain DP; "
                "composing DP with SP/EP/PP needs the async driver's "
                "composite meshes (polybeast)"
            )
        if flags.batch_size % n_dev != 0:
            raise ValueError(
                f"batch_size {flags.batch_size} not divisible by "
                f"num_learner_devices {n_dev}"
            )
        if getattr(flags, "opt_impl", "xla") == "pallas":
            raise ValueError(
                "--opt_impl pallas does not compose with "
                "--num_learner_devices > 1 yet (the fused tail is a "
                "per-chip kernel; its sharded-update story is the "
                "Sebulba item's)"
            )
    # Sebulba device split (ISSUE 15, runtime/placement.py): resolved
    # and composition-checked before any side effects. None covers the
    # single-device degradation.
    from torchbeast_tpu.runtime.placement import (
        resolve_device_split,
        validate_split_composition,
    )

    split = resolve_device_split(
        getattr(flags, "device_split", ""), jax.devices()
    )
    validate_split_composition(
        flags, split,
        parallel_flags=("sequence_parallel", "expert_parallel",
                        "pipeline_parallel"),
    )
    if split is not None and getattr(flags, "opt_impl", "xla") == "pallas":
        raise ValueError(
            "--opt_impl pallas does not compose with --device_split "
            "yet (the fused tail is a per-chip kernel)"
        )
    if flags.xpid is None:
        flags.xpid = "torchbeast-tpu-%s" % time.strftime("%Y%m%d-%H%M%S")
    plogger = FileWriter(
        xpid=flags.xpid, xp_args=vars(flags), rootdir=flags.savedir
    )
    checkpoint_path = os.path.join(
        os.path.expanduser(flags.savedir), flags.xpid, "model.ckpt"
    )
    # Telemetry (ISSUE 2): stage latencies, learner batch-size
    # distribution, and dispatch-queue occupancy land in
    # {xpid}/telemetry.jsonl on the 5s log cadence.
    tele = telemetry.DriverTelemetry(
        flags, plogger.paths["telemetry"], driver="monobeast",
        annotation_factory=jax.profiler.TraceAnnotation,
        annotation_active=jax.profiler.TraceAnnotation.is_enabled,
    )
    telemetry_on = tele.enabled
    reg = tele.registry
    # Stall visibility (ISSUE 6): the sync trainer has no monitor
    # thread, so a wedged collect (dead env worker, hung device) used
    # to look like silence. The watchdog degrades health.state and
    # dumps thread stacks after --learner_stall_timeout_s of no update
    # dispatches.
    from torchbeast_tpu.resilience import LearnerWatchdog, PipelineHealth

    health = PipelineHealth(registry=reg)
    watchdog = LearnerWatchdog(
        getattr(flags, "learner_stall_timeout_s", 300.0),
        health=health,
        registry=reg,
    )

    hp = hparams_from_flags(flags)
    prec = precision_lib.resolve_flags(flags)
    num_actions, frame_shape, frame_dtype = _probe_env(flags)
    B = flags.num_actors
    T = flags.unroll_length

    model, params = _init_model_and_params(
        flags, num_actions, B, frame_shape, frame_dtype
    )
    # The resolved remat plan rides every telemetry line as a static
    # (same convention as polybeast's acting_path block).
    from torchbeast_tpu.runtime import remat_plan as remat_plan_lib

    remat_plan = remat_plan_lib.last_plan()
    if remat_plan is not None:
        tele.set_static("learner.remat_plan", remat_plan.summary())
    optimizer = learner_lib.make_optimizer(hp)
    opt_state = optimizer.init(params)

    step = 0
    stats = {}
    if os.path.exists(checkpoint_path):
        restored = load_checkpoint(
            checkpoint_path,
            params_template=params,
            opt_state_template=opt_state,
        )
        params, opt_state = restored["params"], restored["opt_state"]
        step = restored["step"]
        stats = restored["stats"]
        log.info("Resuming preempted job, current stats:\n%s", stats)

    # Zero-lag mode donates params (nothing references the old buffer
    # once the cell is swapped); overlap mode acts on the old params for
    # a whole unroll, so only the opt state may be donated.
    donate = "opt_only" if flags.overlap_collect else True
    n_dev = getattr(flags, "num_learner_devices", 1)
    K = superstep_k
    # --replay_reuse K': every staged batch is dispatched K' times
    # (IMPACT's sample reuse). Reused batches cannot be donated — the
    # second dispatch would read a donated buffer — so batch donation
    # stays a K'=1 optimization.
    reuse = max(1, hp.replay_reuse)
    # A split with ONE learner device takes the plain-jit path below
    # pinned by explicit placement — a 1-device mesh would pull the
    # update through the SPMD partitioner for nothing (measured ~1.7x
    # slower per update on the CPU lane).
    learner_device = None
    if split is not None and len(split.learner_devices) == 1:
        learner_device = split.learner_devices[0]
    use_mesh = n_dev > 1 or (
        split is not None and learner_device is None
    )
    if use_mesh:
        from torchbeast_tpu.parallel import (
            create_mesh,
            make_parallel_update_step,
            replicate,
            shard_batch,
        )

        # Under the split the mesh spans exactly the learner devices;
        # otherwise the first n_dev devices.
        if split is not None:
            mesh = create_mesh(devices=list(split.learner_devices))
        else:
            mesh = create_mesh(n_dev)
        params = replicate(mesh, params)
        opt_state = replicate(mesh, opt_state)
        # superstep_k > 1: the same K-scan wrapper, sharded — the staged
        # [K, T+1, B] stack is fresh (stack_superstep_columns copies),
        # consumed exactly once, so batch donation's consume-once
        # enforcement applies.
        update_step = make_parallel_update_step(
            model, optimizer, hp, mesh, donate=donate,
            superstep_k=K, donate_batch=K > 1 and reuse == 1,
        )
        place_sub = lambda b, s: shard_batch(  # noqa: E731
            mesh,
            precision_lib.cast_batch(b, prec.batch_dtype),
            precision_lib.cast_batch(s, prec.batch_dtype),
            leading_axes=1 if K > 1 else 0,
        )
        log.info(
            "Sync learner data-parallel over %d devices%s",
            int(mesh.shape["data"]),
            " (device split)" if split is not None else "",
        )
    else:
        if K > 1:
            # One dispatch = K scanned updates; the staged stack is a
            # fresh copy nothing re-reads, so donate it (consume-once
            # deletion — learner.consume_staged_inputs).
            update_step = learner_lib.make_update_superstep(
                model, optimizer, hp, K, donate=donate,
                donate_batch=reuse == 1,
            )
        else:
            # No donate_batch: update_body emits no batch-shaped outputs
            # to alias, so donating the staged batch frees nothing (see
            # learner.donate_argnums_for).
            update_step = learner_lib.make_update_step(
                model, optimizer, hp, donate=donate
            )
        # Explicit (async) placement: donation needs committed device
        # buffers — a host-numpy arg reaches the jit as an undonatable
        # transfer (and a warning); device_put also starts the H2D copy
        # before dispatch instead of inside it. The precision policy's
        # staging cast happens here (bf16_train: float32 leaves travel
        # host->device half-width; the learner upcasts at point of
        # use).
        if learner_device is not None:
            params = jax.device_put(params, learner_device)
            opt_state = jax.device_put(opt_state, learner_device)
        place_sub = lambda b, s: (  # noqa: E731
            jax.device_put(
                precision_lib.cast_batch(b, prec.batch_dtype),
                learner_device,
            ),
            jax.device_put(
                precision_lib.cast_batch(s, prec.batch_dtype),
                learner_device,
            ),
        )
    if telemetry_on:
        # Dispatch latency + batch transfer bytes per update (counts K
        # updates per superstep dispatch).
        update_step = learner_lib.instrument_update_step(
            update_step, superstep_k=K
        )
    count_host_sync = getattr(
        update_step, "count_host_sync", lambda: None
    )
    if K > 1:
        log.info("Learner supersteps: %d updates per dispatch", K)
    act_step = learner_lib.make_act_step(model)

    # Split acting placement: the policy forward runs pinned to the
    # first inference device — params re-placed there (one explicit
    # device-to-device copy) at every rebind, so collect and learn
    # never contend for one chip. Identity off-split.
    if split is not None:
        act_device = split.inference_devices[0]
        place_act = lambda p: jax.device_put(p, act_device)  # noqa: E731
        tele.set_static("device_split", split.describe())
        log.info(
            "Acting pinned to inference device %s",
            getattr(act_device, "id", act_device),
        )
    else:
        place_act = lambda p: p  # noqa: E731
    # The learner mesh shape rides every telemetry line (polybeast's
    # convention): the 1x1 placeholder for the single-device update.
    tele.set_static(
        "learner.mesh_shape",
        {k: int(v) for k, v in mesh.shape.items()}
        if use_mesh else {"data": 1, "model": 1},
    )

    # IMPACT target network (--loss impact): full-precision params
    # stamped every --target_refresh_updates updates ride the same
    # versioned store class as replica serving snapshots — the
    # "learner.target" namespace keeps its cadence out of the serving
    # counters, and cast_bf16=False because the target forward must
    # equal a forward of the exact stamped params.
    target_store = None
    target_forward = None
    updates_done = 0
    if hp.loss == "impact":
        from torchbeast_tpu.serving.snapshot import PolicySnapshotStore

        target_store = PolicySnapshotStore(
            max(1, getattr(flags, "target_refresh_updates", 8) or 1),
            registry=reg,
            namespace="learner.target",
            cast_bf16=False,
        )
        target_forward = learner_lib.make_target_forward(
            model, superstep_k=K
        )
        # v0 before any update: the first batches train against the
        # init params (ratio == 1, the V-trace-equivalent point).
        target_store.publish(0, params)
        log.info(
            "IMPACT loss: target network refresh every %d updates, "
            "replay reuse %d",
            target_store.refresh_updates, reuse,
        )

    pool = _make_pool(flags, B)
    # A failure between the pool spawn and the main try/finally
    # (collector priming, closure setup) must not leak the env
    # worker processes — same reaping contract as polybeast's
    # server group.
    try:
        rng = jax.random.PRNGKey(flags.seed + 2)

        # Mutable cell so the policy closure always samples with fresh rng.
        rng_cell = [rng]
        pipelined = getattr(flags, "pipelined_collect", True)

        def policy(env_output, agent_state):
            rng_cell[0], key = jax.random.split(rng_cell[0])
            model_inputs = {
                k: env_output[k]
                for k in ("frame", "reward", "done", "last_action")
            }
            out, new_state = act_step(params_cell[0], key, model_inputs, agent_state)
            if pipelined:
                # The lag-1 collector owns materialization: it fetches
                # the action per step and everything else one tick
                # behind; state stays on device end-to-end.
                return out, new_state
            return jax.device_get(out), new_state

        params_cell = [place_act(params)]
        collector_cls = (
            PipelinedRolloutCollector if pipelined else RolloutCollector
        )
        collector = collector_cls(
            pool, policy, model.initial_state(B), unroll_length=T
        )

        # Stage latencies (collect/learn) become driver.* histograms in
        # the snapshot and pb: spans on the profiler's clock; with
        # telemetry off, a private registry and tracer keep the 5s log
        # line working unchanged.
        timings = Timings(
            registry=reg if telemetry_on else None, prefix="driver.",
            tracer=telemetry.get_tracer() if telemetry_on else None,
        )
        sp_collect = timings.section("collect")
        sp_learn = timings.section("learn")
        # The sync trainer has no inter-thread queues; its occupancy
        # analog is the delayed-stats dispatch pipeline — update
        # batches dispatched whose stats the host has NOT yet flushed
        # (sampled at the log tick: 0 before the first dispatch /
        # after the final flush, B/batch_size in steady state).
        h_batch_size = reg.histogram("learner.batch_size")
        g_dispatch_q = reg.gauge("dispatch_queue.depth")
        # env vs learn throughput split (ISSUE 18): env_sps counts
        # unique environment frames; learn_sps counts frames consumed
        # by updates — env_sps x replay_reuse in steady state.
        g_env_sps = reg.gauge("learner.env_sps")
        g_learn_sps = reg.gauge("learner.learn_sps")
        reg.gauge("learner.sample_reuse").set(reuse)
        last_checkpoint_time = time.time()
        last_log_time = time.time()
        last_log_step = step
        learn_step = step * reuse  # resume: exact split not persisted
        last_log_learn_step = learn_step

        if flags.profile_dir:
            jax.profiler.start_trace(flags.profile_dir)

        # One-iteration-delayed stats fetch: updates for unroll k are
        # DISPATCHED (async) and the host immediately starts collecting
        # unroll k+1; the blocking device_get of k's stats happens after
        # k+1's work is underway. What overlaps beyond that depends on the
        # policy-lag choice:
        # - default (zero lag): the first act of unroll k+1 data-depends on
        #   the updated params, so its device_get blocks until the update
        #   chain finishes — only the stats fetch is truly overlapped. This
        #   is a deliberate on-policy guarantee the reference does not have.
        # - --overlap_collect: acting adopts the chain head only after a
        #   full collect has passed since its dispatch, so the update chain
        #   always hides behind env stepping and no act ever blocks on it.
        #   The acting params trail the learner head by one dispatched
        #   unroll-batch — still strictly tighter than the reference, whose
        #   actors lag by queue depth (SURVEY.md, actorpool backpressure).
        pending = None  # (list of device stats, step after those updates)
        latest_params = params_cell[0]  # head of the update chain

        def flush_stats(pending_entry):
            device_stats, at_step = pending_entry
            sub_stats = jax.device_get(device_stats)  # one batched transfer
            count_host_sync()
            agg = {}
            for key in sub_stats[0]:
                # Each dispatch's stats leaves are scalars (K=1) or
                # [K]-stacked (supersteps): concatenate to per-UPDATE
                # rows so episode sums/counts SUM over every update and
                # loss keys MEAN over every update — identical
                # aggregation either way, no /K undercount.
                vals = np.concatenate([
                    np.atleast_1d(np.asarray(s[key], np.float64))
                    for s in sub_stats
                ])
                if key in ("episode_returns_sum", "episode_count"):
                    agg[key] = float(vals.sum())
                else:
                    agg[key] = float(vals.mean())
            out = learner_lib.episode_stat_postprocess(agg)
            out["step"] = at_step
            plogger.log(out)
            return out

        def merge_target(placed_batch, placed_state):
            """Thread the lagged target network's forward outputs into
            the staged batch (learner.TARGET_*_KEY) — computed once per
            FRESH batch and shared by all K' reuse dispatches, so the
            target is held fixed across the reuse epochs (IMPACT's
            contract). Identity under --loss vtrace."""
            if target_forward is None:
                return placed_batch
            _, tparams = target_store.latest()
            t_logits, t_base = target_forward(
                tparams, placed_batch, placed_state
            )
            return {
                **placed_batch,
                learner_lib.TARGET_LOGITS_KEY: t_logits,
                learner_lib.TARGET_BASELINE_KEY: t_base,
            }

        def maybe_refresh_target():
            # Between reuse groups only — never mid-reuse, so every
            # batch trains against exactly one target version.
            if target_store is not None and target_store.note_update(
                updates_done
            ):
                target_store.publish(updates_done, latest_params)

    except BaseException:
        pool.close()
        raise
    watchdog.start()
    try:
        while step < flags.total_steps:
            with sp_collect:
                batch, initial_agent_state = collector.collect()
            with sp_learn:
                if flags.overlap_collect:
                    # Adopt the chain head dispatched BEFORE this
                    # collect — it had the whole collect to materialize,
                    # so the next collect's first act won't block on it;
                    # the updates dispatched below hide behind the NEXT
                    # collect the same way. (Adopting before collect()
                    # would re-create the zero-lag block: the head would
                    # be moments old.)
                    params_cell[0] = place_act(latest_params)

                # Split the [T+1, num_actors] unroll into learner batches
                # of batch_size columns; aggregate stats over ALL
                # sub-batches (losses averaged, episode sums/counts
                # summed). With supersteps, K consecutive sub-batches
                # stack into one [K, T+1, batch_size] dispatch — the scan
                # applies them in the SAME order the per-update loop
                # would, so the update sequence (and with it every
                # schedule tick) is identical.
                device_stats = []
                if K > 1:
                    group = K * flags.batch_size
                    for i in range(0, B, group):
                        stacked, stacked_state = (
                            learner_lib.stack_superstep_columns(
                                batch, initial_agent_state, K,
                                flags.batch_size, offset=i,
                            )
                        )
                        stacked, stacked_state = place_sub(
                            stacked, stacked_state
                        )
                        stacked = merge_target(stacked, stacked_state)
                        # --replay_reuse: the SAME placed batch is
                        # dispatched K' times (donation is off for
                        # K' > 1, so nothing invalidates the buffers);
                        # env frames advance on the first pass only.
                        for r in range(reuse):
                            for _ in range(K):
                                h_batch_size.observe(flags.batch_size)
                            latest_params, opt_state, train_stats = (
                                update_step(
                                    latest_params, opt_state, stacked,
                                    stacked_state,
                                )
                            )
                            device_stats.append(train_stats)
                            updates_done += K
                            if r == 0:
                                step += K * T * flags.batch_size
                            learn_step += K * T * flags.batch_size
                        maybe_refresh_target()
                else:
                    for i in range(0, B, flags.batch_size):
                        sub = {
                            k: v[:, i : i + flags.batch_size]
                            for k, v in batch.items()
                        }
                        sub_state = jax.tree_util.tree_map(
                            lambda s: s[:, i : i + flags.batch_size],
                            initial_agent_state,
                        )
                        sub, sub_state = place_sub(sub, sub_state)
                        sub = merge_target(sub, sub_state)
                        # Actual sub-batch columns, not the flag (honest
                        # even while train() enforces divisibility).
                        cols = min(i + flags.batch_size, B) - i
                        for r in range(reuse):
                            h_batch_size.observe(cols)
                            latest_params, opt_state, train_stats = (
                                update_step(
                                    latest_params, opt_state, sub,
                                    sub_state,
                                )
                            )
                            device_stats.append(train_stats)
                            updates_done += 1
                            if r == 0:
                                step += T * flags.batch_size
                            learn_step += T * flags.batch_size
                        maybe_refresh_target()
                if not flags.overlap_collect:
                    # zero policy lag
                    params_cell[0] = place_act(latest_params)
                if pending is not None:
                    stats = flush_stats(pending)
                pending = (device_stats, step)
            watchdog.ping()

            now = time.time()
            if now - last_log_time > 5:
                sps = (step - last_log_step) / (now - last_log_time)
                learn_sps = (learn_step - last_log_learn_step) / (
                    now - last_log_time
                )
                last_log_time, last_log_step = now, step
                last_log_learn_step = learn_step
                g_env_sps.set(sps)
                g_learn_sps.set(learn_sps)
                # Dispatched-unflushed UPDATES at this instant (the
                # delayed-stats pipeline's real occupancy; a superstep
                # dispatch holds K updates, so count K per entry).
                g_dispatch_q.set(len(pending[0]) * K if pending else 0)
                tele.write(extra={"step": step})
                means = timings.means()
                log.info(
                    "Steps %d @ %.1f SPS. Loss %s. "
                    "[collect %.0fms learn %.0fms] %s",
                    step,
                    sps,
                    # First log can precede the first (delayed) stats
                    # fetch — print a placeholder, not a scary nan.
                    (
                        f"{stats['total_loss']:.4f}"
                        if "total_loss" in stats
                        else "--"
                    ),
                    1000 * means.get("collect", 0.0),
                    1000 * means.get("learn", 0.0),
                    f"Return {stats['mean_episode_return']:.1f}."
                    if "mean_episode_return" in stats
                    else "",
                )

            if now - last_checkpoint_time > flags.checkpoint_interval_s:
                save_checkpoint(
                    checkpoint_path,
                    params=latest_params,
                    opt_state=opt_state,
                    step=step,
                    flags=vars(flags),
                    stats=stats,
                )
                last_checkpoint_time = now
        successful = True
    except KeyboardInterrupt:
        log.info("Interrupted; saving final checkpoint.")
        successful = True
    except BaseException:
        successful = False
        raise
    finally:
        watchdog.stop()
        # Flush the one-iteration-delayed stats so the final checkpoint
        # and return value are current even on interrupt (guarded: an
        # async XLA error may surface here instead of at dispatch).
        if pending is not None:
            try:
                stats = flush_stats(pending)
            except Exception:
                log.exception("Could not flush final stats")
            pending = None
        g_dispatch_q.set(0)  # everything flushed (or abandoned) now
        if flags.profile_dir:
            jax.profiler.stop_trace()
        save_checkpoint(
            checkpoint_path,
            params=latest_params,
            opt_state=opt_state,
            step=step,
            flags=vars(flags),
            stats=stats,
        )
        tele.shutdown(step=step)
        plogger.close(successful=successful)
        pool.close()
    log.info("Learning finished after %d steps.", step)
    return stats


def test(flags):
    """Greedy evaluation episodes (reference monobeast.py:508-542)."""
    if flags.xpid is None:
        checkpoint_path = os.path.expanduser(
            os.path.join(flags.savedir, "latest", "model.ckpt")
        )
    else:
        checkpoint_path = os.path.expanduser(
            os.path.join(flags.savedir, flags.xpid, "model.ckpt")
        )

    num_actions, frame_shape, frame_dtype = _probe_env(flags)
    model, params = _init_model_and_params(
        flags, num_actions, 1, frame_shape, frame_dtype
    )
    if os.path.exists(checkpoint_path):
        hp = hparams_from_flags(flags)
        optimizer = learner_lib.make_optimizer(hp)
        restored = load_checkpoint(
            checkpoint_path,
            params_template=params,
            opt_state_template=optimizer.init(params),
        )
        params = restored["params"]
        log.info("Loaded checkpoint from %s", checkpoint_path)
    else:
        log.warning("No checkpoint at %s; testing random init.", checkpoint_path)

    from torchbeast_tpu.envs.environment import Environment

    # Same seed contract as training: --env_seed pins the eval env's
    # draw stream so repeated evaluations of a checkpoint reproduce.
    env = Environment(
        create_env(flags.env, seed=getattr(flags, "env_seed", None))
    )
    act = jax.jit(
        lambda p, inputs, state: model.apply(
            p, inputs, state, sample_action=False
        )
    )

    returns = []
    observation = env.initial()
    agent_state = model.initial_state(1)
    while len(returns) < flags.num_test_episodes:
        inputs = {
            k: np.asarray(observation[k])[None, None]
            for k in ("frame", "reward", "done", "last_action")
        }
        out, agent_state = act(params, inputs, agent_state)
        observation = env.step(int(out.action[0, 0]))
        if observation["done"]:
            returns.append(float(observation["episode_return"]))
            log.info("Episode ended after %d steps. Return: %.1f",
                     int(observation["episode_step"]), returns[-1])
    env.close()
    log.info(
        "Average returns over %i episodes: %.1f",
        len(returns), sum(returns) / len(returns),
    )
    return returns


def main(flags):
    _configure_logging()
    log_backend(log, flags)
    if flags.mode == "train":
        return train(flags)
    return test(flags)


def cli():
    from torchbeast_tpu.utils import install_preemption_handler
    from torchbeast_tpu.utils.xla_cache import use_compile_cache

    install_preemption_handler()  # SIGTERM -> clean checkpointed exit
    use_compile_cache()
    main(make_parser().parse_args())


if __name__ == "__main__":
    cli()
