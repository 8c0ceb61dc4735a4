"""Anakin: the fully-on-TPU IMPALA trainer for jittable environments.

The Podracer "Anakin" architecture (arXiv:2104.06272): when the env itself
is a JAX function, the ENTIRE actor-learner iteration — vmapped env steps,
policy forward, rollout assembly, V-trace, losses, optimizer update — fuses
into one jitted XLA program with `lax.scan` over the unroll. No host in the
loop at all; multi-chip scaling is the same replicated-params /
batch-sharded jit as the poly learner (parallel/dp.py). Nothing in the
reference corresponds to this: it is the capability the TPU-first design
unlocks (its envs are C++/OpenCV-bound, SURVEY.md §7 design stance).

The rollout kept on device preserves the same batch layout and on-policy
invariants as the host-side collectors (slot 0 = boundary step, agent
output at slot i computed from env output at slot i-1), so the SAME
learner.compute_loss is reused unchanged.

Run:  python -m torchbeast_tpu.anakin --env Catch --total_steps 200000
"""

import argparse
import logging
import os
import time
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu.envs.jax_env import create_jax_env
from torchbeast_tpu.learner_setup import (
    add_learner_arguments,
    hparams_from_flags,
    init_model_and_params,
    initial_params,
)
from torchbeast_tpu.models import MODEL_NAMES
from torchbeast_tpu.utils import (
    FileWriter,
    configure_logging,
    load_checkpoint,
    save_checkpoint,
)
from torchbeast_tpu.utils.backend import log_backend

log = logging.getLogger("torchbeast_tpu.anakin")


def _agent_out_dict(out):
    return {
        "action": out.action,
        "policy_logits": out.policy_logits,
        "baseline": out.baseline,
    }


class ActorCarry(NamedTuple):
    """Cross-update actor state (the on-device analog of the rollout
    collector's pending env/agent outputs + recurrent state)."""

    env_state: Any
    env_out: Any  # dict of [B, ...]
    agent_out: Any  # dict of [B, ...]
    agent_state: Any
    rng: Any


# The families with a path through the JAX envs here; the others
# (pipelined_transformer, olmoe) have none yet.
_FAMILIES = ("shallow", "deep", "mlp", "pipelined_mlp", "transformer")


def make_parser():
    parser = argparse.ArgumentParser(description=__doc__)
    add_learner_arguments(
        parser, model_default="mlp",
        only=(
            "--env", "--xpid", "--savedir", "--total_steps", "--batch_size",
            "--unroll_length", "--model", "--use_lstm", "--num_experts",
            "--seed", "--checkpoint_interval_s", "--entropy_cost",
            "--entropy_cost_final", "--baseline_cost", "--discounting",
            "--reward_clipping", "--learning_rate", "--alpha", "--momentum",
            "--epsilon", "--grad_norm_clipping",
        ),
        overrides={
            "--env": dict(default="Catch", type=None),
            "--model": dict(
                choices=[m for m in MODEL_NAMES if m in _FAMILIES]
            ),
            "--total_steps": dict(default=200000),
            "--batch_size": dict(
                default=64, help="Parallel on-device environments."
            ),
            "--unroll_length": dict(default=16),
        },
    )
    parser.add_argument("--num_devices", type=int, default=1,
                        help="Data-parallel devices (envs sharded, params "
                             "replicated, ICI all-reduce).")
    parser.add_argument("--log_interval_updates", type=int, default=20)
    return parser


def make_train_step(env, model, optimizer, hp: learner_lib.HParams, mesh=None):
    """One fused iteration: T env/policy steps (scan) + learner update.

    (params, opt_state, carry) -> (params, opt_state, carry, stats)
    """
    T = hp.unroll_length

    def policy_step(params, rng, env_out, agent_state):
        """T=1 forward on [B, ...] env outputs (shared learner.act_body)."""
        inputs = {
            k: env_out[k]
            for k in ("frame", "reward", "done", "last_action")
        }
        out, new_state = learner_lib.act_body(
            model, params, rng, inputs, agent_state
        )
        return _agent_out_dict(out), new_state

    def rollout_step(params, carry: ActorCarry, _):
        rng, key = jax.random.split(carry.rng)
        agent_out, agent_state = policy_step(
            params, key, carry.env_out, carry.agent_state
        )
        env_state, env_out = jax.vmap(env.step)(
            carry.env_state, agent_out["action"]
        )
        new_carry = ActorCarry(
            env_state=env_state,
            env_out=env_out,
            agent_out=agent_out,
            agent_state=agent_state,
            rng=rng,
        )
        # Emitted slot pairs env output i with the agent output computed
        # from env output i-1 (collector pairing invariant).
        return new_carry, (env_out, agent_out)

    def train_step(params, opt_state, carry: ActorCarry):
        initial_agent_state = carry.agent_state
        boundary = (carry.env_out, carry.agent_out)

        carry, (env_seq, agent_seq) = jax.lax.scan(
            partial(rollout_step, params), carry, None, length=T
        )

        # Prepend the boundary step -> [T+1, B, ...] learner batch.
        batch = {
            k: jnp.concatenate([boundary[0][k][None], env_seq[k]], axis=0)
            for k in boundary[0]
        }
        for k in boundary[1]:
            batch[k] = jnp.concatenate(
                [boundary[1][k][None], agent_seq[k]], axis=0
            )

        grads, stats = jax.grad(
            lambda p: learner_lib.compute_loss(
                model, p, batch, initial_agent_state, hp,
                entropy_cost=learner_lib.entropy_schedule(hp)(opt_state),
            ),
            has_aux=True,
        )(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        stats["grad_norm"] = optax.global_norm(grads)
        return params, opt_state, carry, stats

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    from torchbeast_tpu.parallel import mesh as mesh_lib

    repl = mesh_lib.replicated(mesh)
    data = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data")
    )
    state_sh = mesh_lib.state_sharding(mesh)

    carry_shardings = ActorCarry(
        env_state=data, env_out=data, agent_out=data,
        agent_state=state_sh, rng=repl,
    )
    return jax.jit(
        train_step,
        in_shardings=(repl, repl, carry_shardings),
        out_shardings=(repl, repl, carry_shardings, repl),
        donate_argnums=(0, 1, 2),
    )


def initial_carry(env, model, batch_size: int, rng):
    """Reset all envs + prime the boundary agent output (state advance
    discarded, collector convention). Param-init keys derive from `rng`,
    so --seed changes the initialization like the host drivers."""
    rng, env_key, prime_key, init_key, action_key = jax.random.split(rng, 5)
    env_keys = jax.random.split(env_key, batch_size)

    def init_one(key):
        return env.initial(key)

    env_state, env_out = jax.vmap(init_one)(env_keys)
    agent_state = model.initial_state(batch_size)

    model_inputs = {
        k: env_out[k]
        for k in ("frame", "reward", "done", "last_action")
    }
    params = initial_params(
        model,
        {"params": init_key, "action": action_key},
        {k: v[None] for k, v in model_inputs.items()},
        agent_state,
    )
    out, _ = learner_lib.make_act_step(model)(
        params, prime_key, model_inputs, agent_state
    )
    agent_out = _agent_out_dict(out)
    carry = ActorCarry(
        env_state=env_state,
        env_out=env_out,
        agent_out=agent_out,
        agent_state=agent_state,
        rng=rng,
    )
    return params, carry


def train(flags):
    if flags.xpid is None:
        flags.xpid = "anakin-%s" % time.strftime("%Y%m%d-%H%M%S")
    plogger = FileWriter(
        xpid=flags.xpid, xp_args=vars(flags), rootdir=flags.savedir
    )
    checkpoint_path = os.path.join(
        os.path.expanduser(flags.savedir), flags.xpid, "model.ckpt"
    )

    env = create_jax_env(flags.env)
    hp = hparams_from_flags(flags)
    # The model only: its parameters come from `initial_carry`, whose
    # keys derive from the run's one rng.
    model, _ = init_model_and_params(
        flags, env.num_actions, flags.batch_size, env.frame_shape,
        init_params=False,
    )
    optimizer = learner_lib.make_optimizer(hp)

    mesh = None
    if flags.num_devices > 1:
        from torchbeast_tpu.parallel import create_mesh

        if flags.batch_size % flags.num_devices != 0:
            raise ValueError(
                f"batch_size {flags.batch_size} not divisible by "
                f"num_devices {flags.num_devices}"
            )
        mesh = create_mesh(flags.num_devices)
        log.info("Anakin over %d devices", flags.num_devices)

    rng = jax.random.PRNGKey(flags.seed)
    params, carry = initial_carry(env, model, flags.batch_size, rng)
    opt_state = optimizer.init(params)

    step = 0
    if os.path.exists(checkpoint_path):
        restored = load_checkpoint(
            checkpoint_path,
            params_template=params,
            opt_state_template=opt_state,
        )
        params, opt_state = restored["params"], restored["opt_state"]
        step = restored["step"]
        log.info("Resuming preempted job at step %d", step)

    if mesh is not None:
        from torchbeast_tpu.parallel import replicate

        params = replicate(mesh, params)
        opt_state = replicate(mesh, opt_state)
        # Shard the carry along the env-batch axis.
        train_step = make_train_step(env, model, optimizer, hp, mesh)
    else:
        train_step = make_train_step(env, model, optimizer, hp)

    frames_per_update = flags.unroll_length * flags.batch_size
    last_log_time = time.time()
    last_log_step = step
    last_checkpoint = time.time()
    stats_host = {}

    try:
        successful = True
        update = 0
        while step < flags.total_steps:
            params, opt_state, carry, stats = train_step(
                params, opt_state, carry
            )
            step += frames_per_update
            update += 1

            if update % flags.log_interval_updates == 0:
                stats_host = learner_lib.episode_stat_postprocess(
                    jax.device_get(stats)
                )
                stats_host["step"] = step
                plogger.log(stats_host)

                now = time.time()
                if now - last_log_time > 5:
                    sps = (step - last_log_step) / (now - last_log_time)
                    last_log_time, last_log_step = now, step
                    log.info(
                        "Steps %d @ %.1f SPS. Loss %.4f. %s",
                        step, sps,
                        stats_host.get("total_loss", float("nan")),
                        f"Return {stats_host['mean_episode_return']:.2f}."
                        if "mean_episode_return" in stats_host else "",
                    )
                if now - last_checkpoint > flags.checkpoint_interval_s:
                    save_checkpoint(
                        checkpoint_path,
                        params=params, opt_state=opt_state, step=step,
                        flags=vars(flags), stats=stats_host,
                    )
                    last_checkpoint = now
    except KeyboardInterrupt:
        pass
    except BaseException:
        successful = False
        raise
    finally:
        try:
            save_checkpoint(
                checkpoint_path,
                params=params, opt_state=opt_state, step=step,
                flags=vars(flags), stats=stats_host,
            )
        except Exception:
            # An interrupt mid-train_step can leave params pointing at
            # donated (deleted) buffers; losing the exit checkpoint must
            # not also lose the logger close.
            log.exception("Final checkpoint failed")
        plogger.close(successful=successful)
    log.info("Learning finished after %d steps.", step)
    stats_host["step"] = step
    return stats_host


def main(flags):
    configure_logging()
    log_backend(log)
    return train(flags)


def cli():
    from torchbeast_tpu.utils import install_preemption_handler
    from torchbeast_tpu.utils.xla_cache import use_compile_cache

    install_preemption_handler()  # SIGTERM -> clean checkpointed exit
    use_compile_cache()
    main(make_parser().parse_args())


if __name__ == "__main__":
    cli()
