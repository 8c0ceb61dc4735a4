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
from torchbeast_tpu.envs import create_env, probe_env
from torchbeast_tpu.envs.vec import ProcessEnvPool, SerialEnvPool
# The builder's names below are what the benchmark's learner driver and
# chip_smoke.py import from here; new code takes them from learner_setup.
from torchbeast_tpu.learner_setup import (  # noqa: F401
    add_learner_arguments,
    dummy_env_outputs,
    hparams_from_flags,
    init_model_and_params as _init_model_and_params,
    stop_profile,
)
from torchbeast_tpu.rollout import (
    PipelinedRolloutCollector,
    RolloutCollector,
)
from torchbeast_tpu.utils import (
    FileWriter,
    Timings,
    configure_logging,
    load_checkpoint,
    save_checkpoint,
)
from torchbeast_tpu.utils.backend import log_backend

log = logging.getLogger("torchbeast_tpu.monobeast")


def make_parser():
    parser = argparse.ArgumentParser(description=__doc__)
    add_learner_arguments(
        parser, model_default="shallow", num_actors_default=8
    )
    # The sync trainer's own.
    parser.add_argument("--serial_envs", action="store_true",
                        help="Step envs in-process (tests/cheap envs).")
    parser.add_argument("--pipeline_stages", type=int, default=0,
                        help="Total tower depth (pipelined_mlp stages / "
                             "pipelined_transformer layers). Default: "
                             "one stage per pipeline device for the MLP; "
                             "the model's own num_layers for the "
                             "transformer. A multiple k*N runs k looped "
                             "passes.")
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
    parser.add_argument("--max_env_restarts", type=int, default=10,
                        help="Supervision budget for process-pool env "
                             "workers: a crashed worker respawns with a "
                             "fresh env, its slot emitting an episode "
                             "boundary. 0 = fail fast. (--serial_envs "
                             "has no workers to supervise.)")
    telemetry.add_arguments(parser)
    return parser


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
    num_actions, frame_shape, frame_dtype = probe_env(flags.env)
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
            stop_profile(flags, tele, update_step, stats)
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

    num_actions, frame_shape, frame_dtype = probe_env(flags.env)
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
    configure_logging()
    log_backend(log)
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
