"""Scalable async IMPALA learner (the reference PolyBeast's role,
/root/reference/torchbeast/polybeast_learner.py + polybeast.py), TPU-native.

Runtime shape mirrors the reference (SURVEY.md §3.2/§3.3): an ActorPool of
socket actor loops feeds a DynamicBatcher whose consumer threads run a
jitted bucket-padded forward on the TPU; completed rollouts flow through a
BatchingQueue (backpressure = on-policy guarantee) into the learner thread,
which runs the single jitted update step. Where the reference copies
weights to a second GPU each step (load_state_dict, polybeast_learner.py:
369), here actor and learner share one on-device params pytree — weight
propagation is a reference rebind under the GIL, zero copies.

Run (combined, like the reference's polybeast.py launcher):
  python -m torchbeast_tpu.polybeast --env Mock --num_servers 4 \
      --total_steps 20000
"""

import argparse
import functools
import logging
import os
import queue as stdlib_queue
import threading
import time

import jax
import numpy as np

from torchbeast_tpu import learner as learner_lib
from torchbeast_tpu import precision as precision_lib
from torchbeast_tpu import telemetry
from torchbeast_tpu import polybeast_env
from torchbeast_tpu.envs import probe_env
from torchbeast_tpu.learner_setup import (
    add_learner_arguments,
    dummy_env_outputs,
    hparams_from_flags,
    init_model_and_params,
    stop_profile,
)
from torchbeast_tpu.models import stats as model_stats
from torchbeast_tpu.runtime import wire
from torchbeast_tpu.runtime.actor_pool import ActorPool
from torchbeast_tpu.runtime.inference import default_buckets, inference_loop
from torchbeast_tpu.runtime.queues import (
    BatchingQueue,
    DevicePrefetcher,
    DynamicBatcher,
)
from torchbeast_tpu.utils import (
    FileWriter,
    Timings,
    configure_logging,
    load_checkpoint,
    save_checkpoint,
)
from torchbeast_tpu.utils.backend import log_backend

log = logging.getLogger("torchbeast_tpu.polybeast")


def make_parser():
    parser = argparse.ArgumentParser(description=__doc__)
    add_learner_arguments(
        parser, model_default="deep", num_actors_default=None
    )
    # The async driver's own.
    parser.add_argument("--pipes_basename", default="unix:/tmp/torchbeast_tpu")
    parser.add_argument("--num_servers", type=int, default=4)
    parser.add_argument("--start_servers", dest="start_servers",
                        action="store_true", default=True,
                        help="Spawn local env servers (the combined "
                             "launcher mode).")
    parser.add_argument("--no_start_servers", dest="start_servers",
                        action="store_false",
                        help="Connect to externally-launched servers.")
    # Serving loops per batcher, each a launcher/replier pair
    # (runtime/inference.py). One: a second launcher only contends for
    # the state table's lock and the GIL (PERF.md section 6, PR 33).
    parser.add_argument("--num_inference_threads", type=int, default=1)
    # Tri-state: None (default) = native-first with a clean, logged
    # fallback to the Python pool when _tbt_core is absent/stale;
    # True (explicit --native_runtime) = native REQUIRED, unusable
    # extension is a hard error (a benchmark asking for the C++ pool
    # must never silently publish Python-pool numbers); False = forced
    # Python pool.
    parser.add_argument("--native_runtime", dest="native_runtime",
                        action="store_true", default=None,
                        help="Require the C++ queues/batcher/actor-pool "
                             "(_tbt_core; build with "
                             "scripts/build_native.sh). The DEFAULT "
                             "(neither flag) is native-first since "
                             "ISSUE 14: the C++ pool when usable, a "
                             "logged fallback to the Python pool when "
                             "the extension is absent or stale "
                             "(predates the shed protocol); passing "
                             "this flag explicitly makes an unusable "
                             "extension a hard error instead.")
    parser.add_argument("--no_native_runtime", dest="native_runtime",
                        action="store_false",
                        help="Force the Python queues/batcher/actor-"
                             "pool (the semantic reference "
                             "implementation; required for replica "
                             "serving today).")
    parser.add_argument("--native_server", action="store_true",
                        help="Serve environments with the C++ EnvServer "
                             "(GIL-free socket I/O; combined-launcher "
                             "mode only).")
    parser.add_argument("--max_server_restarts", type=int, default=10,
                        help="Supervision budget for spawned env servers "
                             "(see polybeast_env --max_server_restarts); "
                             "0 disables restarts.")
    parser.add_argument("--tensor_parallel", type=int, default=0,
                        help="Megatron column/row-paired tensor "
                             "parallelism for the transformer over a "
                             "`model` mesh axis: q/k/v + FFN-up "
                             "column-sharded, out-proj + FFN-down "
                             "row-sharded (one all-reduce per "
                             "attention/FFN). Composes with "
                             "--num_learner_devices DP on one "
                             "(data x model) mesh; model=transformer "
                             "only.")
    parser.add_argument("--admission_depth_factor", type=int, default=4,
                        help="Admission-gate queue-depth bound as a "
                             "multiple of --max_inference_batch_size "
                             "(the continuous-batching depth knob, "
                             "both runtimes): with --request_deadline_"
                             "ms armed, requests arriving while a "
                             "serving queue already holds factor * "
                             "max_batch pending rows are shed. Deeper "
                             "keeps the formation pipeline fed under "
                             "bursts; shallower sheds earlier instead "
                             "of manufacturing deadline expiries.")
    parser.add_argument("--continuous_batching", dest="continuous_batching",
                        action="store_true", default=True,
                        help="Native runtime: roll late-arriving "
                             "admitted requests into the next dispatch "
                             "window when the forming batch has room, "
                             "instead of leaving them queued behind the "
                             "admission depth bound (default on; "
                             "--admission_depth_factor stays armed as "
                             "the fallback hard bound). The shed/expiry "
                             "audit is unchanged: rolled requests face "
                             "the same deadline gate at dispatch. "
                             "Ignored by the Python batcher.")
    parser.add_argument("--no_continuous_batching",
                        dest="continuous_batching", action="store_false",
                        help="Depth-gated dispatch only (the ISSUE 14 "
                             "admission behavior): requests wait for "
                             "the next batch formation cycle even when "
                             "the in-flight window has room.")
    parser.add_argument("--coordinator_address", default=None,
                        help="Multi-host: jax.distributed coordinator "
                             "(host:port); also reads "
                             "TORCHBEAST_COORDINATOR / _NUM_PROCESSES / "
                             "_PROCESS_ID env vars.")
    parser.add_argument("--device_agent_state", dest="device_agent_state",
                        action="store_true", default=True,
                        help="Keep recurrent agent state in a device-"
                             "resident slot table (default): requests "
                             "carry slot ids, state gathers/advances/"
                             "scatters inside the jitted acting step, "
                             "and per-env-step host traffic shrinks to "
                             "obs-down/action-up. Both runtimes speak "
                             "the slot framing; ignored for stateless "
                             "models (nothing to keep resident).")
    parser.add_argument("--no_device_agent_state",
                        dest="device_agent_state", action="store_false",
                        help="Legacy acting path: agent state rides "
                             "every inference request/reply.")
    parser.add_argument("--prewarm_inference", action="store_true",
                        help="Compile every inference bucket (powers of "
                             "two up to max_inference_batch_size) before "
                             "actors connect, so no actor ever stalls on "
                             "a mid-run XLA compile. Costs startup time; "
                             "steady-state behavior unchanged.")
    parser.add_argument("--max_inference_batch_size", type=int, default=64)
    parser.add_argument("--inference_timeout_ms", type=float, default=100)
    parser.add_argument("--request_deadline_ms", type=float, default=0.0,
                        help="Arm the serving tier's admission gate "
                             "(serving/admission.py): inference "
                             "requests carry this enqueue deadline — "
                             "requests that would queue past it (or "
                             "arrive while the queue is at its depth "
                             "bound, --admission_depth_factor x "
                             "max_inference_batch_size) are "
                             "shed with a typed ShedReply the actor "
                             "re-submits after backoff, so overload "
                             "degrades tail latency instead of "
                             "growing the queue without bound. The "
                             "same number is the per-connection SLO "
                             "target exported in the telemetry `slo` "
                             "block. 0 = no admission control (every "
                             "request queues forever, the pre-ISSUE-14 "
                             "behavior).")
    parser.add_argument("--replica_refresh_updates", type=int, default=0,
                        help="Serve acting requests from versioned "
                             "bf16 policy snapshots published every N "
                             "updates (serving/snapshot.py + "
                             "replica.py): replica serving threads "
                             "answer from the latest snapshot with the "
                             "true per-request policy_lag recorded "
                             "into the rollout (V-trace sees the real "
                             "behavior policy either way — the logits "
                             "ARE the stale policy's). 0 = central "
                             "serving only. Both runtimes: under "
                             "--native_runtime the replica/central "
                             "routing runs in the C++ pool with the "
                             "lag-budget health gate pushed from the "
                             "Python serving hooks.")
    parser.add_argument("--max_policy_lag", type=int, default=20,
                        help="Replica staleness budget, in updates: "
                             "when the latest snapshot trails the "
                             "learner head beyond this (a stalled "
                             "refresh), the replica DEGRADES back to "
                             "the central serving path through the "
                             "health machine instead of serving "
                             "arbitrarily stale actions; it recovers "
                             "when a fresh snapshot lands.")
    parser.add_argument("--max_frame_bytes", type=int,
                        default=wire.DEFAULT_MAX_FRAME_BYTES,
                        help="Reject wire frames longer than this before "
                             "allocating (a corrupt 4-byte header must "
                             "surface as WireError, not a multi-GiB "
                             "allocation).")
    parser.add_argument("--max_learner_queue_size", type=int, default=None,
                        help="Backpressure bound (default: batch_size).")
    parser.add_argument("--actor_connect_timeout_s", type=float,
                        default=600.0,
                        help="Per-attempt actor connect deadline (the "
                             "reference's 10-minute WaitForConnected "
                             "semantics). Lower it when a permanently "
                             "dead env-server address should burn the "
                             "actor's reconnect budget in seconds, not "
                             "hours — what drives the --min_live_actors "
                             "floor promptly under real attrition.")
    parser.add_argument("--max_actor_reconnects", type=int, default=3,
                        help="Elastic actors: reconnect (with jittered "
                             "exponential backoff) up to N times per "
                             "actor on env-server transport failure or "
                             "a failed inference batch; the budget "
                             "refills after a full recovered unroll. "
                             "Nonzero by default — a single env-server "
                             "blip must not permanently retire an actor "
                             "(with external unsupervised servers the "
                             "backoff bounds what a truly dead address "
                             "costs). 0 = fail fast, like the "
                             "reference. App-level env errors are never "
                             "absorbed either way.")
    parser.add_argument("--min_live_actors", type=int, default=1,
                        help="Graceful degradation floor: the run "
                             "continues DEGRADED while at least this "
                             "many actor loops are alive, and "
                             "checkpoints-then-exits cleanly (health "
                             "HALTED) below it — instead of hanging on "
                             "a starved learner queue.")
    parser.add_argument("--inference_restart_budget", type=int, default=3,
                        help="How many times the inference supervisor "
                             "may rebuild a poisoned DeviceStateTable "
                             "and restart the serving threads before "
                             "the pipeline goes HALTED "
                             "(checkpoint-and-exit).")
    parser.add_argument("--chaos_plan", default=None,
                        help="Arm a deterministic fault-injection plan "
                             "(JSON, see resilience/chaos.py: seeded "
                             "FaultPlan with step/time-triggered "
                             "env-server SIGKILL, transport sever/"
                             "blackhole/delay, shm-ring corruption, "
                             "state-table poisoning, SIGTERM "
                             "preemption). Injected faults are counted "
                             "in telemetry so recovery can be asserted "
                             "exactly (scripts/chaos_run.py).")
    telemetry.add_arguments(parser)
    return parser


def _reap_servers(procs):
    """One reap implementation for every caller: polybeast_env owns it
    (the standalone CLI needs it too, without importing this module's
    jax surface)."""
    polybeast_env.reap_group(procs)


def effective_replica_refresh_updates(flags):
    """Resolved --replica_refresh_updates. An explicit value always
    wins. Under --loss impact the DEFAULT relaxes to every 10 updates
    (vs every update when a store is armed): the clipped surrogate
    absorbs the extra policy lag, so snapshot publishes — and with a
    fleet, the TAG_SNAPSHOT fanout that inherits this cadence — drop
    ~10x. V-trace keeps the tight default (0: replica tier off,
    split publishes every update)."""
    explicit = getattr(flags, "replica_refresh_updates", 0) or 0
    if explicit > 0:
        return explicit
    if getattr(flags, "loss", "vtrace") == "impact":
        return 10
    return 0


def train(flags):
    from torchbeast_tpu.parallel import initialize_distributed

    superstep_k = getattr(flags, "superstep_k", 1)
    if superstep_k < 1:
        raise ValueError(
            f"--superstep_k must be >= 1, got {superstep_k}"
        )
    # Fleet membership (ISSUE 17, fleet/): parsed BEFORE any side
    # effects. "xla" strategy (TPU/GPU) brings up jax.distributed under
    # a bounded-retry Backoff; "wire" (forced-CPU CI) composes
    # independent per-host runtimes over the control plane instead and
    # never initializes jax.distributed.
    from torchbeast_tpu.fleet import (
        FleetCoordinator,
        compose_fleet_mesh_devices,
        fleet_rendezvous,
        parse_fleet_spec,
    )
    from torchbeast_tpu.parallel.dp import fleet_strategy

    fleet = parse_fleet_spec(getattr(flags, "fleet", None))
    strategy = None
    if fleet is not None:
        if flags.coordinator_address:
            raise ValueError(
                "--fleet and --coordinator_address are exclusive: the "
                "fleet's coord= endpoint IS the rendezvous address"
            )
        strategy = fleet_strategy()
        fleet_rendezvous(fleet, strategy)
    else:
        # No-ops (with a log line) when no coordinator is configured by
        # flag or TORCHBEAST_COORDINATOR env.
        initialize_distributed(flags.coordinator_address)
    # After the rendezvous: asking for devices initialises the backend.
    log_backend(log)
    proc_count = jax.process_count()
    proc_id = jax.process_index()
    # ONE host identity for every host-scoped convention below (xpid
    # suffix, pipe namespaces, env-seed streams, acting rng): the fleet
    # rank when --fleet names one, else the jax process index. They
    # coincide under the xla strategy; the wire strategy keeps
    # proc_count == 1 while the fleet spans n_hosts runtimes.
    n_hosts = fleet.num_hosts if fleet is not None else proc_count
    host_rank = fleet.host_rank if fleet is not None else proc_id
    is_lead = host_rank == 0
    if fleet is not None and fleet.num_hosts > 1:
        if flags.xpid is None:
            raise ValueError(
                "multi-host runs need an explicit --xpid (the timestamp "
                "default would differ per host and break checkpoint "
                "resume)"
            )
        if flags.batch_size % fleet.num_hosts != 0:
            raise ValueError(
                f"--batch_size {flags.batch_size} (global) must be "
                f"divisible by the fleet's {fleet.num_hosts} hosts"
            )
        if (
            getattr(flags, "expert_parallel", 0) > 1
            or flags.sequence_parallel > 1
            or getattr(flags, "tensor_parallel", 0) > 1
            or getattr(flags, "pipeline_parallel", 0) > 1
        ):
            raise ValueError(
                "--fleet composes a data-only learner mesh; it does "
                "not compose with --expert_parallel/--sequence_"
                "parallel/--tensor_parallel/--pipeline_parallel yet"
            )
    elif proc_count > 1:
        # Multi-host topology (the reference's per-machine deployment,
        # polybeast_learner.py:436-444): every host runs its own env
        # servers + actors + inference, all hosts run the SAME number of
        # collective update steps over one global mesh, and the lead host
        # owns logging-dir conventions and checkpoints.
        if flags.xpid is None:
            raise ValueError(
                "multi-host runs need an explicit --xpid (the timestamp "
                "default would differ per host and break checkpoint "
                "resume)"
            )
        if flags.num_learner_devices <= 1:
            raise ValueError(
                "multi-host runs need --num_learner_devices > 1 (each "
                "host training single-device would silently diverge)"
            )
        if flags.num_learner_devices % proc_count != 0:
            raise ValueError(
                f"--num_learner_devices {flags.num_learner_devices} must "
                f"be divisible by the {proc_count} processes"
            )
        # --tensor_parallel composes with multi-host DP: the `model`
        # axis nests inside the cross-host data axis, so local_view
        # assembles full kernels from this host's shards for inference
        # and checkpointing (tests/test_distributed.py dp_tp mode), and
        # TP binds no mesh into the model, so acting needs no unmeshed
        # twin.
        if flags.batch_size % proc_count != 0:
            raise ValueError(
                f"--batch_size {flags.batch_size} (global) must be "
                f"divisible by the {proc_count} processes"
            )
    local_rows = flags.batch_size // n_hosts
    # Sebulba device split (ISSUE 15, runtime/placement.py): resolved —
    # and its composition rules rejected — BEFORE any side effects
    # (FileWriter dir, server spawns). None = time-shared path, incl.
    # the single-device degradation.
    from torchbeast_tpu.runtime.placement import (
        resolve_device_split,
        validate_split_composition,
    )

    fleet_learner_devices = None
    if fleet is not None and strategy == "xla":
        # xla-strategy fleet: each host resolves its OWN split over its
        # local devices, and the global learner group (host-major) is
        # what the DCN-spanning mesh compiles over.
        split, fleet_learner_devices = compose_fleet_mesh_devices(
            fleet, getattr(flags, "device_split", ""), jax.devices()
        )
    else:
        # Single-host and wire-strategy fleets: jax.devices() IS the
        # local device group (the wire strategy never initializes
        # jax.distributed), so the plain resolve is the per-host split.
        split = resolve_device_split(
            getattr(flags, "device_split", ""), jax.devices()
        )
    validate_split_composition(
        flags, split,
        parallel_flags=("expert_parallel", "sequence_parallel",
                        "pipeline_parallel", "tensor_parallel"),
    )
    if split is not None:
        if proc_count > 1 and fleet is None:
            raise ValueError(
                "--device_split with bare --coordinator_address "
                "multi-host is not supported: use --fleet host=<rank>/"
                "<n>,coord=<addr> — the fleet plane composes the split "
                "per host over DCN (fleet/topology.py)"
            )
    if getattr(flags, "admission_depth_factor", 4) < 1:
        # Pure flag predicate — rejected BEFORE any side effects, like
        # the split checks above (the serving-setup site that consumes
        # it runs after servers have spawned).
        raise ValueError(
            "--admission_depth_factor must be >= 1, got "
            f"{flags.admission_depth_factor}"
        )
    if flags.xpid is None:
        flags.xpid = "polybeast-tpu-%s" % time.strftime("%Y%m%d-%H%M%S")
    plogger = FileWriter(
        xpid=flags.xpid if is_lead else f"{flags.xpid}-host{host_rank}",
        xp_args=vars(flags), rootdir=flags.savedir,
    )
    # Telemetry (ISSUE 2): one process-wide registry every runtime
    # stage writes into; snapshots append to {xpid}/telemetry.jsonl on
    # the monitor cadence. --no_telemetry turns the global instruments
    # into no-ops.
    tele = telemetry.DriverTelemetry(
        flags, plogger.paths["telemetry"], driver="polybeast",
        annotation_factory=jax.profiler.TraceAnnotation,
        annotation_active=jax.profiler.TraceAnnotation.is_enabled,
    )
    telemetry_on = tele.enabled
    reg = tele.registry
    # Host identity on EVERY telemetry line (single-host runs stamp
    # host_rank=0 / fleet_size=1): multi-host analyses join the
    # per-host telemetry.jsonl files on these two statics.
    tele.set_static("host_rank", host_rank)
    tele.set_static("fleet_size", n_hosts)
    if fleet is not None:
        tele.set_static(
            "fleet", dict(fleet.describe(), strategy=strategy)
        )
    # Pipeline health (ISSUE 6): HEALTHY/DEGRADED/HALTED as the
    # `health.state` gauge. Actor attrition degrades the run until the
    # --min_live_actors floor; a halt (floor crossed, or the inference
    # restart budget exhausted) checkpoints and exits cleanly instead
    # of hanging on a starved learner queue.
    from torchbeast_tpu.resilience import (
        ChaosController,
        FaultPlan,
        InferenceSupervisor,
        LearnerWatchdog,
        PipelineHealth,
    )

    health = PipelineHealth(registry=reg)
    chaos = None
    if getattr(flags, "chaos_plan", None):
        chaos = ChaosController(
            FaultPlan.from_json(flags.chaos_plan), registry=reg
        )
    # Fleet control plane (fleet/coordinator.py): heartbeats + health
    # folding, the TAG_SNAPSHOT publication path, and (wire strategy)
    # the param-composition rounds. start() blocks until every host is
    # connected — BEFORE server spawns, so a host that cannot join
    # fails without leaking processes. A 1-host fleet degrades to
    # today's single-host path (no control plane to run).
    fleet_coord = None
    if fleet is not None and fleet.num_hosts > 1:
        fleet_coord = FleetCoordinator(
            fleet, health, strategy,
            min_live_hosts=getattr(flags, "min_live_hosts", 1),
            registry=reg,
        )
        fleet_coord.start()
    # All hosts resume from the LEAD's checkpoint (shared filesystem, as
    # with the reference's savedir convention).
    checkpoint_path = os.path.join(
        os.path.expanduser(flags.savedir), flags.xpid, "model.ckpt"
    )

    pipes_basename = polybeast_env.host_scoped_basename(
        flags.pipes_basename, host_rank, flags.num_servers
    )
    num_actors = flags.num_actors or flags.num_servers
    addresses = [
        polybeast_env.server_address(pipes_basename, i % flags.num_servers)
        for i in range(num_actors)
    ]

    # Any failure from the instant the server group exists until the
    # main try/finally below takes over (the settle sleep, flag
    # validation, env-spec probe, model/mesh construction) must not
    # leak the just-spawned processes — observed as orphaned
    # spawn-context children after validation-failure tests. Even a
    # KeyboardInterrupt during the settle sleep reaps them.
    server_procs = []
    server_supervisor = None
    try:
        if flags.start_servers:
            env_seed = getattr(flags, "env_seed", None)
            if env_seed is not None:
                # Per-host offset past every seed server i on one host
                # can derive (i*1000 + stream): hosts share --env_seed
                # but never a stream.
                env_seed += host_rank * flags.num_servers * 1000
            server_supervisor = polybeast_env.ServerSupervisor(
                flags, pipes_basename=pipes_basename, env_seed=env_seed,
                max_restarts=getattr(flags, "max_server_restarts", 10),
            )
            # Live list: the supervisor replaces members in place, so
            # the reap paths below always terminate the CURRENT group.
            server_procs = server_supervisor.processes
            server_supervisor.start_watch()
            if tele.ledger is not None:
                # The env stage's own CPU: the listeners and the stream
                # children they fork, as host.cpu_s.env_servers.
                tele.ledger.watch(
                    lambda: [p.pid for p in server_supervisor.processes]
                )
            if chaos is not None:
                chaos.attach_servers(server_supervisor)
            time.sleep(0.5)
        elif getattr(flags, "env_seed", None) is not None:
            log.warning(
                "--env_seed has no effect with --no_start_servers: env "
                "seeding lives in the server processes. Pass --env_seed "
                "to each external polybeast_env launch instead (use a "
                "distinct value per host; this driver cannot offset "
                "servers it did not start)."
            )

        hp = hparams_from_flags(flags)
        policy = precision_lib.resolve_flags(flags)
        num_actions, frame_shape, frame_dtype = _probe_env_via_server(
            flags, addresses[0]
        )

        # Composite (data x expert|seq) mesh: built BEFORE the model so the
        # MoE sharding constraints / attention shard_maps and the jitted
        # update step reference the SAME mesh. The inner axis is innermost —
        # its collectives stay within a data-parallel replica group.
        expert_par = getattr(flags, "expert_parallel", 0)
        seq_par = flags.sequence_parallel
        tensor_par = getattr(flags, "tensor_parallel", 0)
        if tensor_par > 1:
            if flags.model != "transformer":
                raise ValueError(
                    "--tensor_parallel needs --model transformer (the "
                    "Megatron pairing targets its projection/FFN layout)"
                )
            if seq_par > 1 or getattr(flags, "pipeline_parallel", 0) > 1:
                raise ValueError(
                    "--tensor_parallel composes with --num_learner_devices "
                    "and --expert_parallel, not with --sequence_parallel or "
                    "--pipeline_parallel (their shard_maps leave the "
                    "`model` axis unmentioned, which would force gathers of "
                    "the head-sharded projections every layer)"
                )
        pipe_par = getattr(flags, "pipeline_parallel", 0)
        learner_mesh = None
        learner_device = None
        if fleet_learner_devices is not None:
            # xla-strategy fleet: ONE mesh whose data axis runs
            # host-major over every host's learner devices — ICI within
            # a host, DCN between them. (num_hosts >= 2 makes a
            # single-device fleet group impossible.)
            from torchbeast_tpu.parallel import create_mesh

            learner_mesh = create_mesh(
                devices=list(fleet_learner_devices)
            )
        elif split is not None:
            if len(split.learner_devices) > 1:
                # The split's learner mesh: plain DP over exactly the
                # learner devices (data=N, model=1).
                from torchbeast_tpu.parallel import create_mesh

                learner_mesh = create_mesh(
                    devices=list(split.learner_devices)
                )
            else:
                # ONE learner device: plain jit pinned by explicit
                # placement (params/opt/batch committed there). A
                # 1-device mesh would pull the update through the SPMD
                # partitioner for nothing — measured ~1.7x slower per
                # update on the CPU lane, which starved the acting
                # side of the whole 2-core box.
                learner_device = split.learner_devices[0]
        elif flags.num_learner_devices > 1 or tensor_par > 1:
            from torchbeast_tpu.parallel import create_mesh

            inner = (
                max(1, expert_par) * max(1, seq_par) * max(1, tensor_par)
                * max(1, pipe_par)
            )
            learner_mesh = create_mesh(
                flags.num_learner_devices * inner,
                model_parallelism=max(1, tensor_par),
                expert_parallelism=max(1, expert_par),
                seq_parallelism=max(1, seq_par),
                pipe_parallelism=max(1, pipe_par),
            )

        model, params = init_model_and_params(
            flags, num_actions, flags.batch_size, frame_shape, frame_dtype,
            moe_mesh=learner_mesh if expert_par > 1 else None,
            seq_mesh=learner_mesh if seq_par > 1 else None,
            pipe_mesh=(
                learner_mesh
                if pipe_par > 1 and learner_mesh is not None
                else None
            ),
        )
        # The resolved remat plan rides every telemetry line as a
        # static (same convention as the acting_path block).
        from torchbeast_tpu.runtime import remat_plan as remat_plan_lib

        remat_plan = remat_plan_lib.last_plan()
        if remat_plan is not None:
            tele.set_static("learner.remat_plan", remat_plan.summary())
        # The learner mesh shape rides every telemetry line (same
        # convention as acting_path): {"data": N, "model": 1, ...} for
        # meshed learners, the 1x1 placeholder for the single-device
        # update step.
        mesh_shape = (
            {k: int(v) for k, v in learner_mesh.shape.items()}
            if learner_mesh is not None else {"data": 1, "model": 1}
        )
        if fleet is not None and strategy == "wire" and n_hosts > 1:
            # Wire-strategy fleets compose DP across hosts OUTSIDE the
            # mesh (synchronous param averaging over the control
            # plane), so the LOGICAL data width the fleet trains at is
            # per-host width x hosts — what the xla strategy's one
            # global mesh would report.
            mesh_shape["data"] *= n_hosts
        tele.set_static("learner.mesh_shape", mesh_shape)
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
        if proc_count > 1:
            # Hosts that restore different checkpoints (savedir not shared, or
            # a file visible only to the lead) would silently all-reduce
            # gradients from different params and then hang at shutdown when
            # their update counts diverge. Fail loudly at startup instead.
            from jax.experimental import multihost_utils

            sumsq = sum(
                float(np.square(np.asarray(leaf, np.float64)).sum())
                for leaf in jax.tree_util.tree_leaves(params)
            )
            fingerprint = np.asarray([float(step), sumsq], np.float64)
            gathered = multihost_utils.process_allgather(fingerprint)
            if not np.allclose(gathered, gathered[0], rtol=1e-9):
                raise RuntimeError(
                    "Hosts restored inconsistent checkpoints "
                    f"(step/param fingerprints {gathered.tolist()}); the "
                    "savedir must be a shared filesystem so every host "
                    "resumes the lead's checkpoint."
                )

        # donate="opt_only": params stay undonated (inference threads hold
        # live references), but opt_state buffers alias the new opt_state in
        # place — donation's HBM savings on the optimizer without invalidating
        # an in-flight act dispatch. Requires update dispatch and checkpoint
        # reads of opt_state to be serialized (donation_lock, below).
        mesh = learner_mesh
        if learner_mesh is not None:
            from torchbeast_tpu.parallel import (
                make_parallel_update_step,
                replicate,
                shard_batch,
            )

            data_size = int(learner_mesh.shape["data"])
            if fleet is not None and strategy == "wire":
                # The wire strategy's mesh is host-local: the rows it
                # shards per dispatch are this host's local_rows, not
                # the fleet-global batch.
                if local_rows % data_size != 0:
                    raise ValueError(
                        f"per-host batch rows {local_rows} not "
                        f"divisible by the local learner mesh's data "
                        f"axis ({data_size})"
                    )
            elif flags.batch_size % data_size != 0:
                raise ValueError(
                    f"batch_size {flags.batch_size} not divisible by "
                    f"the learner mesh's data axis ({data_size})"
                )
            # Param/opt sharding rules: EP shards the MoE expert kernels, TP
            # the attention/dense-FFN leaves — disjoint sets, merged onto
            # one tree when both are active. optax state mirrors the params
            # leaf-wise (same key paths at the leaves), so each rule applies
            # to it unchanged. Explicit placement is REQUIRED: opt_state is
            # donated, and donation needs input placement == output sharding.
            rules = []
            if expert_par > 1:
                from torchbeast_tpu.parallel import expert_param_shardings

                rules.append(expert_param_shardings)
            if tensor_par > 1:
                from torchbeast_tpu.parallel import transformer_tp_shardings

                rules.append(transformer_tp_shardings)
            if rules and (
                policy.param_dtype == "bf16"
                or getattr(flags, "factored_opt_state", False)
            ):
                # EP/TP opt shardings map leaf-wise rules over
                # opt_state, which must mirror params; the bf16-resident
                # master wrapper and the factored second moment both
                # change the state tree (parallel/dp.py documents the
                # constraint).
                raise RuntimeError(
                    "--precision bf16_train / --factored_opt_state do "
                    "not compose with --expert_parallel/--tensor_"
                    "parallel yet (optimizer-state sharding rules need "
                    "a params-mirroring state tree)"
                )
            param_shardings = opt_shardings = None
            if rules:
                from torchbeast_tpu.parallel import merge_param_shardings

                param_shardings = merge_param_shardings(
                    *(rule(mesh, params) for rule in rules)
                )
                opt_shardings = merge_param_shardings(
                    *(rule(mesh, opt_state) for rule in rules)
                )
            update_step = make_parallel_update_step(
                model, optimizer, hp, mesh, donate="opt_only",
                param_shardings=param_shardings,
                opt_shardings=opt_shardings,
                superstep_k=superstep_k,
                donate_batch=superstep_k > 1,
            )
            if param_shardings is None:
                params = replicate(mesh, params)
                opt_state = replicate(mesh, opt_state)
            else:
                params = jax.tree_util.tree_map(
                    jax.device_put, params, param_shardings
                )
                opt_state = jax.tree_util.tree_map(
                    jax.device_put, opt_state, opt_shardings
                )
            shard = lambda b, s: shard_batch(  # noqa: E731
                mesh, b, s,
                leading_axes=1 if superstep_k > 1 else 0,
            )
            inner_desc = (
                (f" x model={tensor_par}" if tensor_par > 1 else "")
                + (f" x expert={expert_par}" if expert_par > 1 else "")
                + (f" x seq={seq_par}" if seq_par > 1 else "")
            )
            log.info(
                "Parallel learner: data=%d%s (%d chips total, %d processes)",
                data_size, inner_desc,
                len(learner_mesh.devices.flat), proc_count,
            )
        else:
            if learner_device is not None:
                # Pin the whole update chain to the split's learner
                # device: committed params/opt here, committed batches
                # in _place below — the jit executes where its inputs
                # live, no mesh machinery needed.
                params = jax.device_put(params, learner_device)
                opt_state = jax.device_put(opt_state, learner_device)
            if superstep_k > 1:
                # One dispatch = K scanned updates; the staged arena
                # stack is consumed exactly once (consume-once deletion,
                # learner.consume_staged_inputs).
                update_step = learner_lib.make_update_superstep(
                    model, optimizer, hp, superstep_k,
                    donate="opt_only", donate_batch=True,
                )
            else:
                update_step = learner_lib.make_update_step(
                    model, optimizer, hp, donate="opt_only"
                )
            shard = None
        if telemetry_on:
            # Dispatch latency + batch transfer bytes per update
            # (counts K updates per superstep dispatch).
            update_step = learner_lib.instrument_update_step(
                update_step, superstep_k=superstep_k
            )
        count_host_sync = getattr(
            update_step, "count_host_sync", lambda: None
        )
        if superstep_k > 1:
            log.info(
                "Learner supersteps: %d updates per dispatch "
                "(K-batch arena staging)", superstep_k,
            )
        act_model = model
        if proc_count > 1 and (
            expert_par > 1 or seq_par > 1 or pipe_par > 1
        ):
            # The learner model's MoE constraints / attention shard_maps
            # reference the GLOBAL mesh; a host-local inference jit cannot
            # touch non-addressable devices. Acting uses an unmeshed twin —
            # identical flags and param tree, no mesh bindings (meshes only
            # select compute paths, never parameters).
            act_model, _ = init_model_and_params(
                flags, num_actions, flags.batch_size, frame_shape,
                frame_dtype, unmeshed=True, init_params=False,
            )
        act_step = learner_lib.make_act_step(act_model)

        infer_device = jax.local_devices()[0]

        def local_view(tree, device=None):
            """Host-local full-value view of a global pytree. Multi-host
            inference and checkpointing must not hand jit/np a global array
            spanning non-addressable devices, so:

            - replicated leaves: this host's replica, zero-copy
              (addressable_data shares the device buffer);
            - leaves sharded over an INNER mesh axis (expert/model — the
              mesh nests those inside the cross-host data axis, so every
              shard index is present on this host's local devices): the
              full value is assembled from addressable shards, no
              cross-process communication (this must stay collective-free:
              checkpointing calls it on the lead host only).

            `device`: placement for assembled leaves — the inference rebind
            passes the local device (one H2D per rebind instead of one per
            act call); the checkpoint path leaves them on host (the
            serializer would only copy them straight back).
            """
            if proc_count == 1:
                return tree

            def view(a):
                if a.sharding.is_fully_replicated:
                    return a.addressable_data(0)
                out = np.empty(a.shape, a.dtype)
                covered = 0
                seen = set()
                for sh in a.addressable_shards:
                    key = str(sh.index)
                    if key in seen:  # data-axis replicas repeat the index
                        continue
                    seen.add(key)
                    piece = np.asarray(sh.data)
                    out[sh.index] = piece
                    covered += piece.size
                if covered != a.size:
                    raise ValueError(
                        "local_view: leaf sharded ACROSS processes "
                        f"(host covers {covered}/{a.size} elements); inner "
                        "parallel axes must nest inside the data axis "
                        "(parallel/mesh.py) for host-local inference and "
                        "checkpointing"
                    )
                return jax.device_put(out, device) if device is not None else out

            return jax.tree_util.tree_map(view, tree)

        # Shared mutable state: the learner rebinds these; inference reads them.
        state = {
            "params": params,
            "infer_params": local_view(params, device=infer_device),
            "opt_state": opt_state,
            "step": step,
            # Frames consumed by updates: env frames x --replay_reuse
            # in steady state (resume: the exact split isn't persisted,
            # so seed with the steady-state estimate).
            "learn_step": step * max(1, hp.replay_reuse),
            "stats": dict(stats),
            "rng": jax.random.PRNGKey(flags.seed + host_rank),
            "done": False,
        }
        state_lock = threading.Lock()
        # Serializes update-step dispatch (which invalidates donated opt_state
        # buffers) against checkpoint reads of opt_state. Deliberately separate
        # from state_lock so the inference hot path never waits on a dispatch.
        donation_lock = threading.Lock()

        # IMPACT target network (--loss impact): full-precision params
        # stamped every --target_refresh_updates updates ride the same
        # versioned store class as serving snapshots, under the
        # "learner.target" namespace (its cadence never folds into the
        # serving counters). cast_bf16=False: the target forward must
        # equal a forward of the exact stamped params.
        target_store = None
        target_forward = None
        if hp.loss == "impact":
            from torchbeast_tpu.serving import PolicySnapshotStore

            target_store = PolicySnapshotStore(
                max(1, getattr(flags, "target_refresh_updates", 8) or 1),
                registry=reg,
                namespace="learner.target",
                cast_bf16=False,
            )
            # v0 before any update: the first batches train against the
            # init params (ratio == 1, the V-trace-equivalent point).
            target_store.publish(0, params)
            target_forward = learner_lib.make_target_forward(
                model, superstep_k=superstep_k
            )
            log.info(
                "IMPACT loss: target network refresh every %d updates, "
                "replay reuse %d",
                target_store.refresh_updates, max(1, hp.replay_reuse),
            )

        # Native-first runtime (ISSUE 14 / ROADMAP item 1): the C++
        # pool by default; an absent or stale _tbt_core falls back to
        # the Python pool with the reason logged — unless the user
        # EXPLICITLY asked for native, which must stay a hard error
        # (silently downgrading an explicit benchmark request would
        # publish Python-pool numbers as native ones).
        native_pref = flags.native_runtime  # None=auto, True/False=forced
        use_native = native_pref is not False
        if use_native:
            from torchbeast_tpu.runtime.native import (
                gap_reason,
                import_native,
            )

            reason = gap_reason()
            if reason is None:
                queue_mod = import_native()
                log.info("Using native (C++) runtime")
            elif native_pref is True:
                raise RuntimeError(
                    f"--native_runtime requested but {reason}"
                )
            else:
                use_native = False
                log.warning(
                    "Native runtime unavailable (%s); falling back to "
                    "the Python pool", reason,
                )
        if not use_native:
            import torchbeast_tpu.runtime as queue_mod

        # Admission control + deadline-aware load shedding on the
        # central inference path (ISSUE 14, serving/admission.py):
        # armed by --request_deadline_ms. The depth bound is
        # --admission_depth_factor x the max batch (default 4) — deep
        # enough that the consumer's formation pipeline never starves,
        # shallow enough that queueing past it only manufactures
        # deadline expiries.
        deadline_ms = getattr(flags, "request_deadline_ms", 0.0) or 0.0
        depth_factor = getattr(flags, "admission_depth_factor", 4)
        shed_depth = (
            depth_factor * flags.max_inference_batch_size
            if deadline_ms > 0 else None
        )
        slo_target_s = deadline_ms / 1000.0 if deadline_ms > 0 else None
        admission = None
        if deadline_ms > 0 and not use_native:
            from torchbeast_tpu.serving import AdmissionController

            admission = AdmissionController(
                deadline_ms=deadline_ms, max_queue_depth=shed_depth,
                registry=reg,
            )

        # Each host's queue batches its LOCAL rows; shard_batch assembles the
        # global array across hosts (local_rows == batch_size single-host).
        # telemetry_name wires depth/batch-size/wait series — Python
        # runtime only (the C++ classes don't take the kwarg; their
        # depths still land in the monitor-loop gauges below).
        queue_tm = (
            {} if use_native
            else {"telemetry_name": "learner_queue"}
        )
        if use_native:
            # The C++ batcher gates admission in-process (actor threads
            # never touch Python on a shed); counters fold back into
            # the serving.* series each monitor tick. Continuous
            # batching (ISSUE 16) rolls admitted late arrivals into the
            # forming dispatch window; --admission_depth_factor stays
            # armed as the fallback hard bound.
            batcher_tm = {
                "continuous": getattr(flags, "continuous_batching", True),
            }
            if deadline_ms > 0:
                batcher_tm.update({
                    "request_deadline_ms": deadline_ms,
                    "shed_max_queue_depth": shed_depth,
                    "slo_target_ms": deadline_ms,
                })
        else:
            batcher_tm = {
                "telemetry_name": "inference", "admission": admission,
            }
        learner_queue = queue_mod.BatchingQueue(
            batch_dim=1,
            minimum_batch_size=local_rows,
            maximum_batch_size=local_rows,
            maximum_queue_size=flags.max_learner_queue_size or local_rows,
            check_inputs=True,
            **queue_tm,
        )
        # Split mode has no CENTRAL batcher: each inference slice owns
        # one (parallel/sebulba.py, built below once the model exists);
        # the router presents the batcher-shaped surface to the pool.
        inference_batcher = None
        if split is None:
            inference_batcher = queue_mod.DynamicBatcher(
                batch_dim=1,
                minimum_batch_size=1,
                maximum_batch_size=flags.max_inference_batch_size,
                timeout_ms=flags.inference_timeout_ms,
                **batcher_tm,
            )

        # The model's acting inputs (a subset of the actor traffic's
        # _ENV_KEYS nest) — ONE definition for the central act path,
        # the state table's filter/act, and the replica act path.
        _MODEL_KEYS = ("frame", "reward", "done", "last_action")

        def _act_with(params_now, key, env_outputs, agent_state):
            """One legacy-path forward with explicit params/rng: the
            central act_fn and the replica act path differ ONLY in
            where (params, key) come from."""
            # act_step consumes [B, ...] (adds T=1 itself); inputs are [1, B].
            model_inputs = {k: env_outputs[k][0] for k in _MODEL_KEYS}
            out, new_state = act_step(params_now, key, model_inputs, agent_state)
            out = {
                "action": np.asarray(out.action)[None],
                "policy_logits": np.asarray(out.policy_logits)[None],
                "baseline": np.asarray(out.baseline)[None],
            }
            return out, new_state

        def act_fn(env_outputs, agent_state, batch_size):
            """Bucket-static jitted forward. Called CONCURRENTLY from every
            inference thread (no global lock — see the measurement note at
            the thread setup): any shared state touched here must stay under
            state_lock."""
            with state_lock:
                params_now = state["infer_params"]
                state["rng"], key = jax.random.split(state["rng"])
            return _act_with(params_now, key, env_outputs, agent_state)

        # Device-resident agent-state table (runtime/state_table.py):
        # recurrent state lives in a [.., num_actors+1, ..] on-device
        # pytree keyed by actor slot; the jitted acting step gathers,
        # advances, and scatters it in ONE dispatch, so per-env-step
        # host traffic shrinks to obs-down / action-up. Both runtimes
        # speak the slot framing (the C++ pool drives the same table
        # through its slot hooks, pymodule.cc); stateless models have
        # nothing to keep resident and fall back.
        state_table = None
        stateful_acting = getattr(
            flags, "device_agent_state", True
        ) and bool(jax.tree_util.tree_leaves(act_model.initial_state(1)))
        if stateful_acting:

            def _table_ctx():
                # Params only: the table owns the acting rng key and
                # splits it inside its jitted step.
                with state_lock:
                    return state["infer_params"]

            def _table_act(ctx, env_outputs, agent_state):
                params_now, key = ctx  # (context, the batch's subkey)
                # act_body consumes [B, ...] (adds T=1 itself); batcher
                # nests are [1, B, ...]; reply framing restores [1, B].
                model_inputs = {
                    k: env_outputs[k][0] for k in _MODEL_KEYS
                }
                out, new_state = learner_lib.act_body(
                    act_model, params_now, key, model_inputs, agent_state
                )
                outputs = {
                    "action": out.action[None],
                    "policy_logits": out.policy_logits[None],
                    "baseline": out.baseline[None],
                }
                return outputs, new_state

            # Host-side subset to the model's inputs BEFORE the
            # launch: actor traffic carries the full _ENV_KEYS
            # nest (episode_step/episode_return included), which the
            # model never reads — without the filter those leaves
            # transfer every dispatch AND the 4-key prewarm dummy
            # compiles a signature real 6-key traffic misses.
            def _table_filter(env):
                return {k: env[k] for k in _MODEL_KEYS}

        if stateful_acting and split is None:
            from torchbeast_tpu.runtime.state_table import DeviceStateTable

            state_table = DeviceStateTable(
                act_model.initial_state(1),
                num_slots=num_actors,
                act_fn=_table_act,
                context_fn=_table_ctx,
                batch_dim=1,
                input_filter=_table_filter,
                rng_key=state["rng"],
            )

        # The chaos learner_stall gate (shared-chip overload model):
        # consulted by the learner's dispatch site and every serving
        # loop's per-batch site; None when chaos is unarmed. Defined
        # before serving construction — slice loops bind it then.
        throttle = chaos.throttle if chaos is not None else None

        # Sebulba split serving (ISSUE 15, parallel/sebulba.py): one
        # batcher + pinned DeviceStateTable + serving loop per
        # inference slice, all answering from versioned snapshots the
        # learner publishes device-to-device through the
        # PolicySnapshotStore (--replica_refresh_updates sets the
        # cadence; default: every update). The ShardedStateTables view
        # drops into every single-table consumer (pool, supervisor,
        # chaos) unchanged.
        sebulba = None
        snapshot_store = None
        native_slice_router = None
        refresh_updates = effective_replica_refresh_updates(flags)
        if split is not None:
            from torchbeast_tpu.parallel.sebulba import (
                build_sebulba_serving,
            )
            from torchbeast_tpu.serving import PolicySnapshotStore

            snapshot_store = PolicySnapshotStore(
                max(1, refresh_updates), registry=reg
            )
            # Version 0 = the initial params, published before serving
            # starts so no slice is ever empty-handed.
            snapshot_store.note_update(0)
            snapshot_store.publish(0, state["infer_params"])

            def _split_legacy_act(env_outputs, agent_state, batch_size,
                                  ctx):
                params_now, key = ctx
                return _act_with(params_now, key, env_outputs,
                                 agent_state)

            # Native serving plane (ISSUE 16): each slice gets a C++
            # DynamicBatcher (admission + continuous batching gated
            # in-process) so the pool's C++ SliceRouter fans out
            # GIL-free; the Python serving loops, hooks, and pinned
            # state tables built by build_sebulba_serving are
            # unchanged.
            native_slice_factory = None
            if use_native:
                def native_slice_factory(i, name):
                    return queue_mod.DynamicBatcher(
                        batch_dim=1,
                        minimum_batch_size=1,
                        maximum_batch_size=(
                            flags.max_inference_batch_size
                        ),
                        timeout_ms=flags.inference_timeout_ms,
                        **batcher_tm,
                    )

            sebulba = build_sebulba_serving(
                split,
                snapshot_store,
                num_slots=num_actors,
                max_batch_size=flags.max_inference_batch_size,
                timeout_ms=flags.inference_timeout_ms,
                max_policy_lag=flags.max_policy_lag,
                rng_seed=flags.seed,
                initial_state=(
                    act_model.initial_state(1) if stateful_acting
                    else None
                ),
                table_act_fn=_table_act if stateful_acting else None,
                legacy_act_fn=(
                    None if stateful_acting else _split_legacy_act
                ),
                input_filter=(
                    _table_filter if stateful_acting else None
                ),
                health=health,
                registry=reg,
                admission=admission,
                throttle_fn=throttle,
                batcher_factory=native_slice_factory,
            )
            state_table = sebulba.state_tables
            if use_native:
                # The C++ router the pool serves through: slot-hash
                # fan-out over the slices' native batchers, bit-
                # identical to the Python SliceRouter's assignment
                # (splitmix64, pinned by beastlint ROUTE-PARITY).
                native_slice_router = queue_mod.SliceRouter(
                    slices=[s.batcher for s in sebulba.stacks]
                )
                if telemetry_on:
                    # Per-request serving_ok() pokes live in the Python
                    # router; on the native path the monitor tick
                    # drives each slice's keyed lag degrade/recover
                    # transitions instead.
                    def _slice_health_tick():
                        for _stack in sebulba.stacks:
                            if _stack.hooks is not None:
                                _stack.hooks.serving_ok()

                    tele.add_tick_callback(_slice_health_tick)
            tele.set_static("device_split", split.describe())
            if telemetry_on:
                tele.add_tick_callback(sebulba.gauge_tick(reg))
            log.info(
                "Sebulba serving: %d slice(s), snapshot refresh every "
                "%d update(s), max policy lag %d (%s routing)",
                split.n_slices, max(1, refresh_updates),
                flags.max_policy_lag,
                "native" if use_native else "python",
            )

        if chaos is not None:
            chaos.attach_state_table(state_table)

            def _chaos_step():
                with state_lock:
                    return state["step"]

            chaos.set_step_fn(_chaos_step)

        # Per-env-step wire accounting for the acting path. Exported as
        # telemetry gauges + a static `acting_path` block on every
        # telemetry.jsonl line (a reader takes the structured snapshot,
        # not scraped logs; the cumulative actual traffic is the actor
        # pool's wire.bytes_up/down counters). The
        # state table's whole point is making the state term vanish
        # from both directions.
        env_up = (
            int(np.prod(frame_shape)) * np.dtype(frame_dtype).itemsize
            + 4 + 1 + 4 + 4 + 4  # reward, done, episode_step/return, last_action
        )
        state_bytes = sum(
            int(np.asarray(leaf).nbytes)
            for leaf in jax.tree_util.tree_leaves(act_model.initial_state(1))
        )
        out_down = 4 + 4 * num_actions + 4  # action, logits, baseline
        if state_table is not None:
            bytes_up, bytes_down = env_up + 4 + 1, out_down
        else:
            bytes_up = env_up + state_bytes
            bytes_down = out_down + state_bytes
        acting_mode = "device_table" if state_table is not None else "host"
        reg.gauge("acting.bytes_per_step_up").set(bytes_up)
        reg.gauge("acting.bytes_per_step_down").set(bytes_down)
        tele.set_static("acting_path", {
            "agent_state": acting_mode,
            "bytes_per_step_up": bytes_up,
            "bytes_per_step_down": bytes_down,
        })
        log.info("Acting path: agent_state=%s", acting_mode)

        # No global inference lock (unlike reference polybeast_learner.py:269):
        # act_fn is a pure jitted call whose shared state access is already
        # synchronized, so concurrent threads overlap their host-side pad/
        # dispatch/device-sync work. Measured on 32 actors x 2 threads:
        # +27% steps/s (python runtime) / +18% (native), p99 latency -20-35%
        # (benchmarks/artifacts/inference_lock_decision.md).
        if flags.prewarm_inference:
            t0 = time.time()
            buckets = default_buckets(flags.max_inference_batch_size)
            for b in buckets:
                dummy_env = dummy_env_outputs(1, b, frame_shape, frame_dtype)
                if sebulba is not None:
                    # Per-slice prewarm with a REAL snapshot ctx (ctx
                    # leaves are traced, so live batches hit the same
                    # compiled signature). The stateless path compiles
                    # per slice device too — the jit cache is keyed by
                    # the ctx params' device.
                    for stack in sebulba.stacks:
                        ctx, _ = stack.hooks.begin_batch()
                        if stack.state_table is not None:
                            stack.state_table.step(
                                np.full(
                                    b, stack.state_table.trash_slot,
                                    np.int32,
                                ),
                                np.zeros(b, bool),
                                dummy_env,
                                context=ctx,
                            )
                        else:
                            dummy_state = jax.tree_util.tree_map(
                                np.asarray, act_model.initial_state(b)
                            )
                            _split_legacy_act(
                                dummy_env, dummy_state, b,
                                (ctx, stack.hooks.next_key()),
                            )
                elif state_table is not None:
                    # Compile the table step per bucket: all-trash slots,
                    # advance=False — no real slot is disturbed.
                    state_table.step(
                        np.full(b, state_table.trash_slot, np.int32),
                        np.zeros(b, bool),
                        dummy_env,
                    )
                else:
                    dummy_state = jax.tree_util.tree_map(
                        np.asarray, act_model.initial_state(b)
                    )
                    act_fn(dummy_env, dummy_state, b)
            log.info(
                "Prewarmed %d inference buckets in %.1fs",
                len(buckets), time.time() - t0,
            )

        # Snapshotted policy replicas (ISSUE 14, serving/): the learner
        # publishes versioned bf16 snapshots every
        # --replica_refresh_updates; replica serving threads answer
        # acting requests from them through the SAME state table (ctx
        # override — state continuity is routing-independent), stamping
        # the true policy_lag into each reply. Lag beyond
        # --max_policy_lag degrades the replica back to the central
        # path via the health machine. Python runtime only: the router
        # sits in the Python pool's request path.
        replica_parts = None
        if split is not None:
            # The slices ARE snapshot serving under the split;
            # --replica_refresh_updates already set the publish cadence
            # above, so a separate replica tier would be redundant.
            pass
        elif refresh_updates > 0:
            from torchbeast_tpu.serving import (
                PolicySnapshotStore,
                ReplicaRouter,
                ReplicaServingHooks,
            )

            snapshot_store = PolicySnapshotStore(
                refresh_updates, registry=reg
            )  # the learner loop publishes into whichever store exists
            # Version 0 = the initial params, published before serving
            # starts so the replica path is never empty-handed.
            snapshot_store.note_update(0)
            snapshot_store.publish(0, state["infer_params"])
            replica_hooks = ReplicaServingHooks(
                snapshot_store,
                max_policy_lag=flags.max_policy_lag,
                rng_seed=flags.seed + 7919 * (host_rank + 1),
                health=health,
                batch_dim=1,
                registry=reg,
            )
            loop_hooks = replica_hooks
            if use_native:
                # Native replica routing (ISSUE 16): the C++
                # ReplicaRouter answers replica-first with central
                # fallback, gated by an atomic flag the Python hooks
                # PUSH (per served batch + per monitor tick) instead
                # of a GIL round-trip per request. Degradation flips
                # routing at batch granularity; recovery rides the
                # monitor tick — a degraded replica sees no batches,
                # so only the tick can re-arm it.
                replica_batcher = queue_mod.DynamicBatcher(
                    batch_dim=1,
                    minimum_batch_size=1,
                    maximum_batch_size=flags.max_inference_batch_size,
                    timeout_ms=flags.inference_timeout_ms,
                    **batcher_tm,
                )
                native_replica_router = queue_mod.ReplicaRouter(
                    central=inference_batcher, replica=replica_batcher,
                )
                native_replica_router.set_serving(
                    replica_hooks.serving_ok()
                )
                if telemetry_on:
                    tele.add_tick_callback(
                        lambda: native_replica_router.set_serving(
                            replica_hooks.serving_ok()
                        )
                    )

                class _FlagSyncHooks:
                    """The replica serving loop's hook twin: every
                    begin_batch refreshes the router's serving flag
                    before picking the snapshot ctx, keeping the C++
                    routing decision one batch behind the lag budget
                    at most."""

                    def __init__(self, hooks, router):
                        self._hooks = hooks
                        self._router = router

                    def begin_batch(self):
                        self._router.set_serving(
                            self._hooks.serving_ok()
                        )
                        return self._hooks.begin_batch()

                    def next_key(self):
                        return self._hooks.next_key()

                loop_hooks = _FlagSyncHooks(
                    replica_hooks, native_replica_router
                )
                replica_router = native_replica_router
            else:
                replica_batcher = DynamicBatcher(
                    batch_dim=1,
                    minimum_batch_size=1,
                    maximum_batch_size=flags.max_inference_batch_size,
                    timeout_ms=flags.inference_timeout_ms,
                    telemetry_name="replica",
                    admission=admission,
                )
                replica_router = ReplicaRouter(
                    inference_batcher, replica_batcher, replica_hooks,
                    registry=reg,
                )
            replica_parts = {
                "store": snapshot_store,
                "hooks": replica_hooks,
                "batcher": replica_batcher,
                "router": replica_router,
            }

            def _replica_act_fn(env_outputs, agent_state, batch_size, ctx):
                """Legacy-path replica forward: the central act body
                with the hook-provided (snapshot params, key) instead
                of the live ones (stateless models only — the
                state-table path feeds ctx through the table step)."""
                params_now, key = ctx
                return _act_with(params_now, key, env_outputs, agent_state)

            def _replica_loop():
                inference_loop(
                    replica_batcher,
                    None if state_table is not None else _replica_act_fn,
                    flags.max_inference_batch_size,
                    lock=None,
                    state_table=state_table,
                    serving_hooks=loop_hooks,
                    throttle_fn=throttle,
                    telemetry_prefix="replica",
                )

            log.info(
                "Replica serving armed: refresh every %d updates, "
                "max policy lag %d (%s routing)",
                refresh_updates, flags.max_policy_lag,
                "native" if use_native else "python",
            )

        # Supervised serving threads (ISSUE 6): a poisoned state table
        # no longer ends the run — the supervisor rebuilds it from
        # initial state and restarts the thread, up to
        # --inference_restart_budget times; exhaustion goes HALTED
        # (checkpoint-and-exit below) instead of wedging the actors.
        # Replica/slice loops ride the SAME supervisor: they share the
        # (sharded) state table, so poison recovery must rebuild once
        # and restart every serving thread under one budget.
        if sebulba is not None:
            # --num_inference_threads serving loops (launcher/replier
            # pairs) PER SLICE: each slice's loops drain only that
            # slice's batcher, so the pinned dispatch story is
            # unchanged.
            slice_loops = [
                loop
                for loop in sebulba.loop_fns
                for _ in range(max(1, flags.num_inference_threads))
            ]
            infer_supervisor = InferenceSupervisor(
                slice_loops[0],
                num_threads=1,
                state_table=state_table,
                restart_budget=getattr(
                    flags, "inference_restart_budget", 3
                ),
                health=health,
                registry=reg,
                extra_loop_fns=slice_loops[1:],
            )
        else:
            def _serve_loop():
                inference_loop(
                    inference_batcher,
                    act_fn,
                    flags.max_inference_batch_size,
                    lock=None,
                    state_table=state_table,
                    throttle_fn=throttle,
                )

            infer_supervisor = InferenceSupervisor(
                _serve_loop,
                num_threads=flags.num_inference_threads,
                state_table=state_table,
                restart_budget=getattr(
                    flags, "inference_restart_budget", 3
                ),
                health=health,
                registry=reg,
                extra_loop_fns=(
                    [_replica_loop] if replica_parts is not None else None
                ),
            )

        # The batcher-shaped surface the pool (and the monitor's depth
        # series) talks to: the slice router under the split (the C++
        # one when the native pool serves — same slot hash, zero GIL),
        # the replica router when replicas are armed, else the central
        # batcher itself.
        if sebulba is not None:
            serving_frontend = (
                native_slice_router if native_slice_router is not None
                else sebulba.router
            )
        elif replica_parts is not None:
            serving_frontend = replica_parts["router"]
        else:
            serving_frontend = inference_batcher
        # Monitor depth series: the central batcher where one exists
        # (replica mode keeps its historical central-only semantics);
        # the router's summed slice depths under the split.
        serving_depth_fn = (
            inference_batcher.size if inference_batcher is not None
            else serving_frontend.size
        )

        pool_cls = queue_mod.ActorPool if use_native else ActorPool
        pool_kwargs = {"max_frame_bytes": flags.max_frame_bytes}
        if state_table is not None:
            pool_kwargs["state_table"] = state_table
        if not use_native:
            # SLO breach accounting lives actor-side in the Python
            # pool (the C++ pool counts breaches batcher-side and
            # retries sheds in its own loops).
            pool_kwargs["slo_target_s"] = slo_target_s
        if replica_parts is not None or sebulba is not None:
            # Both pools normalize a missing policy_lag leaf to zeros
            # when lag-stamped serving is armed, so rollouts mixing
            # replica/slice and central replies stay well-formed.
            pool_kwargs["record_policy_lag"] = True
        # Chaos interposition (ISSUE 6/12) on EITHER runtime: the Python
        # pool wraps each fresh transport in a FaultingTransport; the
        # C++ pool builds its FaultHooks (csrc/chaos.h) and the
        # controller drives them through the pool's chaos_* methods.
        if chaos is not None:
            if use_native:
                pool_kwargs["fault_hooks"] = True
            else:
                pool_kwargs["transport_wrap"] = chaos.wrap_transport
        actors = pool_cls(
            unroll_length=flags.unroll_length,
            learner_queue=learner_queue,
            inference_batcher=serving_frontend,
            env_server_addresses=addresses,
            initial_agent_state=model.initial_state(1),
            max_reconnects=flags.max_actor_reconnects,
            connect_timeout_s=flags.actor_connect_timeout_s,
            **pool_kwargs,
        )
        if chaos is not None and use_native:
            chaos.attach_native_pool(actors)
        if use_native and telemetry_on:
            # The C++ core has no registry access; fold its per-request
            # stage stamps + wire/step counters into the same series the
            # Python runtime writes, on every exported line.
            from torchbeast_tpu.runtime.native import NativeTelemetryFolder

            folder_kwargs = {}
            if native_slice_router is not None:
                # Per-slice fold (ISSUE 16): slice batcher admission
                # counters aggregate into serving.*, slice depths +
                # routed counts land on the same inference.slice.<i>.*
                # series the Python router/gauge-tick publish.
                folder_kwargs.update(
                    slice_batchers=[s.batcher for s in sebulba.stacks],
                    slice_router=native_slice_router,
                )
            if replica_parts is not None:
                folder_kwargs.update(
                    replica_batcher=replica_parts["batcher"],
                    replica_router=replica_parts["router"],
                )
            if fleet_coord is not None:
                # Remote hosts' heartbeat gauges land as
                # host<r>.inference.slice.<i>.* on this host's lines
                # (only the lead receives heartbeats; the fold no-ops
                # elsewhere).
                folder_kwargs.update(fleet=fleet_coord)
            folder = NativeTelemetryFolder(
                reg, pool=actors, batcher=inference_batcher,
                queue=learner_queue, slo_target_s=slo_target_s,
                ledger=tele.ledger, **folder_kwargs,
            )
            tele.add_tick_callback(functools.partial(
                folder.tick,
                ledger_min_interval_s=telemetry.LEDGER_PERIOD_S,
            ))
        elif fleet_coord is not None and telemetry_on:
            # Python runtime: the folder runs for the fleet fold alone
            # (every native source None).
            from torchbeast_tpu.runtime.native import NativeTelemetryFolder

            tele.add_tick_callback(
                NativeTelemetryFolder(reg, fleet=fleet_coord).tick
            )
        actor_thread = threading.Thread(
            target=actors.run, daemon=True, name="actorpool"
        )

        # Learner stall watchdog: the learner loop pings per dispatch;
        # silence past the deadline -> DEGRADED + a thread-stack dump
        # with pipeline occupancy, so "where is it stuck" is in the log
        # before anyone has to attach a debugger.
        def _stall_diagnostics():
            return {
                "learner_queue": learner_queue.size(),
                "inference_batcher": serving_depth_fn(),
                "live_actors": getattr(
                    actors, "live_actors", lambda: -1
                )(),
            }

        watchdog = LearnerWatchdog(
            getattr(flags, "learner_stall_timeout_s", 300.0),
            health=health,
            dump_fn=_stall_diagnostics,
            registry=reg,
        )

        if fleet_coord is not None:
            if not is_lead and snapshot_store is not None:
                # Remote stores consume the lead's TAG_SNAPSHOT stream
                # (applied on the coordinator's reader thread); the
                # local params pin the pytree structure the wire's
                # flattened leaves rebuild against.
                fleet_coord.attach_snapshot_store(
                    snapshot_store, state["infer_params"]
                )
            from torchbeast_tpu.parallel.sebulba import (
                slice_gauge_snapshot,
            )

            def _fleet_stats():
                # Heartbeat recovery counters: what the lead folds into
                # the fleet verdict (a supervised env-server restart or
                # actor reconnect on THIS host becomes a sticky
                # fleet.host<r> mark on the lead).
                with state_lock:
                    at_step = state["step"]
                reconnect_fn = getattr(actors, "reconnect_count", None)
                return {
                    "updates": int(at_step),
                    "restarts": int(
                        server_supervisor.restarts
                        if server_supervisor is not None else 0
                    ),
                    "reconnects": int(
                        reconnect_fn() if reconnect_fn is not None
                        else 0
                    ),
                }

            fleet_coord.set_stats_source(_fleet_stats)
            fleet_coord.set_gauges_source(
                lambda: slice_gauge_snapshot(reg)
            )

        if telemetry_on:
            # Per-connection SLO block (ISSUE 14 satellite) on EVERY
            # telemetry line: the p99 of actor.request_rtt_s against
            # the same target the shed gate's deadline uses, plus the
            # breach count — dashboards and the admission gate read
            # one number.
            h_rtt = reg.histogram("actor.request_rtt_s")
            c_breach = reg.counter("slo.rtt_breaches")

            def _slo_tick():
                tele.set_static("slo", {
                    "target_s": slo_target_s,
                    "p99_s": round(h_rtt.percentile(0.99), 6),
                    "breaches": int(c_breach.value()),
                })

            tele.add_tick_callback(_slo_tick)

        # Stage latencies (dequeue/learn) become learner.* histograms
        # in the snapshot and pb: spans on the profiler's clock; with
        # telemetry off, a private registry and tracer keep the 5s log
        # line working unchanged. Inside "learn" the learner thread's
        # stages are spans of their own: the update dispatch
        # (learner.instrument_update_step), publish and stats_fetch.
        tracer = telemetry.get_tracer()
        timings = Timings(
            registry=reg if telemetry_on else None, prefix="learner.",
            tracer=tracer if telemetry_on else None,
        )
        sp_dequeue = timings.section("dequeue")
        sp_learn = timings.section("learn")
        sp_publish = tracer.span("learner.publish")
        sp_stats_fetch = tracer.span("learner.stats_fetch")

        # Host->HBM prefetch (SURVEY §7 hard part #3): the double-buffered
        # staging thread between the learner queue and the learner thread
        # (runtime/queues.DevicePrefetcher). device_put (and the DP shard
        # placement) is async, so by the time the learner pulls an item its
        # transfer is already riding behind the previous update's compute
        # instead of stalling dispatch; a consumed batch's buffers free
        # when its update's last use drops the reference (no donation —
        # update_body has no batch-shaped outputs to alias, see
        # learner.donate_argnums_for).
        def _place(item):
            # Precision staging cast (bf16_train): float32 leaves go
            # half-width BEFORE the transfer. Under supersteps the
            # arena already staged bf16 columns (cast_batch is then a
            # no-op); the K=1 path casts here.
            batch = precision_lib.cast_batch(
                item["batch"], policy.batch_dtype
            )
            initial_agent_state = precision_lib.cast_batch(
                item["initial_agent_state"], policy.batch_dtype
            )
            if arena is not None and superstep_k == 1:
                # --replay_reuse with K=1: the arena stages [1, T+1, B]
                # stacks (its slots are what replay re-serves); the K=1
                # update step consumes plain [T+1, B] batches, so strip
                # the unit column axis here (a view, not a copy).
                batch = jax.tree_util.tree_map(lambda a: a[0], batch)
                initial_agent_state = jax.tree_util.tree_map(
                    lambda a: a[0], initial_agent_state
                )
            if shard is not None:
                return shard(batch, initial_agent_state)
            return (
                jax.device_put(batch, learner_device),
                jax.device_put(initial_agent_state, learner_device),
            )

        # Superstep mode: rollouts drain straight into the preallocated
        # [K, T+1, B, ...] host arena (runtime/queues.BatchArena) and the
        # prefetcher stages ONE K-batch transfer per superstep. Arena
        # slots are release-fenced: the learner releases each at its
        # stats flush (completion proven), so pool = prefetch depth + a
        # filling slot + the two dispatched-unflushed supersteps.
        # --replay_reuse rides the SAME arena (K=1 gets a unit-column
        # one): slots are re-served K' times before refill, each handout
        # re-placed to fresh device buffers so batch donation stays
        # legal.
        prefetch_depth = 2
        arena = None
        replay_reuse = max(1, hp.replay_reuse)
        if superstep_k > 1 or replay_reuse > 1:
            from torchbeast_tpu.runtime.queues import BatchArena

            # Same series prefix as the queue: learner_queue.batch_size
            # keeps reporting assembled update batches across modes
            # (--no_telemetry already no-ops the global instruments).
            arena = BatchArena(
                k=superstep_k, rows=local_rows, batch_dim=1,
                pool=prefetch_depth + 3, telemetry_name="learner_queue",
                # bf16_train: float32 rollout leaves land in bf16 arena
                # columns — the write-through copy IS the cast, and the
                # staged [K, T+1, B, ...] transfer is half-width.
                float_dtype=policy.batch_dtype,
                replay_reuse=replay_reuse,
            )
        prefetcher = DevicePrefetcher(
            learner_queue, _place, depth=prefetch_depth,
            telemetry_name="prefetch", arena=arena,
        )

        def learner_loop():
            try:
                _learner_loop_body()
            finally:
                if fleet_coord is not None:
                    # Leave the fleet's param-sync rendezvous set so
                    # slower hosts stop waiting on this learner.
                    fleet_coord.learner_done()
                # Always mark done — an async XLA error surfacing in the
                # delayed flush must stop the monitor loop, not wedge it.
                with state_lock:
                    state["done"] = True

        def _learner_loop_body():
            # One-step-delayed stats fetch: device_get on the PREVIOUS update's
            # stats happens after the current one is dispatched, so the host
            # never stalls XLA's async pipeline (the reference's equivalent
            # overlap came from extra learner threads + a lock). Under
            # supersteps each dispatch carries K updates and [K]-stacked
            # stats, so this ONE delayed sync covers K updates.
            pending = None  # (device_stats, step_after, arena_release)
            updates_done = 0  # snapshot versioning, in UPDATES

            def flush(pending_entry):
                device_stats, at_step, release = pending_entry
                # The learner thread's wait for the device: these stats
                # arrive when the update before this one has run.
                with sp_stats_fetch:
                    s = learner_lib.episode_stat_postprocess(
                        jax.device_get(device_stats)
                    )
                count_host_sync()
                if release is not None:
                    # Stats arrived => that superstep's execution (which
                    # read the arena stack) finished: its slot may be
                    # rewritten now (BatchArena fence contract).
                    release()
                s["step"] = at_step
                s["learner_queue_size"] = learner_queue.size()
                with state_lock:
                    state["stats"] = s
                plogger.log(s)

            while True:
                # 'dequeue' is the whole wait for a prefetched batch, one
                # observation per batch (actor starvation shows up here).
                with sp_dequeue:
                    staged = None
                    while staged is None:
                        try:
                            staged = prefetcher.get(timeout=1.0)
                        except stdlib_queue.Empty:
                            if not prefetcher.is_alive():
                                break
                if staged is None:
                    break
                with sp_learn:
                    if arena is not None:
                        (batch, initial_agent_state), release = staged
                    else:
                        batch, initial_agent_state = staged
                        release = None
                    # Replay handouts (BatchArena re-serving a slot under
                    # --replay_reuse) carry release.fresh == False: they
                    # advance the LEARN clock but not the env-frame clock.
                    fresh = release is None or getattr(release, "fresh", True)
                    if target_forward is not None:
                        # Lagged target-network forward, threaded into the
                        # batch under the learner.TARGET_*_KEYs (computed
                        # per dispatch: replay handouts see the CURRENT
                        # target, same as fresh ones).
                        _, tparams = target_store.latest()
                        t_logits, t_base = target_forward(
                            tparams, batch, initial_agent_state
                        )
                        batch = {
                            **batch,
                            learner_lib.TARGET_LOGITS_KEY: t_logits,
                            learner_lib.TARGET_BASELINE_KEY: t_base,
                        }
                    if throttle is not None:
                        # Chaos learner_stall gate: models the busy-chip
                        # stall at the dispatch site (no-op unarmed).
                        throttle()
                    # Dispatch under donation_lock (NOT state_lock):
                    # opt_state is donated, so the dispatch that
                    # invalidates the old opt buffers must not race a
                    # checkpoint's device_get of them — but dispatch can
                    # block behind in-flight compute, and holding
                    # state_lock here would stall every inference
                    # thread's params read for that long. Checkpointing
                    # takes donation_lock first.
                    with donation_lock:
                        with state_lock:
                            params_now = state["params"]
                            opt_now = state["opt_state"]
                        new_params, new_opt, train_stats = update_step(
                            params_now, opt_now, batch, initial_agent_state
                        )
                        with sp_publish:
                            # Build the host view OUTSIDE state_lock: for
                            # multi-host sharded params this blocks on the
                            # dispatched compute + D2H/H2D, and holding the
                            # lock for that long would stall every inference
                            # thread's params read.
                            infer_view = local_view(
                                new_params, device=infer_device
                            )
                            with state_lock:
                                state["params"] = new_params
                                state["opt_state"] = new_opt
                                state["infer_params"] = infer_view
                                # Global frames: every host ran this
                                # collective dispatch of superstep_k updates.
                                # Replay handouts re-consume frames already
                                # counted — only the learn clock moves for
                                # them.
                                if fresh:
                                    state["step"] += (
                                        superstep_k
                                        * flags.unroll_length
                                        * flags.batch_size
                                    )
                                state["learn_step"] += (
                                    superstep_k
                                    * flags.unroll_length
                                    * flags.batch_size
                                )
                                now_step = state["step"]
                    with sp_publish:
                        watchdog.ping()
                        updates_done += superstep_k
                        if target_store is not None and target_store.note_update(
                            updates_done
                        ):
                            # Full-precision target refresh (the store copies
                            # the tree, so the next dispatch's donation of
                            # these params cannot invalidate the snapshot).
                            with state_lock:
                                params_now = state["params"]
                            target_store.publish(updates_done, params_now)
                        if fleet_coord is not None and strategy == "wire":
                            # DCN param composition (wire strategy): one
                            # synchronous fleet-mean round per dispatch — the
                            # CPU-CI equivalent of the xla strategy's in-mesh
                            # grad all-reduce (averaging post-update params
                            # from equal starts IS gradient averaging for the
                            # SGD step; per-host RMSprop state stays local, the
                            # documented approximation — fleet/coordinator.py).
                            # None = the round degraded (timeout / fleet
                            # shutting down): keep this host's params.
                            with state_lock:
                                params_now = state["params"]
                            synced = fleet_coord.sync_params(params_now)
                            if synced is not None:
                                if learner_device is not None:
                                    synced = jax.device_put(
                                        synced, learner_device
                                    )
                                elif mesh is not None:
                                    synced = replicate(mesh, synced)
                                infer_view = local_view(
                                    synced, device=infer_device
                                )
                                with state_lock:
                                    state["params"] = synced
                                    state["infer_params"] = infer_view
                        if snapshot_store is not None:
                            # Versioned snapshot publish (serving/snapshot.py):
                            # due when the head has run >= refresh_updates past
                            # the last snapshot — a dropped refresh (the chaos
                            # failure hook) stays due and retries next update.
                            # Under the split this is the CROSS-SLICE publication
                            # path: infer_view is the learner-mesh params
                            # (single-process local_view is a pass-through), the
                            # bf16 cast runs on the mesh, and each slice pulls
                            # its device copy d2d via latest_on — zero host
                            # round-trips (tests/test_sebulba.py pins it).
                            if snapshot_store.note_update(updates_done):
                                if fleet_coord is not None and not is_lead:
                                    # Remote fleet hosts serve the LEAD's
                                    # policy: the wire (TAG_SNAPSHOT) feeds
                                    # this store; a local publish would fork
                                    # the fleet's serving policy. note_update
                                    # keeps advancing the head, so the stamped
                                    # policy_lag is the TRUE wire delay.
                                    pass
                                elif snapshot_store.publish(
                                    updates_done, infer_view
                                ) and fleet_coord is not None:
                                    # Cross-host publication (fleet/
                                    # snapshot_wire.py): same bf16 cast,
                                    # flattened leaves + dtype names riding
                                    # TAG_SNAPSHOT to every remote store.
                                    fleet_coord.publish_snapshot(
                                        updates_done, infer_view
                                    )
                    if pending is not None:
                        flush(pending)
                    pending = (train_stats, now_step, release)
                if now_step >= flags.total_steps:
                    break
            if pending is not None:
                flush(pending)

        learner_thread = threading.Thread(
            target=learner_loop, daemon=True, name="learner"
        )
    except BaseException:
        if server_supervisor is not None:
            server_supervisor.stop()  # before terminate: no resurrect-mid-reap
        _reap_servers(server_procs)
        if fleet_coord is not None:
            fleet_coord.shutdown()
        raise
    # From the first thread start onward, the main try/finally below owns
    # ALL cleanup (queues closed, threads joined, logger closed, servers
    # reaped) — a failure here must run that full path, not just the
    # server reap.
    try:
        infer_supervisor.start()
        actor_thread.start()
        prefetcher.start()
        learner_thread.start()
        watchdog.start()
        if chaos is not None:
            chaos.start()

        if flags.profile_dir:
            jax.profiler.start_trace(flags.profile_dir)

        num_live_floor = max(1, min(flags.min_live_actors, num_actors))
        degraded_dead = 0  # dead-actor count already reported
        last_checkpoint = time.time()
        last_step, last_time = state["step"], time.time()
        last_learn_step = state["learn_step"]
        while not state["done"]:
            # A halt cuts the monitor sleep short: HALTED must reach
            # the checkpoint-and-exit path now, not a tick later.
            health.halted.wait(timeout=5)
            if state["done"]:
                break
            # Graceful degradation (ISSUE 6, native since ISSUE 12):
            # individual actor deaths DEGRADE the run instead of ending
            # it; crossing the --min_live_actors floor halts it
            # cleanly. BOTH pools expose live_actors()/errors now, so
            # the same health machine drives either runtime; the
            # fallback branch below covers only a _tbt_core build that
            # predates liveness tracking.
            live_fn = getattr(actors, "live_actors", None)
            if live_fn is not None:
                live = live_fn()
                pool_errors = getattr(actors, "errors", [])
                dead = num_actors - live
                # Attrition-DEGRADED is sticky: retired actors never
                # come back, so a later stall/poison recovery must not
                # flip the run back to HEALTHY (health.degrade sticky=).
                if dead > degraded_dead and pool_errors:
                    degraded_dead = dead
                    health.degrade(
                        f"{dead}/{num_actors} actors retired "
                        f"(last error: {pool_errors[-1]})",
                        key="actor_attrition",
                        sticky=True,
                    )
                if live < num_live_floor:
                    health.halt(
                        f"live actors {live} below --min_live_actors "
                        f"{num_live_floor}"
                    )
                if (
                    not actor_thread.is_alive()
                    and live > 0
                    and not health.is_halted
                    and not state["done"]
                ):
                    # The pool runner itself died with loops alive — a
                    # wholesale failure, not attrition. (done-guarded:
                    # a finish landing mid-tick must not turn into a
                    # spurious failure.)
                    raise RuntimeError("Actor pool exited unexpectedly")
            else:
                # Stale _tbt_core build (predates live_actors): errors
                # are recorded C++-side while surviving loops keep
                # running; poll them so one dead actor surfaces within
                # 5s. done-guarded like the code this replaced: actors
                # erroring against reaped servers during a clean finish
                # are expected, not failures.
                first_error = getattr(actors, "first_error_message", None)
                if first_error is not None and not state["done"]:
                    msg = first_error()
                    if msg:
                        raise RuntimeError(f"Actor pool failed: {msg}")
                if not actor_thread.is_alive() and not state["done"]:
                    raise RuntimeError("Actor pool exited unexpectedly")
            if infer_supervisor.errors:
                # An unrecoverable serving bug (not a poisoning):
                # surface it like the old raw threads did — checked
                # BEFORE the halt break, because with one serving
                # thread the supervisor halts on its own crash and a
                # clean HALTED exit would mask the bug behind rc 0.
                raise RuntimeError(
                    "Inference thread failed"
                ) from infer_supervisor.errors[0]
            if health.is_halted:
                log.error(
                    "Pipeline HALTED (%s); checkpointing and exiting "
                    "cleanly.",
                    "; ".join(r for _, r in health.reasons()[-3:]),
                )
                break
            with state_lock:
                now_step = state["step"]
                now_learn_step = state["learn_step"]
                stats_now = dict(state["stats"])
            now = time.time()
            sps = (now_step - last_step) / (now - last_time)
            learn_sps = (now_learn_step - last_learn_step) / (
                now - last_time
            )
            last_step, last_time = now_step, now
            last_learn_step = now_learn_step
            if telemetry_on:
                # Gauges set here (not in the queues) also cover the
                # native runtime, whose C++ queues carry no instruments.
                # env vs learn throughput split (ISSUE 18): env_sps
                # counts unique env frames; learn_sps counts frames
                # consumed by updates — env_sps x --replay_reuse in
                # steady state.
                reg.gauge("learner.env_sps").set(sps)
                reg.gauge("learner.learn_sps").set(learn_sps)
                reg.gauge("learner.sample_reuse").set(replay_reuse)
                reg.gauge("learner_queue.depth").set(learner_queue.size())
                reg.gauge("inference.depth").set(serving_depth_fn())
                # From the last fetched update's own stats: a gauge
                # `<family>.<name>` for every `<family>_<name>` a model's
                # layers sowed of themselves (models/stats.py: what the
                # routers did, a looped trunk's passes, which attention
                # path a block was traced through, the recurrent
                # layers' state) or the update counted (`moe_bias_
                # steps`).
                for key, value in stats_now.items():
                    gauge = model_stats.gauge_name(key)
                    if gauge:
                        reg.gauge(gauge).set(value)
                tele.write(extra={"step": now_step})
            means = timings.means()
            log.info(
                "Step %d @ %.1f SPS. Inference batcher size: %d. "
                "Learner queue size: %d. Loss %.4f. "
                "[dequeue %.0fms learn %.0fms] %s",
                now_step, sps, serving_depth_fn(),
                learner_queue.size(),
                stats_now.get("total_loss", float("nan")),
                1000 * means.get("dequeue", 0.0),
                1000 * means.get("learn", 0.0),
                f"Return {stats_now['mean_episode_return']:.1f}."
                if "mean_episode_return" in stats_now else "",
            )
            if is_lead and now - last_checkpoint > flags.checkpoint_interval_s:
                with donation_lock, state_lock:
                    save_checkpoint(
                        checkpoint_path,
                        params=local_view(state["params"]),
                        opt_state=local_view(state["opt_state"]),
                        step=state["step"],
                        flags=vars(flags),
                        stats=state["stats"],
                    )
                last_checkpoint = now
        successful = True
    except KeyboardInterrupt:
        successful = True
    except BaseException:
        successful = False
        raise
    finally:
        if chaos is not None:
            chaos.stop()
            # The final telemetry line carries the injection ledger the
            # chaos harness audits recovery counters against.
            tele.set_static("chaos", chaos.summary())
        watchdog.stop()
        if flags.profile_dir:
            stop_profile(
                flags, tele, update_step, state["stats"]
            )
        # Shutdown ordering mirrors the reference (polybeast_learner.py:
        # 587-593): close batcher + queue, join actors, join threads.
        # The replica batcher (when armed) closes alongside the central
        # one so replica serving threads exit their loops cleanly.
        closers = [learner_queue]
        if inference_batcher is not None:
            closers.insert(0, inference_batcher)
        if sebulba is not None:
            # Every slice batcher closes so each slice's serving thread
            # exits its loop cleanly.
            closers = [s.batcher for s in sebulba.stacks] + closers
        if replica_parts is not None:
            closers.insert(1, replica_parts["batcher"])
        for closer in closers:
            try:
                closer.close()
            except RuntimeError:
                pass
        actor_thread.join(timeout=10)
        prefetcher.close()
        prefetcher.join(timeout=10)
        learner_thread.join(timeout=10)
        if is_lead:
            with donation_lock, state_lock:
                save_checkpoint(
                    checkpoint_path,
                    params=local_view(state["params"]),
                    opt_state=local_view(state["opt_state"]),
                    step=state["step"],
                    flags=vars(flags),
                    stats=state["stats"],
                )
        tele.shutdown(step=state["step"])
        plogger.close(successful=successful)
        if server_supervisor is not None:
            server_supervisor.stop()  # before terminate: no resurrect-mid-reap
        _reap_servers(server_procs)
        if fleet_coord is not None:
            # After the final telemetry write (the folder's last fold
            # reads remote gauges) and the server reap: a clean "bye"
            # to the fleet, so departure is accounted as done, not
            # lost.
            fleet_coord.shutdown()
    log.info(
        "Learning finished after %d steps (health %s).",
        state["step"], health.state_name,
    )
    stats = dict(state["stats"])
    stats["server_restarts"] = (
        server_supervisor.restarts if server_supervisor is not None else 0
    )
    # Recovery/health summary: what scripts/chaos_run.py asserts its
    # exact fault accounting against (and what a log reader needs to
    # know whether "finished" meant HEALTHY or limped-home DEGRADED).
    stats["health"] = health.state_name
    stats["health_reasons"] = health.reasons()
    # reconnect_count() is the method BOTH pools expose (the C++ pool
    # has no `reconnects` property; a getattr fallback to 0 would
    # silently zero the native runtime's recovery summary).
    reconnect_count = getattr(actors, "reconnect_count", None)
    stats["actor_reconnects"] = (
        int(reconnect_count()) if reconnect_count is not None else 0
    )
    stats["inference_restarts"] = infer_supervisor.restarts
    if chaos is not None:
        stats["chaos"] = chaos.summary()
    return stats


def _probe_env_via_server(flags, address, timeout_s: float = 60.0):
    """Probe action/observation spec from a running env server (split
    deployments may not have the env deps on the learner host); fall back
    to a local probe when no server is reachable (e.g. unit tests calling
    train() with start_servers but slow spawns — the local env id is the
    same)."""
    from torchbeast_tpu.runtime import transport as transport_lib

    deadline = time.monotonic() + timeout_s
    last_error = None
    while time.monotonic() < deadline:
        stream = None
        try:
            # connect_transport speaks every address scheme (incl. the
            # shm handshake, which a raw socket probe would misread as
            # the initial step). recv_timeout_s bounds the spec read: a
            # server that accepts but stalls before the initial step
            # must fall through to the retry loop / local-probe
            # fallback, not hang startup.
            stream = transport_lib.connect_transport(
                address, timeout_s=min(5.0, timeout_s),
                recv_timeout_s=5.0,
            )
            step = stream.recv()
            if not isinstance(step, dict) or step.get("type") == "error":
                # Deterministic server-side failure (env construction
                # raised) or a server that predates spec advertisement:
                # retrying would rebuild the env ~5x/sec for nothing.
                last_error = RuntimeError(f"server replied {step!r:.200}")
                step = None  # drop transport-buffer views before close
                break
            if "num_actions" not in step:
                last_error = KeyError(
                    "server does not advertise num_actions"
                )
                step = None
                break
            frame = np.asarray(step["frame"]).copy()
            num_actions = int(step["num_actions"])
            # Drop the decoded nest before the finally closes the
            # transport: its arrays are views into the shm ring /
            # receive buffer, and unmapping under live views is an error.
            step = None
            return num_actions, frame.shape, frame.dtype
        except (OSError, TimeoutError) as e:  # not up yet — retry
            last_error = e
            time.sleep(0.2)
        except wire.WireError as e:
            last_error = e
            break
        finally:
            if stream is not None:
                stream.close()
    log.warning(
        "Could not probe env spec from %s (%s); probing locally.",
        address, last_error,
    )
    return probe_env(flags.env)


def main(flags):
    configure_logging()
    if flags.mode == "test":
        # Greedy checkpoint evaluation — shared with the mono driver. (The
        # reference's poly test() is a NotImplementedError,
        # polybeast_learner.py:596-597; here it just works.)
        from torchbeast_tpu import monobeast

        return monobeast.test(flags)
    return train(flags)


def cli():
    from torchbeast_tpu.utils import install_preemption_handler
    from torchbeast_tpu.utils.xla_cache import use_compile_cache

    install_preemption_handler()  # SIGTERM -> clean checkpointed exit
    use_compile_cache()
    main(make_parser().parse_args())


if __name__ == "__main__":
    cli()
