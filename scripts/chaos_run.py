#!/usr/bin/env python
"""Chaos acceptance harness (ISSUE 6, scaled + overload-aware in
ISSUE 14): run polybeast under a seeded multi-fault plan and PROVE
recovery, not just survival.

Two in-process polybeast runs on the same config:

  1. baseline — fault-free,
  2. chaos    — a seeded FaultPlan firing >=4 fault classes mid-run
                (env-server SIGKILL x scale, transport sever x scale,
                state-table poison, learner stall by default),

then assert:

  - the chaos run completes (reaches --total_steps, health != HALTED),
  - learning is intact: final mean episode return matches the
    fault-free baseline within --return_tol,
  - recovery telemetry counters EXACTLY equal the injected fault
    counts (server restarts == SIGKILLs, actor reconnects ==
    SIGKILLs x actors-per-server + severs, inference restarts ==
    table rebuilds == poisons),
  - load shedding is real AND lossless: with the admission gate armed
    (--request_deadline_ms) and a learner stall planned, the serving
    tier sheds (serving.shed + serving.expired > 0) and every shed was
    re-submitted (serving.resubmitted == shed + expired — a shed is
    never a lost rollout),
  - nothing leaked: no live child processes, no new /dev/shm segments.

`--scale N` multiplies the actor/server fleet AND the fault plan
together (N SIGKILLs on distinct servers, N severs on distinct actors
disjoint from the killed servers' actors, staggered triggers), so the
10x acceptance run (scale 10 on the 16-actor/8-server base = 160/80)
exercises the same exact accounting as the CI selftest.

`--selftest` is the CPU CI gate (Mock env, short run; scripts/check.sh
runs it at --scale 2); the default mode is the Catch acceptance run,
whose verdict goes where `--out` says.

Usage:
  python scripts/chaos_run.py --selftest
  python scripts/chaos_run.py --selftest --scale 2
  python scripts/chaos_run.py --out logs/chaos_run.json
  python scripts/chaos_run.py --native --scale 10 --num_servers 8 \\
      --num_actors 16 --batch_size 16 --request_deadline_ms 2000 \\
      --out logs/chaos_run_10x.json
"""

import argparse
import json
import multiprocessing as mp
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

SHM_DIR = "/dev/shm"


def parse_args(argv=None):
    # The harness deliberately scales the driver's flags DOWN (small
    # env, short run, tiny batch) so two full polybeast runs fit a CI
    # budget — each shared-name divergence below is that intent, spelled
    # out per flag for beastlint's FLAG-PARITY cross-driver check.
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--selftest", action="store_true",
                   help="Short structural run on Mock (the CI gate).")
    p.add_argument("--native", action="store_true",
                   help="Run both legs with --native_runtime (the C++ "
                        "pool; needs the _tbt_core extension, "
                        "scripts/build_native.sh). Transport faults "
                        "then ride the pool's C++ FaultHooks instead "
                        "of the Python FaultingTransport wrap — the "
                        "same plan, the same exact accounting "
                        "(ISSUE 12). Without this flag both legs pin "
                        "--no_native_runtime: the harness's "
                        "interposition accounting must know which "
                        "runtime it audits, not inherit the driver "
                        "default.")
    p.add_argument("--scale", type=int, default=1,
                   help="Scale knob (ISSUE 14): multiplies "
                        "num_actors/num_servers AND the fault plan "
                        "together — scale N plans N env-server "
                        "SIGKILLs (distinct servers) and N transport "
                        "severs (distinct actors, disjoint from the "
                        "killed servers' actors), staggered across "
                        "the run. Requires num_servers >= 2*scale "
                        "so the two target sets stay disjoint.")
    # beastlint: disable=FLAG-PARITY  armed by default here: the chaos harness's whole point is exercising the shed path; the driver default (0 = off) preserves pre-ISSUE-14 behavior
    p.add_argument("--request_deadline_ms", type=float, default=300.0,
                   help="Forwarded to both legs: arms the admission "
                        "gate so the planned learner_stall produces "
                        "real sheds (asserted > 0). 0 disarms it "
                        "(and the shed assertions).")
    p.add_argument("--stall_s", type=float, default=3.0,
                   help="learner_stall fault duration: how long the "
                        "learner AND serving threads freeze (the "
                        "shared-chip overload model). Must exceed "
                        "request_deadline_ms for deterministic "
                        "expiry sheds.")
    # Replica serving knobs forwarded to BOTH legs verbatim (same
    # type/default as polybeast, FLAG-PARITY-checked): 0 = central
    # serving only; set --replica_refresh_updates to chaos-test the
    # snapshot/lag machinery too (Python runtime only).
    p.add_argument("--replica_refresh_updates", type=int, default=0)
    p.add_argument("--max_policy_lag", type=int, default=20)
    # Continuous-batching depth knob forwarded verbatim (same
    # type/default as polybeast, FLAG-PARITY-checked): the admission
    # gate's queue bound as a multiple of max_inference_batch_size.
    p.add_argument("--admission_depth_factor", type=int, default=4)
    # Resilience knobs forwarded to BOTH legs: re-declared here (same
    # type/default as polybeast) so beastlint FLAG-PARITY keeps the
    # chaos harness from drifting away from the driver's resilience
    # surface.
    p.add_argument("--min_live_actors", type=int, default=1,
                   help="Graceful degradation floor: the run "
                        "continues DEGRADED while at least this "
                        "many actor loops are alive, and "
                        "checkpoints-then-exits cleanly (health "
                        "HALTED) below it — instead of hanging on "
                        "a starved learner queue.")
    p.add_argument("--inference_restart_budget", type=int, default=3,
                   help="How many times the inference supervisor "
                        "may rebuild a poisoned DeviceStateTable "
                        "and restart the serving threads before "
                        "the pipeline goes HALTED "
                        "(checkpoint-and-exit).")
    p.add_argument("--max_actor_reconnects", type=int, default=3,
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
    # beastlint: disable=FLAG-PARITY  a wedged chaos run should fail THIS harness in a minute, not after the driver's 5-minute stall deadline
    p.add_argument("--learner_stall_timeout_s", type=float, default=60.0,
                   help="Learner stall watchdog deadline forwarded to "
                        "both legs (shortened vs the driver default).")
    # beastlint: disable=FLAG-PARITY  Catch solves in minutes on CPU; the chaos harness needs a LEARNABLE short run, not Pong
    p.add_argument("--env", default="Catch")
    # beastlint: disable=FLAG-PARITY  two full runs per invocation: 60k steps keeps the acceptance pass under a CI budget
    p.add_argument("--total_steps", type=int, default=60000)
    p.add_argument("--num_servers", type=int, default=4)
    # beastlint: disable=FLAG-PARITY  pinned to num_servers (1:1 topology) so reconnect accounting is exact; polybeast's None means "derive from servers"
    p.add_argument("--num_actors", type=int, default=4,
                   help="Keep == num_servers: the 1:1 actor/server "
                        "topology is what makes reconnect accounting "
                        "exact (1 per SIGKILL).")
    # beastlint: disable=FLAG-PARITY  small batch matches the 4-actor chaos topology, not the beefy-machine default
    p.add_argument("--batch_size", type=int, default=4)
    # beastlint: disable=FLAG-PARITY  short unrolls make the injected faults land mid-rollout within the short run
    p.add_argument("--unroll_length", type=int, default=20)
    # beastlint: disable=FLAG-PARITY  higher LR so Catch converges inside the shortened run
    p.add_argument("--learning_rate", type=float, default=2e-3)
    # beastlint: disable=FLAG-PARITY  higher exploration bonus for the short Catch run, same reason as the LR
    p.add_argument("--entropy_cost", type=float, default=0.01)
    # beastlint: disable=FLAG-PARITY  the committed chaos artifact is reproduced from THIS seed; it feeds the FaultPlan, not just the env
    p.add_argument("--seed", type=int, default=7,
                   help="FaultPlan seed + --env_seed for both runs.")
    p.add_argument("--return_tol", type=float, default=0.2,
                   help="Allowed |chaos - baseline| final-return gap.")
    p.add_argument("--scheduler_pressure", type=int, default=0,
                   help="Induced-scheduler-pressure mode (ROADMAP "
                        "metastability debt): run the CHAOS leg with N "
                        "spinner subprocesses competing for every core "
                        "and record "
                        "the ring.doorbell_waits / "
                        "ring.recheck_wakeups contrast between the "
                        "unpressured baseline leg and the pressured "
                        "chaos leg in the verdict's \"ring\" block — "
                        "the counter baseline needed to localize the "
                        "doorbell root cause. 0 = off (both legs "
                        "unpressured; the ring block is still "
                        "recorded).")
    # Multi-host fleet lane (ISSUE 17): --hosts 2 runs ONE fleet
    # (in-process lead + subprocess remote) instead of the
    # baseline/chaos pair, SIGKILLs the remote's whole env-server
    # fleet mid-run, and asserts the remote's exact reconnect
    # accounting plus the STICKY fleet.host1 degradation folded on the
    # surviving lead.
    p.add_argument("--hosts", type=int, default=1,
                   help="2 = the fleet chaos lane (lead in-process, "
                        "host 1 a polybeast subprocess joined via "
                        "--fleet over a free loopback port). 1 = the "
                        "classic baseline/chaos pair.")
    p.add_argument("--fleet", default=None,
                   help="Declared for driver parity and rejected when "
                        "set: the harness composes the fleet spec "
                        "itself from --hosts.")
    p.add_argument("--min_live_hosts", type=int, default=1,
                   help="Fleet degradation floor (--fleet runs): "
                        "losing a host marks the fleet DEGRADED "
                        "(sticky fleet.host<r>_lost) while at "
                        "least this many hosts stay live; "
                        "forwarded to both fleet hosts.")
    # beastlint: disable=FLAG-PARITY  None means "fresh temp dir per run": chaos artifacts must never land in the training logdir
    p.add_argument("--savedir", default=None,
                   help="Default: a fresh temp dir.")
    p.add_argument("--out", default=None,
                   help="Also write the JSON verdict here.")
    return p.parse_args(argv)


def build_plan(args) -> dict:
    """>=4 fault classes, step-triggered at fractions of the run so the
    pipeline is warm at injection time, SCALED with --scale (the plan
    grows with the fleet, ISSUE 14).

    The plan-scaling rule (schema-pinned in tests/test_bench_scripts):
    scale N plans N `env_server_sigkill` on servers 0..N-1 and N
    `transport_sever` on actors N..2N-1 — actor i connects to server
    i % num_servers, so with num_servers >= 2N the severed actors'
    servers are never killed and each fault maps to EXACTLY one
    recovery: reconnects == kills * (num_actors // num_servers) +
    severs. One state-table poison and one learner_stall (duration
    --stall_s) round out the classes; triggers stagger across
    [0.15, 0.65] of the run so recoveries do not overlap their own
    class's next injection."""
    t, n = args.total_steps, args.scale
    faults = []
    for i in range(n):
        faults.append({
            "kind": "env_server_sigkill",
            "at_step": int(t * (0.15 + 0.4 * i / n)),
            "target": i,
        })
        faults.append({
            "kind": "transport_sever",
            "at_step": int(t * (0.25 + 0.4 * i / n)),
            "target": n + i,
        })
    faults.append({"kind": "learner_stall", "at_step": int(t * 0.5),
                   "duration_s": args.stall_s})
    faults.append({"kind": "state_table_poison", "at_step": int(t * 0.7)})
    return {"seed": args.seed, "faults": faults}


def make_argv(args, savedir, xpid, chaos_plan_path=None,
              fleet_spec=None):
    argv = [
        "--env", args.env,
        "--model", "mlp",
        "--use_lstm",  # the state table only exists for recurrent models
        "--num_servers", str(args.num_servers),
        "--num_actors", str(args.num_actors),
        "--batch_size", str(args.batch_size),
        "--unroll_length", str(args.unroll_length),
        "--total_steps", str(args.total_steps),
        "--learning_rate", str(args.learning_rate),
        "--entropy_cost", str(args.entropy_cost),
        "--env_seed", str(args.seed),
        "--savedir", savedir,
        "--xpid", xpid,
        # shm rings so the SIGKILL class also exercises the segment
        # sweep (the no-leak assertion below would catch a regression).
        "--pipes_basename", f"shm:{savedir}/pipes-{xpid}",
        "--num_inference_threads", "1",
        "--max_inference_batch_size", "4",
        "--checkpoint_interval_s", "100000",
        "--min_live_actors", str(args.min_live_actors),
        "--inference_restart_budget", str(args.inference_restart_budget),
        "--max_actor_reconnects", str(args.max_actor_reconnects),
        "--learner_stall_timeout_s", str(args.learner_stall_timeout_s),
        "--request_deadline_ms", str(args.request_deadline_ms),
        "--admission_depth_factor", str(args.admission_depth_factor),
        "--replica_refresh_updates", str(args.replica_refresh_updates),
        "--max_policy_lag", str(args.max_policy_lag),
    ]
    # The runtime is pinned explicitly either way: the harness's fault
    # interposition accounting (FaultHooks vs FaultingTransport) must
    # audit the runtime it CHOSE, not inherit the driver's default.
    if getattr(args, "native", False):
        argv += ["--native_runtime"]
    else:
        argv += ["--no_native_runtime"]
    if chaos_plan_path is not None:
        argv += ["--chaos_plan", chaos_plan_path]
    if fleet_spec is not None:
        argv += ["--fleet", fleet_spec,
                 "--min_live_hosts", str(args.min_live_hosts)]
    return argv


def make_flags(args, savedir, xpid, chaos_plan_path=None,
               fleet_spec=None):
    from torchbeast_tpu import polybeast

    return polybeast.make_parser().parse_args(
        make_argv(args, savedir, xpid, chaos_plan_path, fleet_spec)
    )


def final_return(savedir, xpid):
    """Last non-empty mean_episode_return from the run's logs.csv (the
    in-memory stats dict can miss it when the final flush window closed
    no episode)."""
    import csv

    path = os.path.join(savedir, xpid, "logs.csv")
    last = None
    with open(path) as f:
        for row in csv.DictReader(f):
            val = row.get("mean_episode_return")
            if val:
                last = float(val)
    return last


def _shm_entries():
    if not os.path.isdir(SHM_DIR):
        return set()
    return {n for n in os.listdir(SHM_DIR) if n.startswith("psm_")}


def _live_children():
    return {p.pid for p in mp.active_children() if p.is_alive()}


class _SchedulerPressure:
    """Spinner subprocesses competing for every core while the chaos
    leg runs, paired with the ring-wait counters so the verdict
    carries a pressured-vs-unpressured baseline for the doorbell
    metastability investigation. n=0 is a
    no-op (spawns nothing), so the harness can wrap the leg
    unconditionally."""

    def __init__(self, n: int):
        self._n = max(0, int(n))
        self._procs = []

    def __enter__(self):
        import subprocess

        for _ in range(self._n):
            self._procs.append(subprocess.Popen(
                [sys.executable, "-c", "while True: pass"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                start_new_session=True,
            ))
        return self

    def __exit__(self, *exc):
        import signal

        for proc in self._procs:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                proc.kill()
            proc.wait()
        self._procs = []
        return False


def run_one(args, savedir, xpid, chaos_plan_path=None, fleet_spec=None):
    """One polybeast run with leak accounting and a counter delta."""
    from torchbeast_tpu import polybeast, telemetry

    shm_before = _shm_entries()
    procs_before = _live_children()
    snap_before = telemetry.snapshot()
    t0 = time.monotonic()
    flags = make_flags(args, savedir, xpid, chaos_plan_path, fleet_spec)
    stats = polybeast.train(flags)
    elapsed = time.monotonic() - t0
    counters = telemetry.delta(telemetry.snapshot(), snap_before).get(
        "counters", {}
    )
    return {
        "xpid": xpid,
        "elapsed_s": round(elapsed, 1),
        "step": stats.get("step", 0),
        "health": stats.get("health"),
        "mean_episode_return": final_return(savedir, xpid),
        "server_restarts": stats.get("server_restarts", 0),
        "actor_reconnects": stats.get("actor_reconnects", 0),
        "inference_restarts": stats.get("inference_restarts", 0),
        "health_reasons": stats.get("health_reasons"),
        "chaos": stats.get("chaos"),
        "counters": counters,
        "leaked_processes": sorted(_live_children() - procs_before),
        "leaked_shm": sorted(_shm_entries() - shm_before),
    }


def _free_coord_port():
    """A loopback port P with P+1 also free (rendezvous + control
    plane, fleet/topology.py CONTROL_PORT_OFFSET)."""
    import socket as socketlib

    for _ in range(50):
        s1 = socketlib.socket()
        s2 = socketlib.socket()
        try:
            s1.bind(("127.0.0.1", 0))
            port = s1.getsockname()[1]
            try:
                s2.bind(("127.0.0.1", port + 1))
            except OSError:
                continue
            return port
        finally:
            s1.close()
            s2.close()
    raise RuntimeError("no free adjacent port pair for --fleet coord")


def build_fleet_plan(args) -> dict:
    """The remote host's plan: SIGKILL its ENTIRE env-server fleet,
    staggered across [0.15, 0.55] of the run — one whole host's
    serving substrate churns while the lead host rides through
    untouched. Each kill maps to exactly actors-per-server reconnects
    on THAT host (the same accounting rule as the single-host plan)."""
    t, n = args.total_steps, args.num_servers
    faults = [
        {
            "kind": "env_server_sigkill",
            "at_step": int(t * (0.15 + 0.4 * i / n)),
            "target": i,
        }
        for i in range(n)
    ]
    return {"seed": args.seed, "faults": faults}


def run_fleet(args, savedir) -> int:
    """--hosts 2 lane (ISSUE 17): one fleet run — in-process lead +
    subprocess remote joined over a free loopback coord port — with the
    remote's whole env-server fleet SIGKILLed mid-run. Asserts the
    remote recovered with EXACT accounting, the lead folded a STICKY
    fleet.host1 degradation, and nobody halted."""
    import signal
    import subprocess

    from torchbeast_tpu import telemetry
    from torchbeast_tpu.resilience.chaos import FaultPlan

    xpid = "chaos-fleet"
    n_hosts = args.hosts
    plan_dict = build_fleet_plan(args)
    plan = FaultPlan.from_dict(plan_dict)
    plan_path = os.path.join(savedir, "fault_plan_host1.json")
    with open(plan_path, "w") as f:
        json.dump(plan_dict, f, indent=2)

    coord = f"127.0.0.1:{_free_coord_port()}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + ":" + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    # Remote host 1 launches first (it Backoff-dials the lead's control
    # plane) and carries the fault plan; its own process group so a
    # timeout kill also reaps its env-server children.
    remote_log = os.path.join(savedir, "host1.log")
    remote_argv = make_argv(
        args, savedir, xpid, plan_path,
        fleet_spec=f"host=1/{n_hosts},coord={coord}",
    )
    with open(remote_log, "w") as logf:
        remote = subprocess.Popen(
            [sys.executable, "-m", "torchbeast_tpu.polybeast"]
            + remote_argv,
            env=env, stdout=logf, stderr=subprocess.STDOUT, cwd=repo,
            start_new_session=True,
        )
        try:
            lead = run_one(
                args, savedir, xpid,
                fleet_spec=f"host=0/{n_hosts},coord={coord}",
            )
            try:
                remote_rc = remote.wait(timeout=120)
            except subprocess.TimeoutExpired:
                remote_rc = None  # killed below; fails the rc check
        finally:
            try:
                os.killpg(remote.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            remote.wait()

    remote_snaps = telemetry.read_jsonl(
        os.path.join(savedir, f"{xpid}-host1", "telemetry.jsonl")
    )
    remote_snap = remote_snaps[-1] if remote_snaps else {}
    remote_counters = remote_snap.get("counters", {})

    failures = []
    # -- completion on BOTH hosts (degraded, never halted) ----------------
    if lead["step"] < args.total_steps:
        failures.append(
            f"lead stopped at step {lead['step']} < {args.total_steps} "
            f"(health {lead['health']})"
        )
    if lead["health"] == "HALTED":
        failures.append("lead ended HALTED (floor is 1: the surviving "
                        "host must degrade, not abort)")
    if remote_rc != 0:
        failures.append(f"remote host exited rc={remote_rc} "
                        f"(log {remote_log})")
    # -- remote host identity on its telemetry stream ---------------------
    if remote_snap.get("host_rank") != 1:
        failures.append(
            f"remote host_rank static: got {remote_snap.get('host_rank')}"
            ", want 1"
        )
    if remote_snap.get("fleet_size") != n_hosts:
        failures.append(
            f"remote fleet_size static: got "
            f"{remote_snap.get('fleet_size')}, want {n_hosts}"
        )
    # -- exact recovery accounting on the faulted host --------------------
    n_kill = plan.counts().get("env_server_sigkill", 0)
    actors_per_server = args.num_actors // args.num_servers
    expected = {
        "chaos.env_server_sigkill.injected": n_kill,
        "recovery.server_restarts": n_kill,
        "recovery.actor_reconnects": n_kill * actors_per_server,
    }
    for name, want in expected.items():
        got = int(remote_counters.get(name, 0))
        if got != want:
            failures.append(
                f"remote counter {name}: got {got}, want {want}"
            )
    # -- the lead folded the incident as a STICKY degradation -------------
    reasons = lead.get("health_reasons") or []
    if not any(r.startswith("fleet.host1") for _, r in reasons):
        failures.append(
            "no fleet.host1 degradation folded on the lead "
            f"(reasons: {reasons})"
        )
    if lead["health"] != "DEGRADED":
        failures.append(
            f"lead health {lead['health']}: the remote's recovered "
            "SIGKILLs must leave a sticky DEGRADED mark"
        )

    verdict = {
        "bench": "chaos_run",
        "selftest": bool(args.selftest),
        "native": bool(args.native),
        "hosts": n_hosts,
        "scale": args.scale,
        "num_actors": args.num_actors,
        "num_servers": args.num_servers,
        "ok": not failures,
        "failures": failures,
        "env": args.env,
        "total_steps": args.total_steps,
        "plan": plan_dict,
        "expected_counters": expected,
        "results": {
            "lead": lead,
            "remote": {
                "rc": remote_rc,
                "telemetry_lines": len(remote_snaps),
                "counters": {
                    k: v for k, v in remote_counters.items()
                    if k.startswith(("chaos.", "recovery.", "fleet."))
                },
                "log": remote_log,
            },
        },
        "telemetry": telemetry.telemetry_block(),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=2)
            f.write("\n")
    print(json.dumps(verdict))
    return 0 if verdict["ok"] else 1


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.selftest:
        # Short structural gate: Mock's return is deterministic (200.0
        # per episode regardless of policy), so return parity is exact
        # and the whole thing fits a CI budget.
        args.env = "Mock"
        args.total_steps = 2400
        args.num_servers = args.num_actors = 2
        args.batch_size = 2
        args.return_tol = 1e-6
        # Short stall, same contract: it still exceeds the deadline so
        # expiry sheds fire deterministically.
        args.stall_s = min(args.stall_s, 1.5)

    if args.scale < 1:
        print("--scale must be >= 1", file=sys.stderr)
        return 2
    if args.fleet:
        print(
            "--fleet is composed internally from --hosts; do not set "
            "it on the harness",
            file=sys.stderr,
        )
        return 2
    if args.hosts not in (1, 2):
        print("--hosts must be 1 or 2 (the fleet lane pins one remote "
              "host)", file=sys.stderr)
        return 2
    if args.hosts > 1 and args.scheduler_pressure:
        print(
            "--scheduler_pressure is a single-host mode: it wraps the "
            "chaos leg of the baseline/chaos pair, which the fleet "
            "lane replaces",
            file=sys.stderr,
        )
        return 2
    if args.hosts > 1 and args.batch_size % args.hosts != 0:
        print(
            f"--batch_size {args.batch_size} (global) must be "
            f"divisible by --hosts {args.hosts}",
            file=sys.stderr,
        )
        return 2
    # The scale knob multiplies the fleet AND the plan together.
    args.num_servers *= args.scale
    args.num_actors *= args.scale
    if args.num_actors % args.num_servers != 0:
        print(
            f"num_actors {args.num_actors} must be a multiple of "
            f"num_servers {args.num_servers} (uniform actors-per-server "
            "is what keeps reconnect accounting exact)",
            file=sys.stderr,
        )
        return 2
    if args.num_servers < 2 * args.scale:
        print(
            f"num_servers {args.num_servers} must be >= 2*scale "
            f"{2 * args.scale} (kill and sever target sets must stay "
            "disjoint for exact accounting)",
            file=sys.stderr,
        )
        return 2
    if (
        args.request_deadline_ms > 0
        and args.stall_s * 1000 <= args.request_deadline_ms
    ):
        print(
            "--stall_s must exceed --request_deadline_ms or the stall "
            "cannot produce deterministic expiry sheds",
            file=sys.stderr,
        )
        return 2

    if args.native:
        # gap_reason, not available(): a stale extension would make the
        # driver fall back to the Python pool and this harness would
        # silently audit the WRONG runtime into a "native": true
        # artifact.
        from torchbeast_tpu.runtime.native import gap_reason

        reason = gap_reason()
        if reason is not None:
            print(f"chaos_run --native: {reason}", file=sys.stderr)
            return 2

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

    # The harness calls train() directly, so it owns logging config —
    # without this the driver's step/health/chaos lines are invisible.
    from torchbeast_tpu.utils import configure_logging

    configure_logging()

    from torchbeast_tpu import telemetry
    from torchbeast_tpu.resilience.chaos import FaultPlan

    savedir = args.savedir
    if savedir is None:
        import tempfile

        savedir = tempfile.mkdtemp(prefix="chaos_run_")
    if args.hosts >= 2:
        return run_fleet(args, savedir)

    plan_dict = build_plan(args)
    plan = FaultPlan.from_dict(plan_dict)  # validates kinds/triggers
    plan_path = os.path.join(savedir, "fault_plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan_dict, f, indent=2)

    failures = []
    baseline = run_one(args, savedir, "chaos-baseline")
    # Only the chaos leg runs under induced scheduler pressure: the
    # unpressured baseline leg is the contrast the ring block needs.
    with _SchedulerPressure(args.scheduler_pressure):
        chaos = run_one(args, savedir, "chaos-faulted", plan_path)

    # -- completion --------------------------------------------------------
    if chaos["step"] < args.total_steps:
        failures.append(
            f"chaos run stopped at step {chaos['step']} < "
            f"{args.total_steps} (health {chaos['health']})"
        )
    if chaos["health"] == "HALTED":
        failures.append("chaos run ended HALTED")

    # -- learning intact ---------------------------------------------------
    base_ret, chaos_ret = (
        baseline["mean_episode_return"], chaos["mean_episode_return"]
    )
    if base_ret is None or chaos_ret is None:
        failures.append(
            f"missing episode returns (baseline {base_ret}, "
            f"chaos {chaos_ret})"
        )
    elif abs(base_ret - chaos_ret) > args.return_tol:
        failures.append(
            f"return drift: baseline {base_ret} vs chaos {chaos_ret} "
            f"(tol {args.return_tol})"
        )

    # -- exact recovery accounting ----------------------------------------
    injected = (chaos.get("chaos") or {}).get("injected", {})
    plan_counts = plan.counts()
    if injected != plan_counts:
        failures.append(
            f"injected {injected} != planned {plan_counts} "
            "(a fault never fired)"
        )
    n_kill = plan_counts.get("env_server_sigkill", 0)
    n_sever = plan_counts.get("transport_sever", 0)
    n_poison = plan_counts.get("state_table_poison", 0)
    # Uniform fan-in (validated above): a killed server drops ALL its
    # actors' streams, so each SIGKILL accounts for actors-per-server
    # reconnects (1 at the classic 1:1 topology).
    actors_per_server = args.num_actors // args.num_servers
    counters = chaos["counters"]
    expected = {
        # every chaos.<kind>.injected counter must match the plan...
        **{
            f"chaos.{kind}.injected": n
            for kind, n in plan_counts.items()
        },
        # ...and each fault class maps to its recovery counter exactly:
        # 1 respawn per SIGKILL, actors-per-server reconnects per
        # SIGKILL + 1 per sever, 1 rebuild+restart per poison.
        "recovery.server_restarts": n_kill,
        "recovery.actor_reconnects": (
            n_kill * actors_per_server + n_sever
        ),
        "recovery.inference_restarts": n_poison,
        "recovery.table_rebuilds": n_poison,
    }
    for name, want in expected.items():
        got = int(counters.get(name, 0))
        if got != want:
            failures.append(f"counter {name}: got {got}, want {want}")

    # -- load shedding: real AND lossless (ISSUE 14) ----------------------
    serving = {
        key: int(counters.get(f"serving.{key}", 0))
        for key in ("admitted", "shed", "expired", "resubmitted")
    }
    shed_total = serving["shed"] + serving["expired"]
    n_stall = plan_counts.get("learner_stall", 0)
    if serving["resubmitted"] != shed_total:
        failures.append(
            f"shed accounting broken: resubmitted {serving['resubmitted']}"
            f" != shed {serving['shed']} + expired {serving['expired']} "
            "(a shed was a lost request)"
        )
    if args.request_deadline_ms > 0 and n_stall > 0 and shed_total == 0:
        failures.append(
            "learner stall injected with the admission gate armed but "
            "nothing was shed (the overload path was not exercised)"
        )

    # -- no leaks ----------------------------------------------------------
    for run in (baseline, chaos):
        if run["leaked_processes"]:
            failures.append(
                f"{run['xpid']}: leaked processes "
                f"{run['leaked_processes']}"
            )
        if run["leaked_shm"]:
            failures.append(
                f"{run['xpid']}: leaked /dev/shm segments "
                f"{run['leaked_shm']}"
            )

    # -- ring-wait contrast (doorbell metastability baseline) --------------
    # Per-leg ring.doorbell_waits / ring.recheck_wakeups, with only the
    # chaos leg pressured when --scheduler_pressure > 0: a
    # recheck-heavy pressured leg against a doorbell-quiet baseline is
    # the signature the metastability investigation needs.
    ring = {
        "scheduler_pressure": args.scheduler_pressure,
        "baseline": {
            "doorbell_waits": int(
                baseline["counters"].get("ring.doorbell_waits", 0)
            ),
            "recheck_wakeups": int(
                baseline["counters"].get("ring.recheck_wakeups", 0)
            ),
        },
        "chaos": {
            "doorbell_waits": int(
                counters.get("ring.doorbell_waits", 0)
            ),
            "recheck_wakeups": int(
                counters.get("ring.recheck_wakeups", 0)
            ),
        },
    }

    verdict = {
        "bench": "chaos_run",
        "selftest": bool(args.selftest),
        "native": bool(args.native),
        "scale": args.scale,
        "num_actors": args.num_actors,
        "num_servers": args.num_servers,
        "request_deadline_ms": args.request_deadline_ms,
        "ok": not failures,
        "failures": failures,
        "env": args.env,
        "total_steps": args.total_steps,
        "plan": plan_dict,
        "expected_counters": expected,
        "serving": serving,
        "ring": ring,
        "results": {"baseline": baseline, "chaos": chaos},
        "telemetry": telemetry.telemetry_block(),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=2)
            f.write("\n")
    print(json.dumps(verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
