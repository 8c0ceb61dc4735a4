"""End-to-end async-driver system benchmark on the ambient accelerator.

Runs the FULL polybeast stack — env-server processes, actor loops,
DynamicBatcher inference (bucket-padded, pipelined dispatch), the
BatchingQueue learner with prefetch — against the ambient backend (the
real TPU under the driver) and records the SYSTEM numbers the isolated
kernel benches can't show: end-to-end SPS, queue depths over time, and
the Timings breakdown. This is the balanced-pipeline evidence the
reference's design centers on (its 5-second queue telemetry loop,
polybeast_learner.py:553-579).

Usage: python benchmarks/tpu_e2e_async.py [--total_steps N] [--mock]
Writes the captured log to --out (default /tmp/tbt_e2e.log) and prints
a one-line JSON summary (steady-state SPS over the last half of the
run, mean queue depths) with the run's final telemetry snapshot
embedded (read from {savedir}/{xpid}/telemetry.jsonl — structured
JSON, not log scraping; the acting-path wire accounting rides its
`acting_path` block).

`--compare_native` (ISSUE 9 acceptance) runs the SAME workload twice —
the Python runtime over sockets, then the C++ runtime over shm rings
(slot framing + --superstep_k both legs) — and emits both columns plus
the native/python steady-SPS ratio, gated >= 1.5x at >= 8 actors. The
verdict is written to --artifact (default
benchmarks/artifacts/native_parity_bench.json).

One process per chip: this launcher never initialises a JAX backend.
Each leg's polybeast child is the one process that holds the chip, and
legs run strictly one after another. `--fleet_hosts N` starts N drivers
at once, which one chip cannot serve — it is a forced-CPU lane and
requires `--xla_device_count`.
"""

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_ARTIFACT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "artifacts",
    "native_parity_bench.json",
)

LOG_RE = re.compile(
    r"Step (\d+) @ ([\d.]+) SPS\. Inference batcher size: (\d+)\. "
    r"Learner queue size: (\d+)\."
)


def _free_port_pair():
    """A port P with P+1 also free: the fleet's coord= endpoint needs
    both (rendezvous at P, control plane at P+1 — fleet/topology.py)."""
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


def run_config(args, native, shm, log_path, tag):
    """One full polybeast run; returns the summary dict (None SPS rows
    -> error dict)."""
    savedir = "/tmp/tbt_e2e_save"
    xpid = f"e2e-{tag}-{int(time.time())}"
    pipes = (
        f"shm:/tmp/tbt_e2e_pipe_{tag}" if shm
        else f"unix:/tmp/tbt_e2e_pipe_{tag}"
    )
    cmd = [
        sys.executable, "-m", "torchbeast_tpu.polybeast",
        "--env", args.env,
        "--model", args.model,
        "--num_servers", str(args.num_servers),
        "--num_actors", str(args.num_actors),
        "--batch_size", str(args.batch_size),
        "--unroll_length", str(args.unroll_length),
        "--total_steps", str(args.total_steps),
        "--superstep_k", str(args.superstep_k),
        "--savedir", savedir,
        "--xpid", xpid,
        "--pipes_basename", pipes,
        "--prewarm_inference",  # no mid-run compile stalls in telemetry
    ]
    if args.use_lstm:
        cmd += ["--use_lstm"]
    # The runtime is pinned EXPLICITLY either way (chaos_run.py's
    # convention): since the ISSUE 14 native-first default flip, a leg
    # that merely omits --native_runtime would silently run the C++
    # pool — and a "python baseline" that is secretly native corrupts
    # every ratio this bench publishes.
    if native:
        cmd += ["--native_runtime"]
        if args.native_server:
            cmd += ["--native_server"]
    else:
        cmd += ["--no_native_runtime"]
    if args.no_device_agent_state:
        cmd += ["--no_device_agent_state"]
    if getattr(args, "device_split", ""):
        cmd += ["--device_split", args.device_split]
    n_learn = getattr(args, "num_learner_devices", 0) or 0
    if n_learn > 1:
        cmd += ["--num_learner_devices", str(n_learn)]
    # Caller-owned flag passthrough (capacity_bench rides this for
    # --replica_refresh_updates / --no_continuous_batching): run_config
    # stays the single subprocess harness instead of forking a copy per
    # bench that needs one more flag.
    cmd += [str(f) for f in getattr(args, "extra_flags", ()) or ()]

    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + ":" + env.get("PYTHONPATH", "")
    # Forced host devices (the Sebulba scaling curve's CPU lane): the
    # child sees N virtual devices; the flag replaces any inherited
    # count so legs can't leak their topology into each other.
    n_forced = getattr(args, "xla_device_count", 0) or 0
    if n_forced:
        flags_env = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            env.get("XLA_FLAGS", ""),
        ).strip()
        env["XLA_FLAGS"] = (
            f"{flags_env} "
            f"--xla_force_host_platform_device_count={n_forced}"
        ).strip()
        env["JAX_PLATFORMS"] = "cpu"
    # Multi-host fleet lane (ISSUE 17): N polybeast processes, each a
    # fleet host over the SAME workload flags, composed through the
    # coord= control plane. Remotes launch first (they Backoff-dial the
    # lead), the lead last; rank 0's log/telemetry remain the parsed
    # "main" run and the remotes' final snapshots ride the summary.
    fleet_hosts = getattr(args, "fleet_hosts", 0) or 0
    remote_procs = []  # (rank, Popen, logfile)
    if fleet_hosts >= 2:
        coord = f"127.0.0.1:{_free_port_pair()}"
        base_cmd = list(cmd)
        cmd = base_cmd + ["--fleet", f"host=0/{fleet_hosts},coord={coord}"]
        for rank in range(1, fleet_hosts):
            rcmd = base_cmd + [
                "--fleet", f"host={rank}/{fleet_hosts},coord={coord}",
            ]
            rlogf = open(f"{log_path}.host{rank}", "w")
            remote_procs.append((
                rank,
                subprocess.Popen(
                    rcmd, env=env, stdout=rlogf, stderr=subprocess.STDOUT,
                    cwd=_REPO, start_new_session=True,
                ),
                rlogf,
            ))
    # Each leg runs in its own process group and the WHOLE group is
    # killed on timeout: the driver's spawned env-server children
    # otherwise outlive the timeout kill and poison the next leg's
    # numbers with stolen CPU (observed: 8 orphaned servers from leg 1
    # running through leg 2 on a 2-core box flipped the verdict).
    shm_before = set(os.listdir("/dev/shm")) if os.path.isdir("/dev/shm") else set()
    t0 = time.time()
    timed_out = False
    rc = None
    remote_rcs = {}
    with open(log_path, "w") as logf:
        proc = subprocess.Popen(
            cmd, env=env, stdout=logf, stderr=subprocess.STDOUT,
            cwd=_REPO, start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=args.timeout_s)
            # Remotes finish their own --total_steps around the same
            # time; a short grace covers their checkpoint/teardown.
            for rank, rproc, _ in remote_procs:
                try:
                    remote_rcs[rank] = rproc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    timed_out = True
        except subprocess.TimeoutExpired:
            # The log up to the kill still holds steady-state telemetry
            # — summarize it rather than dying without the JSON line.
            timed_out = True
        finally:
            for _, rproc, rlogf in remote_procs:
                try:
                    os.killpg(rproc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                rproc.wait()
                rlogf.close()
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            proc.wait()
    # SIGKILL skips the drivers' shm hygiene — sweep segments created
    # during this leg so they don't accumulate across legs/runs. Only
    # names the drivers can create (psm_* from Python SharedMemory,
    # tbtring_* from csrc/shm.h): a set-difference alone would also
    # unlink segments an unrelated process created during the leg.
    # psm_* is still multiprocessing's global default prefix, so this
    # sweep — like the SPS measurement itself — assumes the box runs
    # nothing else during a leg.
    if os.path.isdir("/dev/shm"):
        created = set(os.listdir("/dev/shm")) - shm_before
        for name in created:
            if not name.startswith(("psm_", "tbtring_")):
                continue
            try:
                os.unlink(os.path.join("/dev/shm", name))
            except OSError:
                pass
    wall = time.time() - t0

    rows = []
    with open(log_path) as f:
        for line in f:
            m = LOG_RE.search(line)
            if m:
                rows.append(tuple(float(x) for x in m.groups()))

    # Structured telemetry from the run's own exporter (queue depths,
    # batch-size distribution p50/p95, stage latencies, wire-byte
    # counters, and the acting-path accounting) — the attribution data
    # the SPS log rows can't carry.
    from torchbeast_tpu import telemetry

    snaps = telemetry.read_jsonl(
        os.path.join(savedir, xpid, "telemetry.jsonl")
    )
    final_snap = snaps[-1] if snaps else None
    # Remote fleet hosts write their own streams at {xpid}-host<r> (the
    # driver's per-host FileWriter suffix); their final snapshots carry
    # the wire-delivery evidence (serving.snapshot_version > 0 with no
    # local publishes, non-zero serving.policy_lag).
    remote_hosts = None
    if fleet_hosts >= 2:
        remote_hosts = {}
        for rank in range(1, fleet_hosts):
            rsnaps = telemetry.read_jsonl(
                os.path.join(savedir, f"{xpid}-host{rank}",
                             "telemetry.jsonl")
            )
            remote_hosts[str(rank)] = {
                "rc": remote_rcs.get(rank),
                "telemetry_lines": len(rsnaps),
                "snapshot": rsnaps[-1] if rsnaps else None,
                "log": f"{log_path}.host{rank}",
            }
    acting = final_snap.get("acting_path") if final_snap else None
    # Steady SPS from the snapshot timestamps (learner step delta over
    # wall time, first third discarded as warmup) — the per-tick log SPS
    # samples alias the monitor cadence and read noisy on a loaded box.
    steady_sps_telemetry = None
    mid_snap = snaps[len(snaps) // 3] if len(snaps) >= 3 else None
    if (
        mid_snap is not None
        and final_snap.get("step") is not None
        and mid_snap.get("step") is not None
        and final_snap["time"] > mid_snap["time"]
    ):
        steady_sps_telemetry = round(
            (final_snap["step"] - mid_snap["step"])
            / (final_snap["time"] - mid_snap["time"]),
            1,
        )
    # Ring-wait counters (ISSUE 12/15, ROADMAP item 1): the adaptive
    # doorbell recheck's metastability signature — committed with the
    # parity artifact so the counters have an in-anger baseline.
    ring = None
    if final_snap:
        counters = final_snap.get("counters", {})
        ring = {
            k: int(counters[k])
            for k in ("ring.doorbell_waits", "ring.recheck_wakeups")
            if k in counters
        } or None
    if not rows:
        return {
            "error": f"no telemetry rows parsed (rc={rc}, "
                     f"timed_out={timed_out})",
            "log": log_path,
        }
    steady = rows[len(rows) // 2:]
    sps = [r[1] for r in steady]
    inf_q = [r[2] for r in steady]
    lrn_q = [r[3] for r in steady]
    return {
        "config": {
            **{
                k: getattr(args, k, None)
                for k in ("env", "model", "use_lstm", "num_servers",
                          "num_actors", "batch_size", "unroll_length",
                          "total_steps", "superstep_k",
                          "no_device_agent_state", "device_split")
            },
            "native": native,
            "transport": "shm" if shm else "socket",
            "fleet_hosts": fleet_hosts or None,
        },
        "rc": rc,
        "timed_out": timed_out,
        "wall_s": round(wall, 1),
        "steady_sps_mean": round(sum(sps) / len(sps), 1),
        "steady_sps_max": round(max(sps), 1),
        "steady_sps_telemetry": steady_sps_telemetry,
        "inference_q_mean": round(sum(inf_q) / len(inf_q), 2),
        "learner_q_mean": round(sum(lrn_q) / len(lrn_q), 2),
        # Acting-path wire accounting from the run's telemetry snapshot:
        # which side holds agent state and what crosses per step.
        "acting_path": acting,
        # shm doorbell-wait counters (None on socket transports).
        "ring": ring,
        # The run's final cumulative telemetry snapshot — bench variance
        # is attributable (queue wait vs batch wait vs dispatch) without
        # re-running under a profiler.
        "telemetry": {
            "enabled": final_snap is not None,
            "snapshot": final_snap,
            # The warmup-boundary snapshot the steady-SPS window starts
            # at — counter deltas (final - mid) / (time delta) give
            # steady per-second rates for any cumulative series.
            "mid_snapshot": mid_snap,
        },
        "telemetry_lines": len(snaps),
        "n_telemetry_rows": len(rows),
        # Per-remote-host final snapshots (fleet runs only, None
        # otherwise): the cross-host acceptance evidence.
        "remote_hosts": remote_hosts,
        "log": log_path,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--total_steps", type=int, default=400_000)
    ap.add_argument("--num_servers", type=int, default=16)
    ap.add_argument("--num_actors", type=int, default=32)
    ap.add_argument("--batch_size", type=int, default=8)
    ap.add_argument("--unroll_length", type=int, default=40)
    ap.add_argument("--superstep_k", type=int, default=1,
                    help="Learner superstep K (both runtimes).")
    ap.add_argument("--model", default="shallow")
    ap.add_argument("--use_lstm", action="store_true",
                    help="Recurrent core — exercises the device state "
                         "table (slot framing) on the acting path.")
    ap.add_argument("--env", default="Mock")
    ap.add_argument("--native", action="store_true",
                    help="C++ queues/pool (+ C++ env server with "
                         "--native_server)")
    ap.add_argument("--native_server", action="store_true",
                    help="With --native: serve envs from the C++ "
                         "EnvServer too (default: Python servers — the "
                         "comparison isolates the runtime choice on the "
                         "learner side).")
    ap.add_argument("--shm", action="store_true",
                    help="shm: pipes (shared-memory rings) instead of "
                         "unix sockets.")
    ap.add_argument("--compare_native", action="store_true",
                    help="Run python+socket vs native+shm at the same "
                         "workload and emit the >=1.5x acceptance "
                         "verdict (ISSUE 9).")
    ap.add_argument("--no_device_agent_state", action="store_true",
                    help="Legacy acting path (agent state rides every "
                         "inference request/reply) — for before/after "
                         "comparison against the device-resident table.")
    ap.add_argument("--device_split", default="",
                    help="Forwarded to polybeast: the Sebulba device "
                         "split spec ('auto' / 'inf=K,learn=rest|M'; "
                         "Python runtime). Combine with "
                         "--xla_device_count for a forced-host-device "
                         "CPU lane.")
    ap.add_argument("--fleet_hosts", type=int, default=0,
                    help="Run N polybeast processes as a multi-host "
                         "fleet (--fleet host=<r>/N over a free "
                         "127.0.0.1 coord port; ISSUE 17). Rank 0 is "
                         "the parsed run; remote hosts' final "
                         "telemetry snapshots ride the summary under "
                         "remote_hosts. 0/1 = single process.")
    ap.add_argument("--xla_device_count", type=int, default=0,
                    help="Run the child with JAX_PLATFORMS=cpu and N "
                         "forced host devices (XLA_FLAGS "
                         "--xla_force_host_platform_device_count=N). "
                         "0 = inherit the ambient backend.")
    ap.add_argument("--out", default="/tmp/tbt_e2e.log")
    ap.add_argument("--artifact", default=_ARTIFACT,
                    help="Comparison-verdict artifact path ('' skips "
                         "the write; --compare_native only).")
    ap.add_argument("--timeout_s", type=int, default=1500)
    args = ap.parse_args()
    if args.fleet_hosts >= 2 and not args.xla_device_count:
        ap.error(
            "--fleet_hosts starts several drivers at once; a chip "
            "belongs to one process, so pass --xla_device_count N to "
            "run the fleet lane on forced CPU devices"
        )

    if not args.compare_native:
        summary = run_config(
            args, native=args.native, shm=args.shm, log_path=args.out,
            tag="native" if args.native else "python",
        )
        print(json.dumps(summary))
        if "error" in summary:
            sys.exit(1)
        return

    # ISSUE 9 acceptance: native+shm+slots+K vs python+socket, same
    # workload, >= 8 actor processes. (The python leg runs over unix
    # sockets — faster than TCP loopback, so the gate is conservative.)
    baseline = run_config(
        args, native=False, shm=False, log_path=args.out + ".python",
        tag="cmp-python",
    )
    native = run_config(
        args, native=True, shm=True, log_path=args.out + ".native",
        tag="cmp-native",
    )
    ratio = None
    if "error" not in baseline and "error" not in native:
        base_sps = (
            baseline["steady_sps_telemetry"] or baseline["steady_sps_mean"]
        )
        native_sps = (
            native["steady_sps_telemetry"] or native["steady_sps_mean"]
        )
        ratio = native_sps / base_sps if base_sps else None
    out = {
        "bench": "native_parity_e2e",
        "baseline_python_socket": baseline,
        "native_shm": native,
        "native_speedup": round(ratio, 3) if ratio else None,
        "acceptance": {
            "min_actors": args.num_actors,
            "superstep_k": args.superstep_k,
            "required_speedup": 1.5,
            "ok": bool(ratio and ratio >= 1.5 and args.num_actors >= 8),
        },
    }
    if args.artifact:
        os.makedirs(os.path.dirname(args.artifact) or ".", exist_ok=True)
        with open(args.artifact, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
            f.write("\n")
    print(json.dumps(out))
    # Same machine-checkable contract as the single-run branch: a CI
    # lane gating on exit status must see the failed leg / missed gate.
    if not out["acceptance"]["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
