"""The whole async pipeline: polybeast with the native runtime, shm
pipes and spawned env servers, a closed loop of `num_actors` actors.

The launch is `chip_smoke.py:_poly_argv`'s recipe, copied. This process
holds the chip and runs `polybeast.main(flags)` on its main thread; a
sampler thread reads the pool's own env-step counter with its clock,
decides when the window opens (the rule is in the traffic file), takes
the facts at both ends of the window and then ends the run the way
Ctrl-C does (`_thread.interrupt_main`), which polybeast's monitor loop
takes as a clean finish.

A run leads a process group of its own, so that the env servers
polybeast spawns are in it; on every way out it kills what is left of
the group, unlinks the shm segments that appeared during the run and
removes its savedir. It refuses to start while a process of an earlier
run is alive.
"""

import _thread
import glob
import os
import shutil
import signal
import statistics
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from perfbench import common, manifest

RUN_TAG = "PERFBENCH_POLY_RUN"
SHM_DIR = "/dev/shm"
SHM_PREFIX = "tbtring_"


# ------------------------------------------------------ process hygiene


def _environ_of(pid: int) -> bytes:
    try:
        with open(f"/proc/{pid}/environ", "rb") as f:
            return f.read()
    except OSError:
        return b""


def tagged_processes(exclude: Tuple[int, ...] = ()) -> List[int]:
    """Processes that carry a poly run's tag in their environment."""
    found = []
    needle = RUN_TAG.encode() + b"="
    for entry in os.listdir("/proc"):
        if entry.isdigit() and int(entry) not in exclude:
            if needle in _environ_of(int(entry)):
                found.append(int(entry))
    return sorted(found)


def group_members(pgid: int, exclude: Tuple[int, ...] = ()) -> List[int]:
    members = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) in exclude:
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        if int(fields[2]) == pgid and fields[0] != "Z":
            members.append(int(entry))
    return sorted(members)


def kill_group(pgid: int, grace_s: float = 3.0) -> List[int]:
    """End every other member of the process group; returns the pids
    that had to be killed."""
    me = os.getpid()
    left = group_members(pgid, exclude=(me,))
    for pid in left:
        try:
            os.kill(pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + grace_s
    while time.monotonic() < deadline and group_members(pgid, (me,)):
        time.sleep(0.05)
    for pid in group_members(pgid, exclude=(me,)):
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + grace_s
    while time.monotonic() < deadline and group_members(pgid, (me,)):
        time.sleep(0.05)
    return left


def shm_segments() -> set:
    return set(glob.glob(os.path.join(SHM_DIR, SHM_PREFIX + "*")))


def lead_new_group() -> int:
    if os.getpgrp() != os.getpid():
        os.setpgid(0, 0)
    return os.getpgrp()


# ------------------------------------------------------- native runtime


def ensure_native() -> str:
    """`_tbt_core`, built from csrc/ into the work directory unless a
    build newer than its sources is there; returns the directory."""
    build = os.path.join(common.WORK_DIR, "native")
    sources = glob.glob(os.path.join(manifest.ROOT, "csrc", "*"))
    sources.append(os.path.join(manifest.ROOT, "setup.py"))
    built = glob.glob(os.path.join(build, "_tbt_core*.so"))
    newest = max(os.path.getmtime(p) for p in sources)
    if not built or os.path.getmtime(built[0]) < newest:
        shutil.rmtree(build, ignore_errors=True)
        os.makedirs(build)
        proc = subprocess.run(
            [sys.executable, "setup.py", "build_ext", "--force",
             "--build-lib", build,
             "--build-temp", os.path.join(build, "tmp")],
            cwd=manifest.ROOT, capture_output=True, text=True,
        )
        if proc.returncode != 0:
            sys.stderr.write(proc.stdout[-4000:] + proc.stderr[-4000:])
            raise RuntimeError(
                f"native build failed (rc={proc.returncode})"
            )
    if build not in sys.path:
        sys.path.insert(0, build)
    return build


# ----------------------------------------------------------- the window


def steady(rates: List[float], share: float) -> bool:
    """Each of the rates lies within `share` of their own mean."""
    mean = statistics.fmean(rates)
    return mean > 0 and all(abs(r - mean) <= share * mean for r in rates)


def per_second(samples: List[Tuple[float, float]], start: float,
               end: float) -> List[float]:
    """Differences of a cumulative count over whole seconds from
    `start`, the count at each second's edge read off the last sample
    at or before it. `samples` are (time, count), sorted."""
    edges, out = [], []
    index = 0
    t = start
    while t <= end + 1e-9:
        while index + 1 < len(samples) and samples[index + 1][0] <= t:
            index += 1
        edges.append(samples[index])
        t += 1.0
    for (ta, ca), (tb, cb) in zip(edges, edges[1:]):
        if tb > ta:
            out.append((cb - ca) / (tb - ta))
    return out


def _hist_state(hist) -> Dict:
    merged = hist.merged()
    return {
        "count": merged.count, "total": merged.total,
        "buckets": dict(merged.buckets),
    }


def _hist_delta(after: Dict, before: Dict) -> Dict:
    buckets = {
        str(k): v - before["buckets"].get(k, 0)
        for k, v in after["buckets"].items()
        if v - before["buckets"].get(k, 0) > 0
    }
    return {
        "count": after["count"] - before["count"],
        "total": after["total"] - before["total"],
        "buckets": buckets,
    }


class Sampler(threading.Thread):
    """Reads the program's counters while polybeast runs; opens and
    closes the window; ends the run."""

    def __init__(self, cell, seconds, trace, meter, captured):
        super().__init__(name="perfbench-sampler", daemon=True)
        self.cell = cell
        self.seconds, self.trace = seconds, trace
        self.meter, self.captured = meter, captured
        self.rule = cell.traffic["window"]
        self.result: Optional[Dict] = None
        self.error: Optional[BaseException] = None

    # One read: the pool's own counter together with its clock.
    def _read(self) -> Tuple[float, Dict]:
        counters = self.pool.telemetry()
        return time.monotonic(), counters

    def _registry_state(self) -> Dict:
        from torchbeast_tpu import telemetry
        from torchbeast_tpu.telemetry.metrics import Counter, Histogram

        state = {"counters": {}, "histograms": {}}
        for name, inst in telemetry.get_registry().instruments().items():
            if isinstance(inst, Counter):
                state["counters"][name] = inst.value()
            elif isinstance(inst, Histogram):
                state["histograms"][name] = _hist_state(inst)
        return state

    def run(self):
        try:
            self.result = self._measure()
        except BaseException as e:  # noqa: BLE001 - reported by the run
            self.error = e
        # polybeast's own way out on Ctrl-C: the monitor loop, which
        # sleeps in 5 s ticks on the main thread, wakes at once, closes
        # the queues, joins its threads and reaps its servers.
        _thread.interrupt_main()

    def _measure(self) -> Dict:
        from torchbeast_tpu import telemetry

        rule = self.rule
        deadline = time.monotonic() + float(rule["pool_wait_s"])
        while "folder" not in self.captured:
            if time.monotonic() > deadline:
                raise RuntimeError("the actor pool never came up")
            time.sleep(0.05)
        folder = self.captured["folder"]
        self.pool = folder._pool
        registry = telemetry.get_registry()
        updates = registry.counter("learner.updates")
        batch_hist = registry.histogram("inference.batch_size")
        period = float(rule["sample_period_s"])

        steps: List[Tuple[float, float]] = []
        batches: List[Tuple[float, float, float]] = []
        update_times: List[float] = []
        seen_updates = updates.value()
        first_step_at = None
        window = None  # (t0, counters0, registry0, setup_s, opened_by)
        tracer = None
        compiles0 = 0
        while True:
            now, counters = self._read()
            steps.append((now, counters["env_steps"]))
            state = batch_hist.merged()
            batches.append((now, state.count, state.total))
            done = updates.value()
            if done > seen_updates:
                update_times.extend([now] * int(done - seen_updates))
                seen_updates = done
            if first_step_at is None and counters["env_steps"] > 0:
                first_step_at = now
            if window is None and first_step_at is not None:
                opened_by = None
                if len(update_times) >= int(rule["min_updates"]):
                    n = int(rule["steady_seconds"])
                    rates = per_second(steps, now - n, now)
                    if (
                        now - first_step_at >= n and len(rates) == n
                        and steady(rates, float(rule["steady_share"]))
                    ):
                        opened_by = "steady"
                if (
                    opened_by is None
                    and now - first_step_at >= float(rule["max_wait_s"])
                ):
                    opened_by = "max_wait"
                if opened_by is not None:
                    folder.tick()
                    registry0 = self._registry_state()
                    compiles0 = self.meter.requests
                    setup_s = common.seconds_since_process_start()
                    t0, counters0 = self._read()
                    steps.append((t0, counters0["env_steps"]))
                    window = (t0, counters0, registry0, setup_s, opened_by)
                    tracer = common.TraceWindow(
                        self.trace, self.cell.name, t0, self.seconds,
                        float(self.cell.traffic["trace_seconds"]),
                    )
            if window is not None:
                tracer.poll(now)
                if now - window[0] >= self.seconds:
                    break
            time.sleep(period)

        t1, counters1 = self._read()
        steps.append((t1, counters1["env_steps"]))
        folder.tick()
        registry1 = self._registry_state()
        compiles = self.meter.requests - compiles0
        reduced = tracer.finish()
        t0, counters0, registry0, setup_s, opened_by = window

        in_window = [t for t in update_times if t0 <= t <= t1]
        intervals = [b - a for a, b in zip(in_window, in_window[1:])]
        batch_series = []
        edges = per_second([(t, c) for t, c, _ in batches], t0, t1)
        totals = per_second([(t, s) for t, _, s in batches], t0, t1)
        for count, total in zip(edges, totals):
            batch_series.append(total / count if count else 0.0)
        histograms = {
            k: _hist_delta(v, registry0["histograms"].get(
                k, {"count": 0, "total": 0.0, "buckets": {}}
            ))
            for k, v in registry1["histograms"].items()
        }
        facts = {
            "counters": dict(
                {
                    k: v - registry0["counters"].get(k, 0.0)
                    for k, v in registry1["counters"].items()
                },
                **{
                    "pool." + k: counters1[k] - counters0[k]
                    for k in counters1
                },
            ),
            "histograms": histograms,
            "values": {
                "window_s": t1 - t0,
                "window_compiles": compiles,
                "updates": len(in_window),
                "update_interval_max_s": max(intervals) if intervals else None,
                "learner_wait_s": histograms.get(
                    "learner_queue.dequeue_wait_s", {}
                ).get("total"),
            },
            "trace": reduced,
        }
        return {
            "t0": t0, "t1": t1, "setup_s": setup_s, "opened_by": opened_by,
            "warmup_s": t0 - first_step_at,
            "env_steps": counters1["env_steps"] - counters0["env_steps"],
            "facts": facts,
            "series": {
                "env_steps_per_s": per_second(steps, t0, t1),
                "infer_batch_mean": batch_series,
                "update_intervals_s": intervals,
                "env_steps_per_s_before_window": per_second(
                    steps, first_step_at, t0
                ),
            },
        }


# -------------------------------------------------------------- the run


def _argv(cell, savedir: str, seed: int) -> List[str]:
    config, traffic = cell.config, cell.traffic
    program_seed = seed % common.PROGRAM_SEED_MODULUS
    return list(config["program_argv"]) + [
        "--env", traffic["env"],
        "--unroll_length", str(config["unroll_length"]),
        "--batch_size", str(config["batch_size"]),
        "--num_actors", str(traffic["num_actors"]),
        "--num_servers", str(traffic["num_servers"]),
        "--native_runtime",
        "--pipes_basename", f"shm:{savedir}/pipes",
        # The sampler ends the run; nothing else does.
        "--total_steps", str(2**31 - 1),
        "--checkpoint_interval_s", str(10**9),
        "--savedir", savedir, "--xpid", "run",
        "--seed", str(program_seed),
        "--env_seed", str(program_seed),
    ] + list(traffic.get("program_argv", []))


def run(cell, seed: int, seconds: float, trace: bool, devices, meter):
    import numpy as np

    stale = tagged_processes(exclude=(os.getpid(),))
    if stale:
        raise RuntimeError(
            f"processes of an earlier poly run are alive: {stale}; "
            "this run would share the host with them"
        )
    pgid = lead_new_group()
    os.environ[RUN_TAG] = str(os.getpid())
    shm_before = shm_segments()
    savedir = common.fresh_dir("runs", cell.name)
    killed: List[int] = []
    try:
        ensure_native()
        from torchbeast_tpu import polybeast
        from torchbeast_tpu.runtime import native

        captured: Dict = {}

        class CapturingFolder(native.NativeTelemetryFolder):
            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                captured["folder"] = self

        flags = polybeast.make_parser().parse_args(
            _argv(cell, savedir, seed)
        )
        sampler = Sampler(cell, seconds, trace, meter, captured)
        original = native.NativeTelemetryFolder
        native.NativeTelemetryFolder = CapturingFolder
        try:
            sampler.start()
            try:
                stats = polybeast.main(flags)
            except KeyboardInterrupt:
                # The interrupt landed outside polybeast's own handler.
                if sampler.error is not None:
                    raise sampler.error from None
                stats = {}
        finally:
            native.NativeTelemetryFolder = original
        sampler.join(timeout=30)
        if sampler.error is not None:
            raise sampler.error
        if sampler.result is None:
            raise RuntimeError(
                "polybeast ended before the window closed "
                f"(health {stats.get('health')})"
            )
    finally:
        killed = kill_group(pgid)
        leaked = sorted(shm_segments() - shm_before)
        for path in leaked:
            try:
                os.unlink(path)
            except OSError:
                pass
        shutil.rmtree(savedir, ignore_errors=True)
        os.environ.pop(RUN_TAG, None)

    measured = sampler.result
    facts = measured["facts"]
    window_s = facts["values"]["window_s"]
    device = common.device_report(devices)
    facts["values"]["peak_hbm_gib"] = device["memory_peak_bytes"] / 2**30
    recovered = {
        k: v for k, v in facts["counters"].items()
        if v and (
            k.startswith("recovery.")
            or k in ("pool.reconnects", "pool.batch_retries")
        )
    }
    loss = float(stats.get("total_loss", float("nan")))
    correct = (
        stats.get("health") == "HEALTHY"
        and not recovered
        and bool(np.isfinite(loss))
        and facts["values"]["window_compiles"] == 0
        and measured["env_steps"] > 0
        and facts["values"]["updates"] > 0
    )
    return {
        "correct": bool(correct),
        "attempted": int(measured["env_steps"]),
        "failed": int(sum(recovered.values())),
        "end_to_end": {
            "env_frames_per_s": measured["env_steps"] / window_s,
            "setup_s": measured["setup_s"],
        },
        "facts": facts,
        "device": device,
        "series": measured["series"],
        "notes": {
            "window_opened_by": measured["opened_by"],
            "warmup_s": measured["warmup_s"],
            "window_s": window_s,
            "health": stats.get("health"),
            "total_loss": loss,
            "recovered": recovered,
            "killed_at_exit": killed,
            "shm_unlinked_at_exit": leaked,
            "affinity_cores": len(os.sched_getaffinity(0)),
        },
    }
