"""A heartbeat that tells a process the host did not run from one whose
Python side waited for its own interpreter lock.

One daemon thread sleeps `PERIOD_S` (the interpreter's switch interval)
in a loop and observes how late it woke, `host.heartbeat_lag_s`. In a
quiet process the lag is the time to get the GIL back, so its mean is
the GIL pressure that every Python-side span of the process includes.

A lag above `STALL_S` counts as a stall (`host.stalls`), with the wall
time (`host.stall_wall_s`) and the CPU time of all the process's
threads (`host.stall_cpu_s`, `time.process_time()`) that passed in that
round. CPU near zero over a stall: the process was not run (the host's
doing), or a thread slept in native code holding the GIL. CPU of about
the stall's length or more: the program was busy under the GIL (a
compile, a checkpoint, a long native call), which is the program's to
cure.

`ThreadLedger` is the kernel's own account of the same process: every
task's CPU time and the time it spent runnable with no core
(`/proc/<pid>/task/<tid>/schedstat`: on-CPU and run-queue nanoseconds;
`stat`'s utime + stime where the kernel keeps no schedstat), credited by role to the counters `host.cpu_s.<role>` and
`host.run_delay_s.<role>` as deltas between folds. It reads /proc when
it is asked to (`fold()`: a telemetry tick, at most every
`LEDGER_PERIOD_S`, and whoever calls the native telemetry folder's
`tick()` itself), never on a serving thread. stdlib only.
"""

import os
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from torchbeast_tpu.telemetry.metrics import MetricsRegistry

PERIOD_S = 0.005
STALL_S = 0.1
# The least time between two folds of the thread ledger made for a
# periodic snapshot (a fold someone asks for by name is always made):
# a fold is a thousand system calls on a large process, each of which
# hands the GIL over and back, and on a sandboxed kernel costs 5-17 us.
LEDGER_PERIOD_S = 30.0

# A Python thread's role, from the name it was started under (first
# match; the replier is named after its launcher, so it comes first):
# runtime/inference.py's replier, resilience/supervisor.py's serving
# threads, polybeast's learner, runtime/queues.py's DevicePrefetcher.
# Every other Python thread (main/monitor, heartbeat, supervisors,
# watchdogs, the thread that waits in ActorPool.run) is `python_other`.
_PYTHON_ROLES: Tuple[Tuple[str, Callable[[str], bool]], ...] = (
    ("replier", lambda name: name.endswith("-replier")),
    ("launcher", lambda name: name.startswith("inference")),
    ("learner", lambda name: name == "learner"),
    ("prefetch", lambda name: name == "device-prefetch"),
)
# A thread Python did not start, from the name the kernel has for it
# (`comm`): csrc/actor_pool.h names its loops; the rest (the XLA
# runtime's and the driver's pools) is `native_other`.
_NATIVE_ROLES: Tuple[Tuple[str, str], ...] = (("tbt-actor", "actors"),)
ROLES = tuple(role for role, _ in _PYTHON_ROLES) + (
    "python_other", "actors", "native_other",
)
# The role of every task of the processes `watch()` names and of their
# descendants (polybeast: the env-server listeners and the stream
# children they fork).
WATCHED_ROLE = "env_servers"


def thread_role(name: Optional[str], comm: str) -> str:
    """`name`: the thread's Python name, None for a thread Python did
    not start; `comm`: the kernel's name of the task."""
    if name is None:
        for prefix, role in _NATIVE_ROLES:
            if comm.startswith(prefix):
                return role
        return "native_other"
    for role, matches in _PYTHON_ROLES:
        if matches(name):
            return role
    return "python_other"


def _read(path: str) -> Optional[str]:
    """A /proc file's text, None once its task is gone (or where the
    kernel has no such file)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return None
    try:
        return os.read(fd, 4096).decode("ascii", "replace")
    except OSError:
        return None
    finally:
        os.close(fd)


class ThreadLedger:
    """CPU seconds and run-queue seconds of this process's threads by
    role, and of the watched process trees, as registry counters.

    A fold credits each task's growth since the fold before (a task
    seen for the first time: all it has, which it can only have used
    since then, or since the process began at the first fold). A task
    that ended between two folds takes its last slice with it. Where
    the kernel keeps no `schedstat` the run-delay counters are not
    registered: a permanent zero would read as "never waits".
    """

    def __init__(self, registry: MetricsRegistry, proc_root: str = "/proc",
                 pid: Optional[int] = None,
                 threads: Callable[[], Iterable] = threading.enumerate):
        self._registry = registry
        self._proc = proc_root
        self._pid = os.getpid() if pid is None else pid
        self._threads = threads
        self._tick_s = 1.0 / os.sysconf("SC_CLK_TCK")
        self._lock = threading.Lock()
        # (pid, tid) -> (cpu_s, run_delay_s) at the last fold
        self._prev: Dict[Tuple[int, int], Tuple[float, float]] = {}  # guarded-by: self._lock
        self._last_fold: Optional[float] = None  # guarded-by: self._lock
        self._watched: Optional[Callable[[], Iterable[int]]] = None
        task = f"{proc_root}/{self._pid}/task/{self._pid}"
        self.has_schedstat = _read(f"{task}/schedstat") is not None
        self._has_children = _read(f"{task}/children") is not None
        self._cpu: Dict[str, object] = {}
        self._delay: Dict[str, object] = {}
        for role in ROLES:
            self._register(role)

    def _register(self, role: str) -> None:
        self._cpu[role] = self._registry.counter(f"host.cpu_s.{role}")
        if self.has_schedstat:
            self._delay[role] = self._registry.counter(
                f"host.run_delay_s.{role}"
            )

    def watch(self, pids: Callable[[], Iterable[int]]) -> None:
        """Credit the processes `pids()` names at each fold, and every
        descendant of theirs, to `host.*.env_servers`."""
        self._register(WATCHED_ROLE)
        self._watched = pids

    def _tasks(self, pid: int, named: Optional[Dict[int, str]] = None
               ) -> List[Tuple[int, Optional[str], float, float]]:
        """(tid, comm, cpu_s, run_delay_s) of every task of `pid` that
        is still there. CPU is `schedstat`'s on-CPU nanoseconds where
        the kernel keeps them, else `stat`'s utime + stime in clock
        ticks (the same total, coarser). `comm` is read for the tasks
        `named` lacks, None for the others (and for all without
        `named`)."""
        base = f"{self._proc}/{pid}/task"
        try:
            tids = os.listdir(base)
        except OSError:
            return []
        out = []
        for entry in tids:
            tid, comm = int(entry), None
            need_comm = named is not None and tid not in named
            if self.has_schedstat:
                sched = _read(f"{base}/{entry}/schedstat")
                if sched is None:
                    continue
                on_cpu, waited = sched.split()[:2]
                cpu, delay = int(on_cpu) * 1e-9, int(waited) * 1e-9
                if need_comm:
                    comm = (_read(f"{base}/{entry}/comm") or "").strip()
            else:
                stat = _read(f"{base}/{entry}/stat")
                if stat is None:
                    continue
                # "tid (comm) state ppid ...": comm may hold spaces
                # and brackets, so split at the last one.
                head, _, rest = stat.rpartition(")")
                fields = rest.split()
                cpu = (int(fields[11]) + int(fields[12])) * self._tick_s
                delay = 0.0
                if need_comm:
                    comm = head.partition("(")[2]
            out.append((tid, comm, cpu, delay))
        return out

    def _parents(self) -> Dict[int, List[int]]:
        """ppid -> pids, over all of /proc: the way to a process's
        children on a kernel without `task/<tid>/children`."""
        children: Dict[int, List[int]] = {}
        for entry in os.listdir(self._proc):
            if not entry.isdigit():
                continue
            stat = _read(f"{self._proc}/{entry}/stat")
            if stat is not None:
                ppid = int(stat.rpartition(")")[2].split()[1])
                children.setdefault(ppid, []).append(int(entry))
        return children

    def _tree(self, roots: Iterable[int]) -> Iterable[int]:
        """`roots` and all their descendants."""
        parents = None if self._has_children else self._parents()
        found, queue = set(), list(roots)
        while queue:
            pid = queue.pop()
            if pid is None or pid in found:
                continue
            found.add(pid)
            if parents is not None:
                queue.extend(parents.get(pid, ()))
                continue
            base = f"{self._proc}/{pid}/task"
            try:
                tids = os.listdir(base)
            except OSError:
                continue
            for tid in tids:
                listed = _read(f"{base}/{tid}/children")
                if listed:
                    queue.extend(int(child) for child in listed.split())
        return found

    # beastlint: holds self._lock
    def _credit(self, role, key, cpu, delay, seen) -> None:
        before_cpu, before_delay = self._prev.get(key, (0.0, 0.0))
        if cpu < before_cpu:  # the id went to a new task
            before_cpu = before_delay = 0.0
        seen[key] = (cpu, delay)
        if cpu > before_cpu:
            self._cpu[role].inc(cpu - before_cpu)
        if delay > before_delay:
            self._delay[role].inc(delay - before_delay)

    def fold(self, min_interval_s: float = 0.0) -> None:
        """Credit every counter with what passed since the last fold;
        skipped where the last one is younger than `min_interval_s`."""
        with self._lock:
            now = time.monotonic()
            if (
                self._last_fold is not None
                and now - self._last_fold < min_interval_s
            ):
                return
            self._last_fold = now
            seen: Dict[Tuple[int, int], Tuple[float, float]] = {}
            # A thread Python did not start stands in enumerate() too
            # once it has run Python code (a native actor thread inside
            # a slot hook), as a _DummyThread: it goes by its `comm`.
            names = {
                t.native_id: t.name for t in self._threads()
                if not isinstance(t, threading._DummyThread)
            }
            for tid, comm, cpu, delay in self._tasks(self._pid, names):
                role = thread_role(names.get(tid), comm or "")
                self._credit(role, (self._pid, tid), cpu, delay, seen)
            if self._watched is not None:
                for pid in self._tree(self._watched()):
                    for tid, _, cpu, delay in self._tasks(pid):
                        self._credit(
                            WATCHED_ROLE, (pid, tid), cpu, delay, seen
                        )
            self._prev = seen


class Heartbeat:
    def __init__(self, registry: MetricsRegistry):
        self._lag = registry.histogram("host.heartbeat_lag_s")
        self._stalls = registry.counter("host.stalls")
        self._stall_wall = registry.counter("host.stall_wall_s")
        self._stall_cpu = registry.counter("host.stall_cpu_s")
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="telemetry-heartbeat"
        )

    def start(self) -> "Heartbeat":
        self._thread.start()
        return self

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout)

    def _run(self) -> None:
        wall = time.monotonic()
        cpu = time.process_time()
        # Event.wait is the sleep: stop() ends the round at once.
        while not self._stop.wait(PERIOD_S):
            now, cpu_now = time.monotonic(), time.process_time()
            lag = max(now - wall - PERIOD_S, 0.0)
            self._lag.observe(lag)
            if lag > STALL_S:
                self._stalls.inc()
                self._stall_wall.inc(now - wall)
                self._stall_cpu.inc(cpu_now - cpu)
            wall, cpu = now, cpu_now
