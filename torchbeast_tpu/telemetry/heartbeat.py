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
cure. stdlib only.
"""

import threading
import time

from torchbeast_tpu.telemetry.metrics import MetricsRegistry

PERIOD_S = 0.005
STALL_S = 0.1


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
