"""What every driver shares: the process clock, the device's report,
compile events and the traced sub-window."""

import os
import shutil
import time
from typing import Dict, Optional

from perfbench import manifest

# Everything a run leaves behind lives here (listed in .gitignore):
# the native build, savedirs and traces of a run, removed when it ends.
WORK_DIR = os.path.join(manifest.ROOT, ".perfbench")
PROGRAM_SEED_MODULUS = 2**31 - 1  # the program's parsers take an int32


def seconds_since_process_start() -> float:
    """Wall seconds this process has existed, from the kernel's own
    record of its start (field 22 of /proc/self/stat, in clock ticks
    since boot), so that interpreter start-up and imports count."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    with open("/proc/uptime") as f:
        return float(f.read().split()[0]) - started


def fresh_dir(*parts: str) -> str:
    path = os.path.join(WORK_DIR, *parts)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


class CompileMeter:
    """Counts what JAX's monitoring says about compilation. A request
    to the persistent cache is made for every program that is traced
    anew, hit or miss, so it is the count of compilations."""

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        self.backend_seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_seconds += duration

    def report(self) -> Dict[str, float]:
        return {
            "requests": self.requests, "hits": self.hits,
            "backend_seconds": self.backend_seconds,
        }


def memory_peak_bytes(stats: Dict) -> int:
    """The most device memory the process has held. The TPU runtime
    keeps a loaded program's temporaries as a reservation of their own
    (`bytes_reserved`) beside what the allocator handed out
    (`bytes_in_use`): the two do not overlap, and the flagship update's
    3 GB of temporaries show only in the first. Where the runtime
    reports no reservation, the allocator's peak is the whole of it."""
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def device_report(devices) -> Dict:
    first = devices[0]
    return {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(
            memory_peak_bytes(d.memory_stats()) for d in devices
        ),
        "memory_stats": dict(first.memory_stats()),
    }


class TraceWindow:
    """The device trace of the last `trace_seconds` of the measured
    window, taken only with --trace 1. `poll(now)` starts it when its
    time has come; `finish()` stops it and reduces it."""

    def __init__(self, enabled: bool, name: str, window_start: float,
                 seconds: float, trace_seconds: float):
        self.enabled = enabled
        self.dir = fresh_dir("trace", name) if enabled else None
        self.start_at = window_start + max(0.0, seconds - trace_seconds)
        self.started_at: Optional[float] = None

    def poll(self, now: float) -> None:
        if self.enabled and self.started_at is None and now >= self.start_at:
            import jax
            from jax.profiler import ProfileOptions

            options = ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.started_at = time.monotonic()

    def finish(self) -> Optional[Dict]:
        if self.started_at is None:
            return None
        import jax

        from perfbench import trace

        jax.profiler.stop_trace()
        try:
            return trace.reduce_trace(
                trace.load_xplane(trace.find_xplane(self.dir))
            )
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
