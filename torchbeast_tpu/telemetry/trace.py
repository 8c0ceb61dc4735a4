"""Pipeline span tracing: one primitive to time a stage, three sinks.

Two granularities:

- `Tracer.span(name)` — THE way to time a stage of a thread's loop. It
  returns a reusable `Span`; call sites resolve it once and write
  `with span:` around the stage on every iteration. One block (i)
  observes the stage's histogram `<name>_s` in the tracer's registry
  and, beside it, `<name>_cpu_s`: the calling thread's own CPU clock
  (`time.thread_time`) over the same block, read in one pass of
  `CPU_SAMPLE_EVERY` through the span and credited that many times
  over, so `<name>_s`
  less `<name>_cpu_s` is what the thread spent off the CPU inside the
  stage (asleep for the chip, waiting for the interpreter lock or for
  a core), (ii) opens a profiler annotation `pb:<name>`, so the span lies on the
  device trace's clock and the benchmark's trace reduction names the
  device's idle gaps by it, and (iii) appends a Chrome "X" event while
  the tracer records (`--trace_path`; nesting renders as stacked bars
  in chrome://tracing / Perfetto, which nest events on one tid by
  containment). The package is stdlib-only, so sink (ii) works through
  a factory the drivers install (`set_annotation_factory(
  jax.profiler.TraceAnnotation, active=...is_enabled)`), and only while
  a profiler session is open; without one a span does (i) and (iii).
- `Tracer.stage(name)` — a StageTrace that travels WITH a request
  across threads: each pipeline stage calls `.stamp("stage")` as the
  request passes (actor -> wire -> inference-queue -> batch -> dispatch
  -> reply; learner dequeue -> stage -> update), and `.finish()` emits
  one span per consecutive stamp pair. This is how a single slow
  request's time is attributed to queue wait vs. batch wait vs. reply.

Events land in a bounded ring buffer (old events drop, hot paths never
block or grow memory) and only while the tracer records: the process
tracer records when a driver was given `--trace_path`, since nothing
else reads the ring. `export_chrome(path)` writes the standard
{"traceEvents": [...]} JSON that chrome://tracing and Perfetto load
directly. Orphaned spans (begun, never ended) are tracked and counted
but never exported — a crashed stage can't leave half-open garbage in
the trace. stdlib only; timestamps are perf_counter-based (monotonic),
mapped once to the wall clock for the export's displayTimeUnit.
"""

import collections
import itertools
import json
import threading
import time
from typing import Callable, Dict, List, Optional

from torchbeast_tpu.telemetry.metrics import (
    _ENABLED,
    Histogram,
    MetricsRegistry,
    bucket_index,
    get_registry,
)

_perf_counter = time.perf_counter
_thread_time = time.thread_time

# A thread reads its CPU clock in one of this many passes through a
# span (see Span).
CPU_SAMPLE_EVERY = 16
_PHASES = itertools.count()

# What the benchmark's trace reduction (perfbench/trace.py) takes for a
# program span among the profiler's host events.
ANNOTATION_PREFIX = "pb:"


def _always() -> bool:
    return True


class _OpenSpan:
    __slots__ = ("name", "cat", "start", "tid", "args", "ended")

    def __init__(self, name, cat, start, tid, args):
        self.name = name
        self.cat = cat
        self.start = start
        self.tid = tid
        self.args = args
        self.ended = False


class StageTrace:
    """Stamps one request's passage through named pipeline stages.

    Thread-safe by handoff: exactly one thread holds the request at a
    time (the same discipline the request payload itself rides on), so
    stamps append without a lock. `finish()` (idempotent) emits the
    per-stage spans into the owning tracer.
    """

    __slots__ = ("_tracer", "name", "_stamps", "_done", "args")

    def __init__(self, tracer: "Tracer", name: str, **args):
        self._tracer = tracer
        self.name = name
        self._stamps = [("start", time.perf_counter())]
        self._done = False
        self.args = args or None

    def stamp(self, stage: str) -> None:
        if not self._done:
            self._stamps.append((stage, time.perf_counter()))

    def stages(self) -> List[str]:
        return [s for s, _ in self._stamps[1:]]

    def finish(self) -> None:
        if self._done:
            return
        self._done = True
        prev_t = self._stamps[0][1]
        for stage, t in self._stamps[1:]:
            self._tracer.add_complete(
                f"{self.name}.{stage}", self.name, prev_t, t - prev_t,
                args=self.args,
            )
            prev_t = t
        if len(self._stamps) > 1:
            self._tracer.add_complete(
                self.name, self.name, self._stamps[0][1],
                self._stamps[-1][1] - self._stamps[0][1], args=self.args,
            )


class Span:
    """One call site's stage: `with span:` times it into all the
    tracer's sinks. Reusable and re-entrant across threads and nesting
    (what is open lives on a per-thread stack), so a call site resolves
    its span once: the histogram and the annotation's name are looked
    up here, not per call.

    The thread's CPU clock is a system call where the wall clock is
    not, and on a sandboxed kernel a system call costs what a short
    span lasts (17 us each on the benchmark's machine; two a span cost
    the poly cell 5.8% of its frames: PERF.md section 6, PR 36). So a
    span reads it in one of every `CPU_SAMPLE_EVERY` passes through it
    and credits `<name>_cpu_s` with that sample `CPU_SAMPLE_EVERY`
    times over: totals and means stay what they would be, from fewer
    readings. The passes are counted on the span, not on the thread (a
    native actor thread's Python state, and with it any thread-local
    count, lasts one slot hook); two threads in one span may lose a
    count between them, which moves a reading and loses none. Spans
    made one after the other read on different passes, so a parent's
    reading holds no child's clock reads."""

    __slots__ = ("_tracer", "name", "cat", "args", "histogram",
                 "cpu_histogram", "_annotation", "_local", "_passes")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 histogram: Optional[Histogram], args: Optional[dict],
                 cpu_histogram: Optional[Histogram] = None):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.histogram = histogram
        self.cpu_histogram = cpu_histogram
        self._annotation = ANNOTATION_PREFIX + name
        self._local = threading.local()
        self._passes = next(_PHASES) % CPU_SAMPLE_EVERY

    def __enter__(self):
        try:
            stack = self._local.stack
        except AttributeError:
            stack = self._local.stack = []
        tracer = self._tracer
        if not tracer.enabled():
            stack.append(None)
            return self
        annotation = None
        factory = tracer._annotation_factory
        if factory is not None and tracer._annotation_active():
            annotation = factory(self._annotation)
            annotation.__enter__()
        cpu_start = None
        if self.cpu_histogram is not None:
            self._passes = passes = self._passes + 1
            if passes % CPU_SAMPLE_EVERY == 0:
                cpu_start = _thread_time()
        # The wall clock is read innermost: `_s` holds neither the
        # span's bookkeeping nor the CPU clock's system calls.
        stack.append((annotation, _perf_counter(), cpu_start))
        return self

    def __exit__(self, exc_type, exc, tb):
        entry = self._local.stack.pop()
        if entry is None:
            return False
        annotation, start, cpu_start = entry
        duration = _perf_counter() - start
        if cpu_start is not None:
            cpu = _thread_time() - cpu_start
        if annotation is not None:
            annotation.__exit__(exc_type, exc, tb)
        if self.histogram is not None:
            self.histogram.observe(duration)
        if cpu_start is not None:
            n = CPU_SAMPLE_EVERY
            self.cpu_histogram.observe_aggregate(
                {bucket_index(cpu): n}, n * cpu, n * cpu * cpu, cpu, cpu
            )
        tracer = self._tracer
        if tracer._record:
            tracer.add_complete(
                self.name, self.cat, start, duration, args=self.args
            )
        return False


class Tracer:
    """`registry` is where spans find their `<name>_s` and
    `<name>_cpu_s` histograms (None: spans observe none); `record` is
    whether Chrome events are kept for `export_chrome`."""

    def __init__(self, max_events: int = 32768, gated: bool = False,
                 registry: Optional[MetricsRegistry] = None,
                 record: bool = True):
        self._events = collections.deque(maxlen=max_events)
        self._gated = gated
        self._registry = registry
        self._record = record
        self._annotation_factory: Optional[Callable] = None
        self._annotation_active: Callable[[], bool] = _always
        self._ids = itertools.count(1)
        self._open: Dict[int, _OpenSpan] = {}
        self._open_lock = threading.Lock()
        self._tid_lock = threading.Lock()
        self._tids: Dict[int, int] = {}
        # One perf_counter<->wall-clock correspondence for the export.
        self._wall_at_zero = time.time() - time.perf_counter()

    def enabled(self) -> bool:
        return not (self._gated and not _ENABLED[0])

    def recording(self) -> bool:
        """Whether Chrome events are being kept right now."""
        return self._record and self.enabled()

    def set_recording(self, on: bool) -> None:
        self._record = bool(on)

    def set_annotation_factory(
        self, factory: Optional[Callable],
        active: Optional[Callable[[], bool]] = None,
    ) -> None:
        """`factory(name)` -> a context manager that marks the span in
        the profiler's host trace (`jax.profiler.TraceAnnotation`);
        `active()` says whether a profiler session is open
        (`TraceAnnotation.is_enabled`), so that a span builds no
        annotation nobody would record (None: always build one). The
        drivers install both at start-up; this package imports no jax."""
        self._annotation_factory = factory
        self._annotation_active = active if active is not None else _always

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._tid_lock:
                tid = self._tids.setdefault(ident, len(self._tids) + 1)
        return tid

    def add_complete(
        self, name: str, cat: str, start: float, dur: float,
        tid: Optional[int] = None, args: Optional[dict] = None,
    ) -> None:
        """Record a completed span (Chrome 'X' event). `start` is a
        perf_counter timestamp; `dur` seconds."""
        if not self.recording():
            return
        event = {
            "name": name,
            "cat": cat or "span",
            "ph": "X",
            "ts": start * 1e6,
            "dur": max(dur, 0.0) * 1e6,
            "pid": 0,
            "tid": tid if tid is not None else self._tid(),
        }
        if args:
            event["args"] = dict(args)
        self._events.append(event)

    def begin(self, name: str, cat: str = "", **args) -> Optional[int]:
        """Open a span by token (for spans that end on another code
        path). Returns the token, or None when tracing is disabled."""
        if not self.enabled():
            return None
        token = next(self._ids)
        span = _OpenSpan(
            name, cat, time.perf_counter(), self._tid(), args or None
        )
        with self._open_lock:
            self._open[token] = span
        return token

    def end(self, token: Optional[int], **args) -> bool:
        """Close a span opened with begin(). Unknown/already-ended/None
        tokens are a no-op (returns False) — double-end can't corrupt
        the trace."""
        if token is None:
            return False
        with self._open_lock:
            span = self._open.pop(token, None)
        if span is None or span.ended:
            return False
        span.ended = True
        merged = dict(span.args or {})
        merged.update(args)
        self.add_complete(
            span.name, span.cat, span.start,
            time.perf_counter() - span.start,
            tid=span.tid, args=merged or None,
        )
        return True

    def open_count(self) -> int:
        """Spans begun but not yet ended (orphans, if it stays > 0)."""
        with self._open_lock:
            return len(self._open)

    def span(self, name: str, cat: str = "",
             histogram: Optional[Histogram] = None, **args) -> Span:
        """The stage `name` as a reusable context manager (see Span).
        Its histogram is `<name>_s` of the tracer's registry unless one
        is given (utils/prof.Timings keeps its sections' older names);
        the thread-CPU histogram beside it is always `<name>_cpu_s` of
        the tracer's registry. `args` ride on every Chrome event of the
        span."""
        cpu_histogram = None
        if self._registry is not None:
            if histogram is None:
                histogram = self._registry.histogram(name + "_s")
            cpu_histogram = self._registry.histogram(name + "_cpu_s")
        return Span(self, name, cat, histogram, args or None,
                    cpu_histogram)

    def stage(self, name: str, **args) -> Optional[StageTrace]:
        """A cross-thread request trace; None when nothing records, so
        call sites guard with `if trace is not None`."""
        if not self.recording():
            return None
        return StageTrace(self, name, **args)

    def events(self) -> List[dict]:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()

    def export_chrome(self, path: str) -> int:
        """Write {"traceEvents": [...]} (chrome://tracing / Perfetto
        format). Returns the number of events written."""
        events = self.events()
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "wall_time_at_ts_zero": self._wall_at_zero,
                "open_spans_dropped": self.open_count(),
            },
        }
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(events)


# Process-wide tracer, gated with the metrics registry and observing
# into it; it records Chrome events once a driver has a --trace_path.
_GLOBAL = Tracer(gated=True, registry=get_registry(), record=False)


def get_tracer() -> Tracer:
    return _GLOBAL
