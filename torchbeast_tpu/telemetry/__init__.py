"""Process-wide observability: metrics registry, pipeline span tracing,
and exporters (ISSUE 2 tentpole).

Five modules, stdlib-only (no jax/numpy — instrumentation inside the
acting hot path must never trigger a device sync or heavyweight import;
pinned by tests/test_telemetry.py):

- metrics: Counter/Gauge/Histogram with per-thread shards (no hot-path
  locks) and mergeable log-bucketed histograms (p50/p95/p99).
- trace:   `Tracer.span`, the one way to time a stage (histogram +
  profiler annotation + Chrome event), and cross-thread StageTraces,
  exportable as Chrome trace-event JSON (chrome://tracing / Perfetto).
- heartbeat: how late a 5 ms sleeper wakes (GIL pressure), and stalls
  with the CPU time that passed in them.
- device_scopes: `device_scope(name)`, the one way to name a part of a
  device program (`jax.named_scope` that notes the name), and the
  account of a profiler trace's device time by those scopes.
- export:  snapshot / delta / merge, the JSON-lines exporter FileWriter
  hosts (`{xpid}/telemetry.jsonl`), a Prometheus-text HTTP endpoint
  (--telemetry_port), and a `--selftest` CLI.

Typical call-site shape (instruments are resolved once, used forever):

    from torchbeast_tpu import telemetry
    _reg = telemetry.get_registry()
    _rtt = _reg.histogram("actor.request_rtt_s")
    ...
    _rtt.observe(dt)
    _prep = telemetry.get_tracer().span("inference.prep")
    ...
    with _prep:  # -> inference.prep_s, pb:inference.prep
        ...

`set_enabled(False)` (the drivers' --no_telemetry) turns every
global-registry instrument and the global tracer into no-ops; private
MetricsRegistry()/Tracer() instances ignore the gate.
"""

from torchbeast_tpu.telemetry.device_scopes import (  # noqa: F401
    device_scope,
    known_device_scopes,
)
from torchbeast_tpu.telemetry.driver import (  # noqa: F401
    DriverTelemetry,
    add_arguments,
)
from torchbeast_tpu.telemetry.export import (  # noqa: F401
    JsonLinesExporter,
    PrometheusServer,
    SCHEMA_VERSION,
    delta,
    merge_snapshots,
    read_jsonl,
    render_prometheus,
    snapshot,
    telemetry_block,
    validate_snapshot,
)
from torchbeast_tpu.telemetry.heartbeat import (  # noqa: F401
    LEDGER_PERIOD_S,
    Heartbeat,
    ThreadLedger,
)
from torchbeast_tpu.telemetry.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    is_enabled,
    set_enabled,
)
from torchbeast_tpu.telemetry.trace import (  # noqa: F401
    Span,
    StageTrace,
    Tracer,
    get_tracer,
)
