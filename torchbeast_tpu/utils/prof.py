"""Online per-section timing profiler — a thin shim over the telemetry
span primitive.

`with timings.section(name):` times a section of a driver's loop through
`Tracer.span`, so a section is a histogram (exact running moments for
`means`/`stds`/`summary`, p50/p95/p99, telemetry snapshots, merge across
threads) and a `pb:<prefix><name>` span on the profiler's clock.

By default every Timings owns a PRIVATE registry and tracer, so tests
and --no_telemetry runs keep their 5 s log line. Drivers pass
`registry=telemetry.get_registry(), tracer=telemetry.get_tracer(),
prefix="learner."` so their stage latencies ("dequeue", "learn",
"collect") become `learner.dequeue` etc. in the exported snapshot — the
stage-latency (p50/p95) series the acceptance criteria name.
"""

from typing import Dict, Optional

from torchbeast_tpu.telemetry.metrics import Histogram, MetricsRegistry
from torchbeast_tpu.telemetry.trace import Span, Tracer


class Timings:
    """Named sections of a loop, each a span over a histogram."""

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        prefix: str = "",
        tracer: Optional[Tracer] = None,
    ):
        self._registry = (
            registry if registry is not None else MetricsRegistry()
        )
        self._tracer = (
            tracer if tracer is not None else Tracer(record=False)
        )
        self._prefix = prefix
        # name -> span, insertion-ordered; list(dict.items()) is a
        # single C call, so monitor threads can read while the timed
        # thread inserts a new section.
        self._sections: Dict[str, Span] = {}

    def section(self, name: str) -> Span:
        """The span that times section `name`; the section's older
        histogram name (prefix + name, no `_s`) is kept."""
        span = self._sections.get(name)
        if span is None:
            full = self._prefix + name
            span = self._sections[name] = self._tracer.span(
                full, histogram=self._registry.histogram(full)
            )
        return span

    def histogram(self, name: str) -> Optional[Histogram]:
        """The backing histogram of a section (percentile access)."""
        span = self._sections.get(name)
        return span.histogram if span is not None else None

    def means(self) -> Dict[str, float]:
        return {
            name: span.histogram.mean
            for name, span in list(self._sections.items())
        }

    def stds(self) -> Dict[str, float]:
        return {
            name: span.histogram.std
            for name, span in list(self._sections.items())
        }

    def summary(self, prefix: str = "") -> str:
        means = self.means()
        stds = self.stds()
        total = sum(means.values()) or 1e-9
        rows = [
            f"  {k}: {1000 * means[k]:.2f}ms +- {1000 * stds[k]:.2f}ms "
            f"({100 * means[k] / total:.1f}%)"
            for k in sorted(means, key=means.get, reverse=True)
        ]
        return "\n".join(
            [f"{prefix}Mean duration of {len(means)} events "
             f"(total {1000 * total:.1f}ms):"] + rows
        )
