"""The fixed set of readers a per-layer metric file can name.

A metric file (`layer_metrics/<name>.json`) gives `reader` and `args`.
A reader takes the run's facts and returns a number, or None when what
it reads is not there; the harness then leaves the metric out of the
line. Facts are a nested dict, addressed by a list of keys:

    counters    deltas over the window of the program's counters
    histograms  deltas over the window of its histograms
                ({"count", "total", "buckets"})
    values      numbers the driver took itself (host clock, memory)
    trace       what perfbench/trace.py made of the device trace
"""

from typing import Any, Dict, List, Optional

# The log-bucket geometry of torchbeast_tpu/telemetry/metrics.py and
# csrc/queues.h (copied: the yardstick does not import the program's
# arithmetic): bucket i > 0 covers [LO * G**(i-1), LO * G**i).
BUCKET_LO = 1e-9
BUCKET_GROWTH = 2.0 ** 0.25


def lookup(facts: Dict, path: List[str]) -> Optional[Any]:
    node: Any = facts
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return node


def _sum(facts: Dict, paths: List[List[str]]) -> Optional[float]:
    values = [lookup(facts, p) for p in paths]
    if any(v is None for v in values):
        return None
    return float(sum(values))


def read_value(facts, path, scale=1.0):
    value = lookup(facts, path)
    return None if value is None else float(value) * scale


def read_ratio(facts, num, den, scale=1.0):
    top, bottom = _sum(facts, num), _sum(facts, den)
    if top is None or not bottom:
        return None
    return top / bottom * scale


def read_hist_mean(facts, path, scale=1.0):
    hist = lookup(facts, path)
    if not hist or not hist.get("count"):
        return None
    return hist["total"] / hist["count"] * scale


def bucket_middle(index: int) -> float:
    return 0.0 if index <= 0 else BUCKET_LO * BUCKET_GROWTH ** (index - 0.5)


def read_hist_percentile(facts, path, q, scale=1.0):
    """Geometric middle of the bucket that holds the q-quantile sample
    (buckets grow by 2**0.25, so this is within 9% of the sample)."""
    hist = lookup(facts, path)
    if not hist or not hist.get("count"):
        return None
    rank = q * hist["count"]
    seen = 0
    for index in sorted(hist["buckets"], key=int):
        seen += hist["buckets"][index]
        if seen >= rank:
            return bucket_middle(int(index)) * scale
    return None


def read_module_mean(facts, module, scale=1.0):
    """Mean device time of the programs whose name starts with `module`
    (the "XLA Modules" line)."""
    modules = lookup(facts, ["trace", "modules"])
    if not modules:
        return None
    count = total = 0.0
    for name, entry in modules.items():
        if name == module or name.startswith(module + "."):
            count += entry["count"]
            total += entry["total_s"]
    return total / count * scale if count else None


def read_idle_pct(facts):
    trace = facts.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def read_mfu_pct(facts):
    """Algorithm FLOPs per second over the chips' peak."""
    values = facts.get("values", {})
    needed = ("flops_per_step", "steps_per_s", "peak_flops", "chips")
    if any(values.get(k) is None for k in needed):
        return None
    return 100.0 * values["flops_per_step"] * values["steps_per_s"] / (
        values["peak_flops"] * values["chips"]
    )


READERS = {
    "value": read_value,
    "ratio": read_ratio,
    "hist_mean": read_hist_mean,
    "hist_percentile": read_hist_percentile,
    "module_mean": read_module_mean,
    "idle_pct": read_idle_pct,
    "mfu_pct": read_mfu_pct,
}


def read_metric(spec: Dict, facts: Dict) -> Optional[float]:
    reader = READERS.get(spec.get("reader"))
    if reader is None:
        raise ValueError(
            f"layer metric {spec.get('name')!r}: reader "
            f"{spec.get('reader')!r} is not one of {sorted(READERS)}"
        )
    return reader(facts, **spec.get("args", {}))
