"""The per-layer metrics that read the program's spans (ISSUE 25): each
file loads through the manifest, agrees with its BENCHMARK.json entry
and reads the expected number from canned facts; a program span in a
profiler capture names a device idle gap."""

import pytest

from perfbench import manifest, readers, trace

CELL = "deep_lstm.poly"


def _hist(count, total, buckets=None):
    return {"count": count, "total": total, "buckets": buckets or {}}


# 40 s window, 9,200 act batches by two serving threads.
FACTS = {
    "counters": {"inference.batches": 9200},
    "histograms": {
        "actor.env_rtt_s": _hist(74000, 370.0),
        "inference.request_wait_s": _hist(74000, 444.0),
        "inference.prep_s": _hist(9200, 4.6),
        "inference.dispatch_s": _hist(9200, 18.4),
        "inference.reply_s": _hist(9200, 13.8),
        "state_table.context_s": _hist(9200, 9.2),
        "state_table.call_s": _hist(9200, 10.12),
        "learner.stats_fetch_s": _hist(14, 1.6),
        # Lags of 0.1 ms (bucket 67), 5 ms (90) and one of 11 s (134).
        "host.heartbeat_lag_s": _hist(
            8000, 12.2, {"67": 7900, "90": 99, "134": 1}
        ),
    },
    "values": {"window_s": 40.0},
    "trace": None,
}

WANT = {
    "env_rtt_mean_ms": 5.0,
    "act_queue_wait_mean_ms": 6.0,
    "serving_busy_threads": 0.92,
    "serving_host_ms_per_batch": 4.0,
    "act_context_mean_ms": 1.0,
    "act_call_mean_ms": 1.1,
    "learner_stats_wait_pct": 4.0,
    "gil_wait_mean_ms": 1.525,
    "host_stall_max_s": readers.bucket_middle(134),
}


@pytest.fixture(scope="module")
def specs():
    return {m["name"]: m for m in manifest.load_cell(CELL).per_layer}


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_loads_agrees_and_reads(specs, name):
    """load_cell has already held the file's unit, layer, moves and
    source against the BENCHMARK.json entry; here the entry's shape and
    the number the reader makes of canned facts."""
    spec = specs[name]
    entry = next(
        m for m in manifest.load_benchmark()["per_layer"]
        if m["name"] == name
    )
    assert set(entry) == {
        "name", "unit", "better", "source", "layer", "moves", "workloads",
    }
    assert entry["workloads"][0] == CELL  # later PRs may append cells
    assert entry["moves"] == "env_frames_per_s"
    assert entry["source"] == "program_span"
    assert entry["better"] == spec["better"] == "lower"
    assert spec["reader"] in ("hist_mean", "hist_percentile", "ratio")
    assert 1 <= len(spec["what"])
    assert readers.read_metric(spec, FACTS) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_is_left_out_where_the_program_has_no_such_span(specs, name):
    """The parent commit has none of these spans: the reader returns
    nothing and does not raise."""
    bare = {"counters": {"inference.batches": 9200}, "histograms": {},
            "values": {"window_s": 40.0}, "trace": None}
    assert readers.read_metric(specs[name], bare) is None


def test_new_entries_follow_the_old_ones_in_order():
    """Appended after the fifteen entries PR 24 made, nothing put in
    between; what later PRs append comes after these."""
    names = [m["name"] for m in manifest.load_benchmark()["per_layer"]]
    assert names[0] == "wire_bytes_per_frame"
    assert names[15:15 + len(WANT)] == [
        "env_rtt_mean_ms", "act_queue_wait_mean_ms", "serving_busy_threads",
        "serving_host_ms_per_batch", "act_context_mean_ms",
        "act_call_mean_ms", "learner_stats_wait_pct", "gil_wait_mean_ms",
        "host_stall_max_s",
    ]


def test_the_stall_bucket_is_within_9_percent():
    assert readers.bucket_middle(134) == pytest.approx(11.0, rel=0.09)


def test_a_program_span_names_an_idle_gap(tmp_path):
    """A capture of JAX's profiler, here on the CPU, holds the span the
    program's tracer opened; with a device line put around it (no chip
    here), reduce_trace names the idle gap `inside_<span>`."""
    import jax
    import jax.numpy as jnp

    from torchbeast_tpu.telemetry import MetricsRegistry, Tracer

    tracer = Tracer(registry=MetricsRegistry(), record=False)
    # As the drivers install it: annotations only inside a session.
    tracer.set_annotation_factory(
        jax.profiler.TraceAnnotation,
        active=jax.profiler.TraceAnnotation.is_enabled,
    )
    wait = tracer.span("inference.wait_batch")
    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((8, 8))
    step(x).block_until_ready()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    jax.profiler.start_trace(str(tmp_path))
    assert jax.profiler.TraceAnnotation.is_enabled()
    step(x).block_until_ready()
    with wait:
        step(x).block_until_ready()
    jax.profiler.stop_trace()
    loaded = trace.load_xplane(trace.find_xplane(str(tmp_path)))
    spans = [
        (trace.host_span_name(name), start, start + duration)
        for plane in loaded["planes"] for line in plane["lines"]
        for name, start, duration in line["events"]
    ]
    inside = [s for s in spans if s[0] == "inference.wait_batch"]
    assert len(inside) == 1
    _, start, end = inside[0]
    first = min(s[1] for s in spans)
    # The device works up to the span's start and again after its end.
    loaded["planes"].append({
        "name": "/device:TPU:0",
        "lines": [{"name": trace.OPS_LINE, "events": [
            ["fusion.a", first, start - first],
            ["fusion.b", end, 1000.0],
        ]}],
    })
    gaps = dict(trace.reduce_trace(loaded)["idle_gaps"])
    # The jit call inside the span started later, so its part of the
    # gap carries its name; the rest is the program span's.
    assert gaps.get("inside_inference.wait_batch", 0.0) > 0.0
    assert not [name for name in gaps if name.startswith("before_")]
    assert sum(gaps.values()) == pytest.approx((end - start) / 1e9, rel=1e-6)
