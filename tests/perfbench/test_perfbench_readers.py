"""The fixed set of readers a per-layer metric file can name."""

import pytest

from perfbench import readers
from torchbeast_tpu.telemetry import metrics as program_metrics

FACTS = {
    "counters": {"pool.bytes_up": 300, "pool.bytes_down": 100,
                 "pool.env_steps": 4},
    "histograms": {"rtt": {"count": 4, "total": 0.02,
                           "buckets": {"80": 3, "90": 1}}},
    "values": {"flops_per_step": 1e12, "steps_per_s": 10.0,
               "peak_flops": 200e12, "chips": 2, "traced_steps": 5},
    "trace": {"window_s": 4.0, "busy_s": 1.0, "collective_exposed_s": 0.01,
              "modules": {"jit_step": {"count": 4, "total_s": 0.002},
                          "jit_step.1": {"count": 4, "total_s": 0.006},
                          "jit_stepper": {"count": 1, "total_s": 9.0}}},
}


@pytest.mark.parametrize("spec,want", [
    ({"reader": "ratio", "args": {
        "num": [["counters", "pool.bytes_up"], ["counters", "pool.bytes_down"]],
        "den": [["counters", "pool.env_steps"]]}}, 100.0),
    ({"reader": "value", "args": {"path": ["values", "chips"], "scale": 2}}, 4.0),
    ({"reader": "hist_mean", "args": {"path": ["histograms", "rtt"],
                                      "scale": 1000.0}}, 5.0),
    ({"reader": "module_mean", "args": {"module": "jit_step",
                                        "scale": 1000.0}}, 1.0),
    ({"reader": "idle_pct"}, 75.0),
    ({"reader": "ratio", "args": {
        "num": [["trace", "collective_exposed_s"]],
        "den": [["values", "traced_steps"]], "scale": 1000.0}}, 2.0),
    ({"reader": "mfu_pct"}, 2.5),
])
def test_reader(spec, want):
    assert readers.read_metric(spec, FACTS) == pytest.approx(want)


@pytest.mark.parametrize("spec", [
    {"reader": "ratio", "args": {"num": [["counters", "absent"]],
                                 "den": [["counters", "pool.env_steps"]]}},
    {"reader": "value", "args": {"path": ["values", "absent"]}},
    {"reader": "hist_mean", "args": {"path": ["histograms", "absent"]}},
    {"reader": "hist_percentile", "args": {"path": ["histograms", "absent"],
                                           "q": 0.95}},
    {"reader": "module_mean", "args": {"module": "jit_absent"}},
    {"reader": "idle_pct"},
    {"reader": "mfu_pct"},
])
def test_nothing_to_read_returns_nothing(spec):
    assert readers.read_metric(spec, {"values": {}, "trace": None}) is None


def test_unknown_reader_is_an_error():
    with pytest.raises(ValueError, match="is not one of"):
        readers.read_metric({"name": "x", "reader": "guess"}, FACTS)


def test_percentile_finds_the_bucket():
    got = readers.read_metric(
        {"reader": "hist_percentile",
         "args": {"path": ["histograms", "rtt"], "q": 0.95}}, FACTS)
    assert got == pytest.approx(readers.bucket_middle(90))
    got = readers.read_metric(
        {"reader": "hist_percentile",
         "args": {"path": ["histograms", "rtt"], "q": 0.5}}, FACTS)
    assert got == pytest.approx(readers.bucket_middle(80))


@pytest.mark.parametrize("value", [1e-6, 1e-3, 0.0128, 0.5])
def test_bucket_geometry_is_the_programs(value):
    """The copy of the log-bucket geometry agrees with the program's."""
    index = program_metrics.bucket_index(value)
    assert readers.bucket_middle(index) == pytest.approx(
        program_metrics.bucket_representative(index)
    )
    lo, hi = program_metrics.bucket_bounds(index)
    assert lo < value <= hi
