"""The per-layer metrics that read an actor's cycle term by term (ISSUE
66): each file loads through the manifest, agrees with its
BENCHMARK.json entry, names a reader the benchmark has and reads the
expected number from hand-made facts; each is left out of the line
where the program has no such histogram (the parent commit)."""

import pytest

from perfbench import manifest, readers

CELL = "deep_lstm.poly"


def _hist(count, total):
    return {"count": count, "total": total, "buckets": {}}


# A 40 s window: 32 actors, 128,000 env steps (3,200/s, a cycle of
# 10 ms), 10,000 act batches.
STEPS = 128000
FACTS = {
    "counters": {
        "pool.env_steps": STEPS, "inference.batches": 10000,
        "actor.env_clock_unshared": 0,
    },
    "histograms": {
        "actor.request_rtt_s": _hist(STEPS, 0.0072 * STEPS),
        "actor.env_rtt_s": _hist(STEPS, 0.0021 * STEPS),
        "actor.env_wire_down_s": _hist(STEPS, 0.0003 * STEPS),
        "actor.env_step_s": _hist(STEPS, 0.0013 * STEPS),
        "actor.env_wire_up_s": _hist(STEPS, 0.0005 * STEPS),
        "actor.reply_wake_s": _hist(STEPS, 0.0004 * STEPS),
        "actor.own_s": _hist(STEPS, 0.00025 * STEPS),
        "actor.cycle_s": _hist(STEPS, 0.01 * STEPS),
        "inference.handover_wait_s": _hist(10000, 1.5),
    },
    "values": {"window_s": 40.0},
    "trace": None,
}

# name: (value in ms, histogram, layer)
WANT = {
    "env_wire_down_mean_ms": (
        0.3, "actor.env_wire_down_s", "env servers, wire"),
    "env_step_mean_ms": (1.3, "actor.env_step_s", "env servers, wire"),
    "env_wire_up_mean_ms": (0.5, "actor.env_wire_up_s", "env servers, wire"),
    "act_wake_mean_ms": (0.4, "actor.reply_wake_s", "actor pool"),
    "actor_own_mean_ms": (0.25, "actor.own_s", "actor pool"),
    "actor_cycle_mean_ms": (10.0, "actor.cycle_s", "actor pool"),
    "act_handover_wait_mean_ms": (
        0.15, "inference.handover_wait_s", "dynamic batcher + state table"),
}
# The issue's order, which is BENCHMARK.json's.
ORDER = [
    "env_wire_down_mean_ms", "env_step_mean_ms", "env_wire_up_mean_ms",
    "act_wake_mean_ms", "actor_own_mean_ms", "actor_cycle_mean_ms",
    "act_handover_wait_mean_ms",
]


@pytest.fixture(scope="module")
def specs():
    return {m["name"]: m for m in manifest.load_cell(CELL).per_layer}


@pytest.mark.parametrize("name", ORDER)
def test_metric_loads_agrees_and_reads(specs, name):
    """load_cell has already held the file's unit, layer, moves and
    source against the BENCHMARK.json entry; here the entry's shape,
    the reader, what it reads and the number it makes of the facts."""
    value, histogram, layer = WANT[name]
    spec = specs[name]
    entry = next(
        m for m in manifest.load_benchmark()["per_layer"]
        if m["name"] == name
    )
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": layer,
        "moves": "env_frames_per_s", "workloads": [CELL],
    }
    assert spec["better"] == "lower" and 1 <= len(spec["what"])
    assert spec["reader"] == "hist_mean" in readers.READERS
    assert spec["args"] == {
        "path": ["histograms", histogram], "scale": 1000.0,
    }
    assert readers.read_metric(spec, FACTS) == pytest.approx(value)


@pytest.mark.parametrize("name", ORDER)
def test_metric_is_left_out_where_its_histogram_is_absent(specs, name):
    """The parent commit stamps none of the cycle's new terms, and a
    stream on another machine's clock observes no wire term: absent or
    empty, the reader returns nothing and does not raise, while the two
    metrics the cell had of an actor's step still read."""
    for kept in (
        {}, {WANT[name][1]: _hist(0, 0.0)},
    ):
        histograms = dict(
            kept,
            **{k: FACTS["histograms"][k]
               for k in ("actor.request_rtt_s", "actor.env_rtt_s")},
        )
        bare = dict(FACTS, histograms=histograms)
        assert readers.read_metric(specs[name], bare) is None
        assert readers.read_metric(
            specs["env_rtt_mean_ms"], bare
        ) == pytest.approx(2.1)
        assert readers.read_metric(
            specs["act_rtt_mean_ms"], bare
        ) == pytest.approx(7.2)


def test_the_env_terms_sum_to_the_round_trip(specs):
    parts = sum(
        readers.read_metric(specs[name], FACTS)
        for name in ORDER[:3]
    )
    whole = readers.read_metric(specs["env_rtt_mean_ms"], FACTS)
    assert parts == pytest.approx(whole, rel=1e-3)


def test_the_terms_sum_to_the_cycle_and_the_cycle_to_the_rate(specs):
    """What the metrics are for: the cycle is the four terms (the
    enqueue's microseconds are the remainder), and actors over the cycle
    is the env frame rate."""
    def read(name):
        return readers.read_metric(specs[name], FACTS)

    cycle = read("actor_cycle_mean_ms")
    parts = (
        read("act_rtt_mean_ms") + read("act_wake_mean_ms")
        + read("actor_own_mean_ms") + read("env_rtt_mean_ms")
    )
    assert 0.0 <= cycle - parts <= 0.01 * cycle
    frames_per_s = STEPS / FACTS["values"]["window_s"]
    assert cycle * frames_per_s == pytest.approx(32000.0, rel=0.02)


def test_new_entries_follow_the_old_ones_in_order():
    """Appended after the 54 entries the benchmark had, in the issue's
    order, nothing put in between."""
    names = [m["name"] for m in manifest.load_benchmark()["per_layer"]]
    assert names[53] == "hbm_bw_pct.granite4"
    assert names[54:54 + len(ORDER)] == ORDER


def test_every_old_metric_of_the_cell_is_still_there(specs):
    for name in (
        "wire_bytes_per_frame", "act_rtt_mean_ms", "act_rtt_p95_ms",
        "env_rtt_mean_ms", "act_queue_wait_mean_ms",
        "serving_host_ms_per_batch", "act_reply_overlap_pct",
        "serving_cpu_ms_per_batch", "slot_hook_gil_wait_ms",
        "python_cpu_share_pct", "env_cpu_ms_per_frame",
    ):
        assert name in specs
    assert set(ORDER) <= set(specs)
