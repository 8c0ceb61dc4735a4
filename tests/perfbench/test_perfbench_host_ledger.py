"""The per-layer metrics that split a serving thread's wall time into
running, waiting for the interpreter lock, waiting for a core and
waiting for the chip (ISSUE 36): each file loads through the manifest,
agrees with its BENCHMARK.json entry, names a reader the benchmark has
and reads the expected number from hand-made facts; each is left out of
the line where the program has no such instrument."""

import pytest

from perfbench import manifest, readers

CELL = "deep_lstm.poly"


def _hist(count, total):
    return {"count": count, "total": total, "buckets": {}}


# A 40 s window: 16,000 act batches, 128,000 env steps by 32 streams,
# 40 unroll boundaries a second.
FACTS = {
    "counters": {
        "inference.batches": 16000,
        "pool.env_steps": 128000,
        "host.cpu_s.launcher": 22.0,
        "host.cpu_s.replier": 9.0,
        "host.cpu_s.learner": 2.0,
        "host.cpu_s.prefetch": 0.5,
        "host.cpu_s.python_other": 0.5,
        "host.cpu_s.actors": 6.0,
        "host.cpu_s.native_other": 30.0,
        "host.cpu_s.env_servers": 160.0,
        "host.run_delay_s.launcher": 1.5,
        "host.run_delay_s.replier": 0.5,
        "host.run_delay_s.env_servers": 64.0,
    },
    "histograms": {
        "inference.prep_cpu_s": _hist(16000, 4.0),
        "inference.dispatch_cpu_s": _hist(16000, 20.0),
        "inference.reply_cpu_s": _hist(16000, 8.0),
        "state_table.call_cpu_s": _hist(16000, 17.6),
        "state_table.fetch_cpu_s": _hist(16000, 3.2),
        "host.gil_wait_s.batcher_next": _hist(16000, 9.6),
        # Never drop the lock: registered, never sampled.
        "host.gil_wait_s.get_inputs": _hist(0, 0.0),
        "host.gil_wait_s.set_outputs": _hist(0, 0.0),
        "host.gil_wait_s.slot_hook": _hist(1600, 4.0),
    },
    "values": {"window_s": 40.0},
    "trace": None,
}

# name: (value, source, layer, reader)
WANT = {
    "serving_cpu_ms_per_batch": (
        2.0, "program_span", "dynamic batcher + state table", "ratio"),
    "act_call_cpu_ms": (1.1, "program_span", "act step", "hist_mean"),
    "act_fetch_cpu_ms": (0.2, "program_span", "act step", "hist_mean"),
    "serving_gil_wait_ms_per_batch": (
        0.6, "program_span", "host process", "ratio"),
    "slot_hook_gil_wait_ms": (
        2.5, "program_span", "actor pool", "hist_mean"),
    "python_cpu_share_pct": (
        85.0, "program_counter", "host process", "ratio"),
    "serving_run_delay_pct": (
        5.0, "program_counter", "host process", "ratio"),
    "env_cpu_ms_per_frame": (
        1.25, "program_counter", "env servers, wire", "ratio"),
    "env_run_delay_pct": (
        5.0, "program_counter", "env servers, wire", "ratio"),
}
# The benchmark's machine runs a kernel without `schedstat` (gVisor), so
# these two would never be in a traced line there and are not in
# BENCHMARK.json (an existing test wants every metric file listed, so
# they have no file either): the readings the issue defined, kept here
# for the first PR whose benchmark machine keeps run-queue times.
RUN_DELAY = {
    "serving_run_delay_pct": {
        "reader": "ratio", "args": {
            "num": [["counters", "host.run_delay_s.launcher"],
                    ["counters", "host.run_delay_s.replier"]],
            "den": [["values", "window_s"]], "scale": 100.0,
        },
    },
    "env_run_delay_pct": {
        "reader": "ratio", "args": {
            "num": [["counters", "host.run_delay_s.env_servers"]],
            "den": [["values", "window_s"]], "scale": 100.0 / 32,
        },
    },
}
LISTED = sorted(set(WANT) - set(RUN_DELAY))


@pytest.fixture(scope="module")
def specs():
    found = {m["name"]: m for m in manifest.load_cell(CELL).per_layer}
    assert not set(RUN_DELAY) & set(found)
    return dict(found, **RUN_DELAY)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_loads_agrees_and_reads(specs, name):
    """load_cell has already held the file's unit, layer, moves and
    source against the BENCHMARK.json entry; here the entry's shape,
    the reader and the number it makes of the facts."""
    value, source, layer, reader = WANT[name]
    spec = specs[name]
    if name in LISTED:
        entry = next(
            m for m in manifest.load_benchmark()["per_layer"]
            if m["name"] == name
        )
        assert set(entry) == {
            "name", "unit", "better", "source", "layer", "moves",
            "workloads",
        }
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "env_frames_per_s"
        assert (entry["source"], entry["layer"]) == (source, layer)
        assert entry["better"] == spec["better"] == "lower"
        assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert 1 <= len(spec["what"])
    assert spec["reader"] == reader and reader in readers.READERS
    assert readers.read_metric(spec, FACTS) == pytest.approx(value)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_is_left_out_where_its_instruments_are_absent(specs, name):
    """The parent commit has no `_cpu_s` histogram, no stamp of the
    lock's wait and no thread ledger: the reader returns nothing and
    does not raise."""
    bare = {
        "counters": {"inference.batches": 16000, "pool.env_steps": 128000},
        "histograms": {
            "inference.prep_s": _hist(16000, 4.0),
            "state_table.call_s": _hist(16000, 40.0),
        },
        "values": {"window_s": 40.0}, "trace": None,
    }
    assert readers.read_metric(specs[name], bare) is None


@pytest.mark.parametrize("name", RUN_DELAY)
def test_run_delay_is_left_out_on_a_kernel_without_schedstat(specs, name):
    """The ledger registers no `host.run_delay_s.*` counter there; the
    CPU metrics beside it still read."""
    counters = {
        k: v for k, v in FACTS["counters"].items() if "run_delay" not in k
    }
    facts = dict(FACTS, counters=counters)
    assert readers.read_metric(specs[name], facts) is None
    assert readers.read_metric(
        specs["python_cpu_share_pct"], facts
    ) == pytest.approx(85.0)


def test_the_lock_wait_needs_no_sample_from_a_site_that_never_waits(specs):
    """get_inputs and set_outputs never drop the lock: their histograms
    are registered and empty, and the sum reads the one site that has
    samples; with a site's histogram missing altogether (an extension
    that lacks it) the metric is left out."""
    spec = specs["serving_gil_wait_ms_per_batch"]
    assert readers.read_metric(spec, FACTS) == pytest.approx(0.6)
    histograms = dict(FACTS["histograms"])
    del histograms["host.gil_wait_s.set_outputs"]
    assert readers.read_metric(
        spec, dict(FACTS, histograms=histograms)
    ) is None


def test_the_four_kinds_add_up_for_a_serving_thread():
    """What the metrics are for: of serving_host_ms_per_batch (wall),
    the CPU part, the stamped lock wait and the rest."""
    specs = {m["name"]: m for m in manifest.load_cell(CELL).per_layer}
    facts = dict(FACTS, histograms=dict(
        FACTS["histograms"],
        **{"inference.prep_s": _hist(16000, 4.8),
           "inference.dispatch_s": _hist(16000, 48.0),
           "inference.reply_s": _hist(16000, 35.2)},
    ))
    wall = readers.read_metric(specs["serving_host_ms_per_batch"], facts)
    cpu = readers.read_metric(specs["serving_cpu_ms_per_batch"], facts)
    assert wall == pytest.approx(5.5) and cpu == pytest.approx(2.0)
    assert wall - cpu == pytest.approx(3.5)  # off the CPU


def test_new_entries_follow_the_old_ones_in_order():
    """Appended after the 31 entries the benchmark had, in the issue's
    order, nothing put in between."""
    names = [m["name"] for m in manifest.load_benchmark()["per_layer"]]
    assert names[30] == "hbm_bw_pct.ouro"
    assert names[31:31 + len(LISTED)] == [
        "serving_cpu_ms_per_batch", "act_call_cpu_ms", "act_fetch_cpu_ms",
        "serving_gil_wait_ms_per_batch", "slot_hook_gil_wait_ms",
        "python_cpu_share_pct", "env_cpu_ms_per_frame",
    ]


def test_every_old_metric_of_the_cell_is_still_there(specs):
    for name in (
        "gil_wait_mean_ms", "serving_host_ms_per_batch", "act_call_mean_ms",
        "act_reply_overlap_pct", "env_rtt_mean_ms", "host_stall_max_s",
    ):
        assert name in specs
    assert len(manifest.load_cell(CELL).per_layer) == 21 + len(LISTED)
