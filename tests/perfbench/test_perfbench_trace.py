"""The trace reduction on a small recorded trace (data/trace_small.json)."""

import json
import os

import pytest

from perfbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return json.load(f)


def test_union_counts_overlap_once():
    assert trace.union([(0, 4), (3, 7), (10, 11)]) == [(0, 7), (10, 11)]
    assert trace.length(trace.union([(0, 4), (3, 7)])) == 7  # not 8


def test_union_drops_empty_and_merges_touching():
    assert trace.union([(5, 5), (0, 1), (1, 2)]) == [(0, 2)]


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 6)], [(0, 2), (3, 5), (6, 10)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
])
def test_subtract(a, b, want):
    assert trace.subtract(a, b) == want


def test_busy_is_union_not_sum_and_idle_is_the_rest(small):
    reduced = trace.reduce_trace(small, window_ns=(1000, 5000))
    # Device 0: a and b overlap (700, not 800); c, all-reduce and d
    # chain into 2000. Device 1: 1000. Mean over the two chips.
    assert reduced["chips"] == 2
    assert reduced["window_s"] == pytest.approx(4000e-9)
    assert reduced["busy_s"] == pytest.approx((2700 + 1000) / 2 * 1e-9)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(1 - 1850 / 4000)


def test_async_copies_are_not_busy(small):
    # copy-start.1 covers 0..5000 on the Async line; were it counted,
    # device 0 would never be idle.
    reduced = trace.reduce_trace(small, window_ns=(0, 5000))
    assert reduced["busy_s"] < reduced["window_s"]


def test_default_window_is_first_to_last_op(small):
    reduced = trace.reduce_trace(small)
    assert reduced["window_s"] == pytest.approx(4000e-9)


def test_module_times(small):
    modules = trace.reduce_trace(small)["modules"]
    assert modules["jit_step"] == {
        "count": 1, "total_s": pytest.approx(700e-9),
    }
    assert modules["jit_update_step"]["total_s"] == pytest.approx(2000e-9)


def test_gap_goes_to_the_enclosing_host_span(small):
    gaps = dict(trace.reduce_trace(small, window_ns=(1000, 5000))["idle_gaps"])
    # Device 0 idles 1700..3000: 1700..2200 lies inside
    # PjitFunction(step), 2200..2800 before PjitFunction(update_step),
    # 2800..2900 inside it, 2900..3000 before the pb:update annotation.
    assert gaps["inside_jit_step"] == pytest.approx(500e-9)
    assert gaps["before_jit_update_step"] == pytest.approx(600e-9)
    assert gaps["inside_jit_update_step"] == pytest.approx(100e-9)
    assert gaps["before_update"] == pytest.approx(100e-9)
    assert sum(gaps.values()) == pytest.approx(1300e-9)


def test_name_gaps_after_last_span():
    named = trace.name_gaps([(10, 20)], [("jit_x", 0, 5)])
    assert named == {"after_last_span": 10}


def test_innermost_span_wins():
    named = trace.name_gaps(
        [(0, 10)], [("outer", 0, 10), ("inner", 2, 4)]
    )
    assert named == {"inside_outer": 8, "inside_inner": 2}


def test_collective_exposed_is_what_compute_does_not_cover(small):
    reduced = trace.reduce_trace(small)
    # all-reduce.1 runs 4000..4600, fusion.d covers 4400..4600: 400
    # exposed on device 0, none on device 1; mean over chips.
    assert reduced["collective_exposed_s"] == pytest.approx(200e-9)


def test_top_ops_are_ranked(small):
    ops = trace.reduce_trace(small)["device_ops"]
    assert ops[0][0] == "fusion.c"
    assert ops[0][1] == pytest.approx(1000e-9)  # 2000 over two chips
    assert len(ops) <= 10


def test_no_device_op_is_an_error(small):
    host_only = {"planes": [p for p in small["planes"]
                            if p["name"] == "/host:CPU"]}
    with pytest.raises(ValueError, match="no operation ran"):
        trace.reduce_trace(host_only)


@pytest.mark.parametrize("name,want", [
    ("PjitFunction(step)", "jit_step"),
    ("PjitFunction(_unstack)", "jit__unstack"),
    ("pb:update", "update"),
    ("PjRtCApiLoadedExecutable::Execute", None),
])
def test_host_span_names(name, want):
    assert trace.host_span_name(name) == want


def test_load_xplane_reads_a_profile(tmp_path):
    """A trace JAX's profiler writes here, on the CPU: the host spans
    come through; reducing it fails, because no device ran anything."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((8, 8))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("pb:probe"):
        step(x).block_until_ready()
    jax.profiler.stop_trace()
    loaded = trace.load_xplane(trace.find_xplane(str(tmp_path)))
    names = {
        trace.host_span_name(e[0])
        for plane in loaded["planes"] for line in plane["lines"]
        for e in line["events"]
    }
    assert "probe" in names
    with pytest.raises(ValueError):
        trace.reduce_trace(loaded)


@pytest.mark.parametrize("label,want", [
    ("%fusion.186 = f32[16,84,84,16]{3,0,2,1} fusion(bf16[16,84,84,4] %copy.4)",
     "fusion.186"),
    ("%all-reduce.3 = f32[256]{0} all-reduce(f32[256]{0} %x)", "all-reduce.3"),
    ("fusion.a", "fusion.a"),
])
def test_op_name_is_the_instructions(label, want):
    assert trace.op_name(label) == want
    assert bool(trace.COLLECTIVE.match(trace.op_name(label))) == (
        want.startswith("all-")
    )


def test_default_window_ends_where_the_host_stopped_recording(small):
    """The host's tracer stops first; device events after its last
    span are outside the window, not idle time nobody can name."""
    import copy

    early = copy.deepcopy(small)
    host = next(p for p in early["planes"] if p["name"] == "/host:CPU")
    host["lines"][0]["events"] = [["PjitFunction(step)", 900, 1300]]
    reduced = trace.reduce_trace(early)
    assert reduced["window_s"] == pytest.approx(1200e-9)  # 1000..2200
    assert dict(reduced["idle_gaps"]) == {
        "inside_jit_step": pytest.approx(500e-9)
    }
