"""The span primitive (`Tracer.span`), its three sinks, the sites that
use it and the heartbeat (ISSUE 25)."""

import ctypes
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

from torchbeast_tpu import telemetry
from torchbeast_tpu.telemetry import Heartbeat, MetricsRegistry, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def annotations():
    FakeAnnotation.log = []
    return FakeAnnotation.log


# ---------------------------------------------------------- the primitive


def test_span_observes_histogram_annotation_and_chrome_event(annotations):
    reg = MetricsRegistry()
    tracer = Tracer(registry=reg)
    tracer.set_annotation_factory(FakeAnnotation)
    stage = tracer.span("unit.stage", cat="test", rows=3)
    for _ in range(2):
        with stage:
            time.sleep(0.002)
    hist = reg.histogram("unit.stage_s")
    assert hist.count == 2 and hist.mean >= 0.002
    assert annotations == [
        ("enter", "pb:unit.stage"), ("exit", "pb:unit.stage"),
    ] * 2
    events = tracer.events()
    assert [e["name"] for e in events] == ["unit.stage"] * 2
    assert events[0]["ph"] == "X" and events[0]["cat"] == "test"
    assert events[0]["args"] == {"rows": 3}
    assert events[0]["dur"] >= 2000  # microseconds


def test_span_without_factory_or_recording_keeps_the_histogram(annotations):
    reg = MetricsRegistry()
    tracer = Tracer(registry=reg, record=False)
    with tracer.span("unit.quiet"):
        pass
    assert reg.histogram("unit.quiet_s").count == 1
    assert tracer.events() == [] and annotations == []
    # Recording switched on later reaches spans resolved before it.
    stage = tracer.span("unit.late")
    tracer.set_recording(True)
    with stage:
        pass
    assert [e["name"] for e in tracer.events()] == ["unit.late"]


def test_span_builds_no_annotation_while_no_profiler_session_is_open(
    annotations,
):
    reg = MetricsRegistry()
    tracer = Tracer(registry=reg, record=False)
    session = [False]
    tracer.set_annotation_factory(FakeAnnotation, active=lambda: session[0])
    stage = tracer.span("unit.gated")
    with stage:
        pass
    assert annotations == []
    session[0] = True
    with stage:
        # A session that closes inside the span still gets its exit.
        session[0] = False
    assert annotations == [
        ("enter", "pb:unit.gated"), ("exit", "pb:unit.gated"),
    ]
    assert reg.histogram("unit.gated_s").count == 2


def test_span_takes_a_named_histogram():
    """utils/prof.Timings keeps its sections' names (no `_s`)."""
    reg = MetricsRegistry()
    tracer = Tracer(registry=reg)
    with tracer.span("learner.dequeue",
                     histogram=reg.histogram("learner.dequeue")):
        pass
    assert reg.histogram("learner.dequeue").count == 1
    assert "learner.dequeue_s" not in reg.instruments()


def test_span_nests_and_is_reentrant_across_threads(annotations):
    reg = MetricsRegistry()
    tracer = Tracer(registry=reg)
    tracer.set_annotation_factory(FakeAnnotation)
    outer, inner = tracer.span("unit.outer"), tracer.span("unit.inner")
    with outer:
        with inner:
            pass
    assert [name for _, name in annotations] == [
        "pb:unit.outer", "pb:unit.inner", "pb:unit.inner", "pb:unit.outer",
    ]
    shared = tracer.span("unit.shared")
    gate = threading.Barrier(4)

    def work():
        gate.wait(timeout=10)
        for _ in range(50):
            with shared:
                with shared:  # the same span, nested on one thread
                    pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert reg.histogram("unit.shared_s").count == 4 * 50 * 2


def test_span_exits_on_an_exception(annotations):
    reg = MetricsRegistry()
    tracer = Tracer(registry=reg)
    tracer.set_annotation_factory(FakeAnnotation)
    with pytest.raises(KeyError):
        with tracer.span("unit.raises"):
            raise KeyError("x")
    assert annotations[-1] == ("exit", "pb:unit.raises")
    assert reg.histogram("unit.raises_s").count == 1


def test_no_telemetry_turns_all_three_sinks_off(annotations):
    tracer = telemetry.get_tracer()
    reg = telemetry.get_registry()
    stage = tracer.span("gate_span.stage")
    tracer.set_annotation_factory(FakeAnnotation)
    tracer.set_recording(True)
    telemetry.set_enabled(False)
    try:
        with stage:
            pass
        assert reg.histogram("gate_span.stage_s").count == 0
        assert annotations == []
        assert not [e for e in tracer.events()
                    if e["name"] == "gate_span.stage"]
    finally:
        telemetry.set_enabled(True)
        tracer.set_annotation_factory(None)
        tracer.set_recording(False)
        tracer.clear()
    with stage:
        pass
    assert reg.histogram("gate_span.stage_s").count == 1


def test_process_tracer_records_only_with_a_trace_path(tmp_path):
    """The ring's one reader is the export at shutdown: DriverTelemetry
    turns recording on with --trace_path and leaves it off without."""
    import argparse

    tracer = telemetry.get_tracer()

    def run(trace_path):
        flags = argparse.Namespace(
            telemetry=True, telemetry_port=0, trace_path=trace_path
        )
        tele = telemetry.DriverTelemetry(
            flags, str(tmp_path / "t.jsonl"), driver="test",
            annotation_factory=FakeAnnotation,
        )
        try:
            assert tele.heartbeat is not None
            with tracer.span("driver_span.stage"):
                pass
            return tracer.recording()
        finally:
            tele.shutdown()
            tracer.set_annotation_factory(None)
            tracer.set_recording(False)
            tracer.clear()

    assert run(None) is False
    path = str(tmp_path / "trace.json")
    assert run(path) is True
    names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert "driver_span.stage" in names


def test_telemetry_runs_without_jax_or_numpy():
    """The package is stdlib-only at run time too: with jax and numpy
    unimportable, a span (all three sinks) and a heartbeat still work.
    The parent package is stubbed, since its own __init__ imports jax."""
    code = """
import sys, types, time
sys.modules["jax"] = None
sys.modules["numpy"] = None
pkg = types.ModuleType("torchbeast_tpu")
pkg.__path__ = [sys.argv[1] + "/torchbeast_tpu"]
sys.modules["torchbeast_tpu"] = pkg
from torchbeast_tpu import telemetry

class Note:
    seen = []
    def __init__(self, name): self.name = name
    def __enter__(self): Note.seen.append(self.name)
    def __exit__(self, *exc): return False

tracer, reg = telemetry.get_tracer(), telemetry.get_registry()
tracer.set_annotation_factory(Note)
tracer.set_recording(True)
with tracer.span("pure.stage"):
    pass
beat = telemetry.Heartbeat(reg).start()
time.sleep(0.05)
beat.stop()
assert Note.seen == ["pb:pure.stage"], Note.seen
assert reg.histogram("pure.stage_s").count == 1
assert reg.histogram("host.heartbeat_lag_s").count >= 1
assert len(tracer.events()) == 1
print("ok")
"""
    proc = subprocess.run(
        [sys.executable, "-c", code, REPO],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


# --------------------------------------------------------- Timings sections


def test_timings_sections_are_spans(annotations):
    from torchbeast_tpu.utils import Timings

    reg = MetricsRegistry()
    tracer = Tracer(registry=reg, record=False)
    tracer.set_annotation_factory(FakeAnnotation)
    timings = Timings(registry=reg, prefix="learner.", tracer=tracer)
    dequeue = timings.section("dequeue")
    assert timings.section("dequeue") is dequeue  # resolved once
    with dequeue:
        time.sleep(0.001)
    assert annotations == [
        ("enter", "pb:learner.dequeue"), ("exit", "pb:learner.dequeue"),
    ]
    assert reg.histogram("learner.dequeue").count == 1
    assert timings.means()["dequeue"] >= 0.001


# ------------------------------------------------------- the serving loop


class FakeBatch:
    def __init__(self, rows, delay_s):
        self.rows, self.delay_s = rows, delay_s
        self.outputs = None

    def __len__(self):
        return self.rows

    def get_inputs(self):
        time.sleep(self.delay_s)
        return {
            "env": {"frame": np.zeros((1, self.rows, 4), np.float32)},
            "agent_state": np.zeros((1, self.rows, 2), np.float32),
        }

    def set_outputs(self, outputs):
        time.sleep(self.delay_s)
        self.outputs = outputs

    def fail(self, error):
        raise AssertionError(error)


class FakeBatcher:
    """Yields `n` batches, sleeping before each as a batcher with no
    request ready would block."""

    def __init__(self, n, delay_s):
        self.batches = [FakeBatch(3, delay_s) for _ in range(n)]
        self.delay_s = delay_s

    def __iter__(self):
        for batch in self.batches:
            time.sleep(self.delay_s)
            yield batch

    def size(self):
        return 0


def test_serving_loop_spans_tile_each_role():
    """Every instant of the launcher lies in one of its three spans:
    wait_batch + prep + dispatch add up to the loop's wall time (within
    5%). The replier's work on a batch is the reply span, beside them:
    it costs the launcher nothing, so the four no longer sum to the
    wall."""
    from torchbeast_tpu.runtime.inference import inference_loop

    delay = 0.004
    batcher = FakeBatcher(40, delay)

    def act_fn(env, state, batch_size):
        time.sleep(delay)
        return {"action": np.zeros((1, batch_size), np.int32)}, state

    prefix = "tile_test"
    started = time.perf_counter()
    inference_loop(
        batcher, act_fn, max_batch_size=4, telemetry_prefix=prefix
    )
    wall = time.perf_counter() - started
    reg = telemetry.get_registry()
    totals = {
        stage: reg.histogram(f"{prefix}.{stage}_s").merged().total
        for stage in ("wait_batch", "prep", "dispatch", "reply")
    }
    # The loop returned, so its replier has answered every batch.
    assert all(batch.outputs is not None for batch in batcher.batches)
    assert reg.counter(f"{prefix}.batches").value() == 40
    # wait_batch saw the 40 batches and the batcher's end.
    assert reg.histogram(f"{prefix}.wait_batch_s").count == 41
    assert reg.histogram(f"{prefix}.reply_s").count == 40
    for stage, total in totals.items():
        assert total >= 40 * delay, (stage, total)
    launcher = sum(totals.values()) - totals["reply"]
    assert launcher <= wall
    assert launcher == pytest.approx(wall, rel=0.05)


def test_serving_loop_span_tree_spans_two_threads():
    """The span tree of a batch, now that two threads work on it:
    `state_table.context` and `.call` lie inside the launcher's
    dispatch span, `state_table.fetch` inside the replier's reply span,
    each child on its parent's thread, and the two threads differ."""
    import jax.numpy as jnp

    from torchbeast_tpu.runtime.inference import inference_loop
    from torchbeast_tpu.runtime.queues import DynamicBatcher
    from torchbeast_tpu.runtime.state_table import DeviceStateTable

    def act(ctx, env, state):
        return {"out": env["frame"] + state["h"]}, {"h": state["h"] + 1.0}

    tracer = telemetry.get_tracer()
    recording = tracer.recording()
    tracer.set_recording(True)
    started_us = time.perf_counter() * 1e6
    try:
        table = DeviceStateTable(
            {"h": jnp.zeros((1, 1, 4))}, num_slots=2, act_fn=act,
            batch_dim=1,
        )
        batcher = DynamicBatcher(
            batch_dim=1, minimum_batch_size=1, maximum_batch_size=1,
            timeout_ms=5,
        )
        prefix = "tree_test"
        server = threading.Thread(
            target=inference_loop, args=(batcher, None, 1),
            kwargs={"state_table": table, "telemetry_prefix": prefix},
            daemon=True,
        )
        server.start()
        for _ in range(3):
            batcher.compute({
                "env": {"frame": np.ones((1, 1, 4), np.float32)},
                "slot": np.zeros((1, 1), np.int32),
                "advance": np.ones((1, 1), bool),
            })
        batcher.close()
        server.join(10)
        assert not server.is_alive()
        events = [
            e for e in tracer.events()
            if e["name"].startswith((prefix, "state_table."))
            and e["ts"] >= started_us
        ]
    finally:
        tracer.set_recording(recording)

    def named(name):
        return [e for e in events if e["name"] == name]

    def inside(child, parents):
        return any(
            p["tid"] == child["tid"]
            and p["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= p["ts"] + p["dur"]
            for p in parents
        )

    dispatches, replies = named(f"{prefix}.dispatch"), named(f"{prefix}.reply")
    assert len(dispatches) == len(replies) == 3
    for child in named("state_table.context") + named("state_table.call"):
        assert inside(child, dispatches), child
    fetches = named("state_table.fetch")
    assert len(fetches) == 3
    for child in fetches:
        assert inside(child, replies), child
    launcher_tids = {e["tid"] for e in dispatches}
    replier_tids = {e["tid"] for e in replies}
    assert len(launcher_tids) == len(replier_tids) == 1
    assert launcher_tids != replier_tids


def test_state_table_step_parts_lie_inside_dispatch():
    """context + call tile `step`, which is all of the serving loop's
    dispatch span (the launcher's); fetch is the child of reply (the
    replier's: the test above follows both through a loop)."""
    import jax.numpy as jnp

    from torchbeast_tpu.runtime.inference import (
        pad_advance,
        pad_slots,
        pad_to,
    )
    from torchbeast_tpu.runtime.state_table import DeviceStateTable

    reg = telemetry.get_registry()
    names = ["context", "call", "fetch"]
    before = {
        n: reg.histogram(f"state_table.{n}_s").merged() for n in names
    }

    def act(ctx, env, state):
        return {"out": env["frame"] + state["h"]}, {"h": state["h"] + 1.0}

    table = DeviceStateTable(
        {"h": jnp.zeros((1, 1, 4))}, num_slots=4, act_fn=act,
        context_fn=lambda: time.sleep(0.002), batch_dim=1,
    )
    env = pad_to({"frame": np.ones((1, 2, 4), np.float32)}, 4, batch_dim=1)
    slots = pad_slots(np.asarray([0, 1]), 4, table.trash_slot)
    advance = pad_advance(np.asarray([True, True]), 4)
    started = time.perf_counter()
    for _ in range(3):
        out = table.step(slots, advance, env)
    step_wall = time.perf_counter() - started
    table.fetch(out, 2)
    after = {
        n: reg.histogram(f"state_table.{n}_s").merged() for n in names
    }
    grew = {n: after[n].count - before[n].count for n in names}
    assert grew == {"context": 3, "call": 3, "fetch": 1}
    parts = sum(
        after[n].total - before[n].total for n in ("context", "call")
    )
    assert after["context"].total - before["context"].total >= 3 * 0.002
    assert parts <= step_wall
    assert parts == pytest.approx(step_wall, rel=0.1)


# ------------------------------------------------------------ the heartbeat


def _stall(reg):
    return (
        reg.counter("host.stalls").value(),
        reg.counter("host.stall_wall_s").value(),
        reg.counter("host.stall_cpu_s").value(),
    )


def test_heartbeat_tells_a_sleep_under_the_gil_from_a_busy_loop():
    """Both stall the heartbeat, which needs the interpreter lock to
    wake. A native sleep that holds the lock burns no CPU (to the
    process it looks as if the host did not run it); one long native
    call that holds it burns a core."""
    reg = MetricsRegistry()
    beat = Heartbeat(reg).start()
    try:
        time.sleep(0.1)
        assert _stall(reg)[0] == 0
        assert reg.histogram("host.heartbeat_lag_s").count >= 5
        # PyDLL keeps the GIL across the call: 0.4 s asleep under it.
        ctypes.PyDLL(None).usleep(400_000)
        time.sleep(0.05)
        stalls, wall, cpu = _stall(reg)
        assert stalls >= 1 and wall >= 0.3
        assert cpu <= 0.15 * wall, (wall, cpu)

        # One bytecode, all in C, holding the GIL while it computes.
        started = time.perf_counter()
        n = 5_000_000
        while time.perf_counter() - started < 0.3:
            n *= 2
            lap = time.perf_counter()
            sum(range(n))
            if time.perf_counter() - lap >= 0.3:
                break
        time.sleep(0.05)
        stalls2, wall2, cpu2 = _stall(reg)
        assert stalls2 > stalls
        busy_wall, busy_cpu = wall2 - wall, cpu2 - cpu
        assert busy_wall >= 0.2
        # (Well under 1.0: the suite's other workers share the cores.)
        assert busy_cpu >= 0.3 * busy_wall, (busy_wall, busy_cpu)
        assert reg.histogram("host.heartbeat_lag_s").merged().max >= 0.2
    finally:
        beat.stop()
    assert not beat._thread.is_alive()


# ------------------------------------------------------ the native actor


def test_native_env_rtt_reaches_the_registry_after_one_fold():
    from torchbeast_tpu.envs import CountingEnv
    from torchbeast_tpu.runtime.env_server import EnvServer
    from torchbeast_tpu.runtime.native import (
        NativeTelemetryFolder,
        import_native,
    )

    core = import_native()
    if core is None:
        pytest.skip("_tbt_core not built (run scripts/build_native.sh)")
    path = os.path.join(tempfile.mkdtemp(), "env_rtt")
    server = EnvServer(lambda: CountingEnv(episode_length=5), f"unix:{path}")
    server.start()
    deadline = time.monotonic() + 5
    while not os.path.exists(path):
        assert time.monotonic() < deadline, "server did not bind"
        time.sleep(0.01)
    learner_queue = core.BatchingQueue(
        batch_dim=1, minimum_batch_size=1, maximum_batch_size=1
    )
    batcher = core.DynamicBatcher(batch_dim=1, timeout_ms=20)

    def inference():
        for batch in batcher:
            done = batch.get_inputs()["env"]["done"]
            batch.set_outputs({
                "outputs": {
                    "action": np.zeros_like(done, np.int32),
                    "policy_logits": np.zeros(done.shape + (1,), np.float32),
                    "baseline": np.zeros(done.shape, np.float32),
                },
                "agent_state": np.zeros(done.shape, np.int64),
            })

    threading.Thread(target=inference, daemon=True).start()
    pool = core.ActorPool(
        unroll_length=4, learner_queue=learner_queue,
        inference_batcher=batcher, env_server_addresses=[f"unix:{path}"],
        initial_agent_state=np.zeros((1, 1), np.int64),
    )
    pool_thread = threading.Thread(target=pool.run, daemon=True)
    pool_thread.start()
    try:
        items = iter(learner_queue)
        for _ in range(3):
            next(items)
        reg = MetricsRegistry()
        folder = NativeTelemetryFolder(reg, pool=pool)
        # The pool's scalar dict stays all scalars (the benchmark
        # subtracts every value of it).
        assert all(
            isinstance(v, int) for v in pool.telemetry().values()
        )
        folder.tick()
        rtt = reg.histogram("actor.env_rtt_s")
        steps = reg.counter("actor.env_steps").value()
        assert rtt.count >= 12 and abs(rtt.count - steps) <= 2
        assert 0 < rtt.mean < 1.0
        # Interval semantics: the next fold adds only what came since.
        seen = rtt.count
        folder.tick()
        assert rtt.count - seen <= 8
    finally:
        batcher.close()
        learner_queue.close()
        pool_thread.join(5)
        server.stop()
