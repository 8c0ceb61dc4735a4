"""One actor cycle, every term stamped where it happens (ISSUE 66): the
native pool's stage histograms against env servers whose behaviour the
test sets.

    cycle   = request_rtt + reply_wake + own + env_rtt   (+ the enqueue)
    env_rtt = env_wire_down + env_step + env_wire_up      (exactly)

The env server's half rides back on the step message as two integers
(`server_recv_ns`, `server_stepped_ns`) on the machine's monotonic
clock; the pool checks by the initial Step that the clock is shared and
otherwise observes `env_step_s` alone. Every test serves its batches by
hand, so that the priming request (in no cycle) can be cut off and every
histogram covers the same iterations."""

import os
import tempfile
import threading
import time

import numpy as np
import pytest

from torchbeast_tpu.envs import CountingEnv
from torchbeast_tpu.runtime import actor_pool as python_pool
from torchbeast_tpu.runtime import env_server
from torchbeast_tpu.runtime.native import import_native
from tests.test_env_server import make_server

core = import_native()
pytestmark = pytest.mark.skipif(
    core is None, reason="_tbt_core not built (run scripts/build_native.sh)"
)

WAIT_S = 20
STEP_SLEEP_S = 0.005
HOUR_NS = 3600 * 10**9
WIRE_TERMS = ("actor.env_wire_down_s", "actor.env_wire_up_s")
STAGES = {
    "actor.env_rtt_s", "actor.env_wire_down_s", "actor.env_step_s",
    "actor.env_wire_up_s", "actor.reply_wake_s", "actor.own_s",
    "actor.cycle_s",
}


class SleepyEnv(CountingEnv):
    def step(self, action):
        time.sleep(STEP_SLEEP_S)
        return super().step(action)


def _wait_for(condition, what):
    deadline = time.monotonic() + WAIT_S
    while not condition():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def _serve(env_init, kind, impl="python"):
    path = os.path.join(tempfile.mkdtemp(), "cycle")
    address = f"{kind}:{path}"
    server = make_server(env_init, address, impl)
    server.start()
    _wait_for(lambda: os.path.exists(path), "the server to bind")
    return server, address


class Cycles:
    """A native pool of `actors` streams on one server, its batcher
    served by hand a batch of `actors` rows at a time: `run(k)` answers
    the priming requests, drops what the batcher stamped for them, then
    answers k rounds and returns (stage histograms, the batcher's,
    the pool's counters) once every actor has asked for round k + 1:
    each actor has then finished exactly k cycles."""

    def __init__(self, address, actors=1, extra_output=None):
        self.actors, self.extra_output = actors, extra_output
        self.learner_queue = core.BatchingQueue(
            batch_dim=1, minimum_batch_size=1, maximum_batch_size=1
        )
        self.batcher = core.DynamicBatcher(
            batch_dim=1, minimum_batch_size=actors,
            maximum_batch_size=actors,
        )
        self.pool = core.ActorPool(
            # Longer than any run here: no rollout is enqueued, so an
            # iteration's own stretch is the push alone.
            unroll_length=1000,
            learner_queue=self.learner_queue,
            inference_batcher=self.batcher,
            env_server_addresses=[address] * actors,
            initial_agent_state=np.zeros((1, 1), np.int64),
        )
        self.thread = threading.Thread(target=self.pool.run, daemon=True)
        self.thread.start()
        self.batches = iter(self.batcher)

    def answer(self):
        batch = next(self.batches)
        inputs = batch.get_inputs()
        done = inputs["env"]["done"]  # [1, B]
        assert done.shape[1] == self.actors
        outputs = {
            "action": np.zeros_like(done, np.int32),
            "policy_logits": np.zeros(done.shape + (2,), np.float32),
            "baseline": np.zeros(done.shape, np.float32),
        }
        if self.extra_output is not None:
            outputs["extra"] = self.extra_output
        batch.set_outputs({
            "outputs": outputs,
            "agent_state": np.zeros(done.shape, np.int64),
        })

    def _all_waiting(self):
        _wait_for(
            lambda: self.batcher.size() == self.actors,
            "every actor's next request",
        )

    def run(self, rounds):
        self._all_waiting()
        self.answer()  # the priming requests: in no cycle
        self._all_waiting()
        self.batcher.telemetry()  # resets on read
        primed = self.pool.stage_histograms()
        assert all(h["count"] == 0 for h in primed.values()), primed
        for _ in range(rounds):
            self.answer()
            self._all_waiting()
        return (
            self.pool.stage_histograms(), self.batcher.telemetry(),
            self.pool.telemetry(),
        )

    def close(self):
        self.batcher.close()
        self.learner_queue.close()
        self.thread.join(WAIT_S)
        assert not self.thread.is_alive()
        assert self.pool.first_error_message() is None


def _mean(hist):
    return hist["total"] / hist["count"]


@pytest.fixture
def running():
    """Whatever a test started, stopped afterwards in the right order."""
    started = []
    yield started
    for thing in reversed(started):
        thing.close() if isinstance(thing, Cycles) else thing.stop()


def _cycles(running, env_init, kind="shm", impl="python", **kwargs):
    server, address = _serve(env_init, kind, impl)
    running.append(server)
    cycles = Cycles(address, **kwargs)
    running.append(cycles)
    return cycles


# ------------------------------------------------------ (a) the env's half


@pytest.mark.parametrize("impl,kind", [
    ("python", "shm"), ("python", "unix"), ("native", "shm"),
])
def test_env_round_trip_is_cut_in_three_that_sum_to_it(running, impl, kind):
    """A fake env that sleeps 5 ms in `step`: the server's two stamps
    say so, the wire terms are what is left, and the three are cut from
    the same four stamps as env_rtt_s."""
    rounds = 20
    cycles = _cycles(running, SleepyEnv, kind, impl)
    stages, _, counters = cycles.run(rounds)
    assert set(stages) == STAGES
    rtt, step = stages["actor.env_rtt_s"], stages["actor.env_step_s"]
    down, up = (stages[name] for name in WIRE_TERMS)
    assert rtt["count"] == step["count"] == rounds
    assert down["count"] == up["count"] == rounds
    assert _mean(step) >= STEP_SLEEP_S
    for term in (down, up):
        assert term["min"] > 0.0
        assert _mean(term) < _mean(step)
    parts = down["total"] + step["total"] + up["total"]
    assert parts == pytest.approx(rtt["total"], rel=1e-6)
    assert counters["env_clock_unshared"] == 0
    assert counters["env_steps"] == rounds


# ------------------------------------------------- (b) the clock is checked


def _shifted(monkeypatch, which, shift_ns):
    """The Python server's message maker with the stamps of the initial
    Step, or of every other, moved by `shift_ns`."""
    real = env_server._step_to_message

    def moved(step, stepped_ns, recv_ns=0):
        initial = recv_ns == 0
        if initial == (which == "initial"):
            stepped_ns += shift_ns
            recv_ns = recv_ns + shift_ns if recv_ns else 0
        return real(step, stepped_ns, recv_ns)

    monkeypatch.setattr(env_server, "_step_to_message", moved)


@pytest.mark.parametrize("shift_ns", [HOUR_NS, -HOUR_NS])
@pytest.mark.parametrize("which", ["initial", "steps"])
def test_a_clock_that_is_not_shared_observes_the_step_alone(
    running, monkeypatch, which, shift_ns
):
    """A server on another machine reads another monotonic clock: its
    initial reading lies after the client's receipt of it, or long
    before the client began to connect. Such a stream counts once, its
    env_step_s (a difference of the server's own stamps) is good, and no
    wire term, which would be a difference of two clocks, is observed.
    Stamps that pass the initial check and are then out of order with
    the client's own end the wire terms the same way: never a negative
    sample."""
    rounds = 6
    _shifted(monkeypatch, which, shift_ns)
    cycles = _cycles(running, SleepyEnv)
    stages, _, counters = cycles.run(rounds)
    assert counters["env_clock_unshared"] == 1
    step = stages["actor.env_step_s"]
    assert step["count"] == stages["actor.env_rtt_s"]["count"] == rounds
    assert STEP_SLEEP_S <= _mean(step) < 1.0
    for name in WIRE_TERMS:
        assert stages[name]["count"] == 0


# ------------------------------------------------ (c) old shapes still run


def test_a_step_message_without_stamps_runs_and_observes_no_env_term(
    running, monkeypatch
):
    """A server from before the stamps: the message simply lacks them."""
    real = env_server._step_to_message

    def old_shape(step, stepped_ns, recv_ns=0):
        msg = real(step, stepped_ns, recv_ns)
        del msg["server_stepped_ns"]
        msg.pop("server_recv_ns", None)
        return msg

    monkeypatch.setattr(env_server, "_step_to_message", old_shape)
    rounds = 5
    cycles = _cycles(running, CountingEnv)
    stages, _, counters = cycles.run(rounds)
    assert stages["actor.env_rtt_s"]["count"] == rounds
    assert stages["actor.cycle_s"]["count"] == rounds
    assert stages["actor.env_step_s"]["count"] == 0
    for name in WIRE_TERMS:
        assert stages[name]["count"] == 0
    assert counters["env_clock_unshared"] == 0


@pytest.mark.parametrize("impl", ["python", "native"])
def test_a_client_that_knows_neither_key_takes_the_env_keys(running, impl):
    """The Python pool reads a step by the env's keys alone; both
    servers put the two integers beside them, the initial Step the
    second only."""
    server, address = _serve(CountingEnv, "unix", impl)
    running.append(server)
    from torchbeast_tpu.runtime import transport

    before = time.monotonic_ns()
    stream = transport.connect_transport(address, 10)
    try:
        initial = stream.recv()
        assert "server_recv_ns" not in initial
        assert before <= initial["server_stepped_ns"] <= time.monotonic_ns()
        sent = time.monotonic_ns()
        stream.send({"type": "action", "action": 1})
        step = stream.recv()
        received = time.monotonic_ns()
        assert type(step["server_recv_ns"]) is int
        assert (
            sent <= step["server_recv_ns"] <= step["server_stepped_ns"]
            <= received
        )
        env = python_pool.ActorPool._env_outputs(step)
        assert set(env) == set(python_pool._ENV_KEYS)
        assert int(env["episode_step"][0, 0]) == 1
    finally:
        stream.close()


# ------------------------------------- (d) the reply's tail, and the whole


def test_the_reply_wake_sees_what_request_rtt_ends_before(running):
    """request_rtt_s ends when set_outputs is ENTERED; the rows are
    sliced and their promises set one after another from there. With
    eight rows of 4 MB a row, row i's actor is held back by the slices
    of the i rows before it: reply_wake_s sees that, request_rtt_s does
    not, and the cycle's terms still add up to the cycle."""
    actors, rounds = 8, 4
    row = np.zeros((1, actors, 1 << 20), np.float32)  # 4 MB a row
    # In-process server threads need the GIL to step; an answer holds it
    # throughout, so nothing else moves while the rows are handed out.
    cycles = _cycles(
        running, CountingEnv, "unix", actors=actors, extra_output=row
    )
    stages, batcher, _ = cycles.run(rounds)
    n = actors * rounds
    cycle, wake = stages["actor.cycle_s"], stages["actor.reply_wake_s"]
    own, env = stages["actor.own_s"], stages["actor.env_rtt_s"]
    rtt = batcher["request_rtt_s"]
    assert cycle["count"] == wake["count"] == own["count"] == n
    assert env["count"] == rtt["count"] == n
    # The last row waits for seven slices of 4 MB; the first for one.
    assert wake["max"] >= 0.002
    assert wake["min"] < wake["max"] / 3
    assert own["min"] > 0.0
    # Nothing is counted twice and nothing is left out: were the
    # slices inside request_rtt_s too, the sum would pass the cycle.
    parts = rtt["total"] + wake["total"] + own["total"] + env["total"]
    assert parts <= cycle["total"]
    assert parts == pytest.approx(cycle["total"], rel=0.01)


def test_the_folder_folds_every_stage_and_the_unshared_count():
    """NativeTelemetryFolder registers a series for whatever stage the
    pool hands it, and the counter for env_clock_unshared."""
    from torchbeast_tpu.runtime.native import NativeTelemetryFolder
    from torchbeast_tpu.telemetry.metrics import MetricsRegistry

    def hist(values):
        return {
            "count": len(values), "total": sum(values),
            "total_sq": sum(v * v for v in values),
            "min": min(values, default=0.0), "max": max(values, default=0.0),
            "buckets": {80: len(values)} if values else {},
        }

    class FakePool:
        unshared = 1

        def telemetry(self):
            return {
                "env_steps": 2, "connects": 1, "reconnects": 0,
                "bytes_up": 0, "bytes_down": 0,
                "env_clock_unshared": self.unshared,
            }

        def stage_histograms(self):
            return {name: hist([1e-3, 1e-3]) for name in STAGES}

    pool, registry = FakePool(), MetricsRegistry()
    folder = NativeTelemetryFolder(registry, pool=pool)
    folder.tick()
    pool.unshared = 3
    folder.tick()
    assert registry.counter("actor.env_clock_unshared").value() == 3
    for name in STAGES:
        folded = registry.histogram(name)
        assert folded.count == 4
        assert folded.mean == pytest.approx(1e-3)
