"""One process a stream: an env server that has a process to itself
(`polybeast_env._serve`) forks a child for every accepted stream, so an
env step holds no other stream's GIL; `EnvServer.start()` inside
someone else's process keeps the thread per connection.

The servers under test run in spawned processes of their own, as the
drivers run them: a fork from the pytest process (JAX, threads) is what
the mechanism exists to avoid.
"""

import multiprocessing as mp
import os
import signal
import tempfile
import time

import numpy as np
import psutil
import pytest

from tests.test_shm_transport import _run_pool
from torchbeast_tpu import polybeast_env
from torchbeast_tpu.envs import create_env
from torchbeast_tpu.runtime import transport, wire
from torchbeast_tpu.runtime.env_server import EnvServer
from torchbeast_tpu.utils import install_preemption_handler

STREAMS = 8
SEED_BASE = 4100
KINDS = ["shm", "unix"]


@pytest.fixture(autouse=True)
def time_limit():
    """Every test here waits on other processes: none may wait for
    longer than this."""

    def expired(signum, frame):
        raise TimeoutError("test exceeded its 120 s limit")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(120)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


class ProbeEnv:
    """Says who steps it: every frame is [pid, seed, steps taken]."""

    num_actions = 2

    def __init__(self, seed=None):
        self._seed = -1 if seed is None else seed
        self._t = 0

    def _frame(self):
        return np.array([os.getpid(), self._seed, self._t], np.int64)

    def reset(self):
        self._t = 0
        return self._frame()

    def step(self, action):
        self._t += 1
        return self._frame(), 0.0, False


def _host_probe_server(address):
    """Spawn target: a process that is the server and nothing else, as
    `polybeast_env._serve` is, around the env that names its process."""
    install_preemption_handler()
    server = EnvServer(ProbeEnv, address, seed_base=SEED_BASE,
                       stream_processes=True)
    try:
        server.run()
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()


def _wait(condition, what, timeout_s=45.0):
    deadline = time.monotonic() + timeout_s
    while not condition():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.02)


def _spawn(target, args, path):
    proc = mp.get_context("spawn").Process(
        target=target, args=args, daemon=True
    )
    proc.start()
    try:
        _wait(lambda: os.path.exists(path), "the server to bind", 30)
    except BaseException:
        proc.kill()
        raise
    return proc


@pytest.fixture(params=KINDS)
def probe_server(request):
    path = os.path.join(tempfile.mkdtemp(), "probe")
    proc = _spawn(_host_probe_server, (f"{request.param}:{path}",), path)
    yield proc, f"{request.param}:{path}"
    proc.kill()
    proc.join(10)


def _connect(address, n):
    """n streams, each past its initial step: [(transport, pid, seed)]."""
    streams = []
    for _ in range(n):
        stream = transport.connect_transport(address, timeout_s=20)
        pid, seed, t = (int(x) for x in stream.recv()["frame"])
        assert t == 0
        streams.append((stream, pid, seed))
    return streams


def _step(stream):
    """One step of a ProbeEnv stream: its [pid, seed, steps taken]."""
    stream.send({"type": "action", "action": 1})
    return [int(x) for x in stream.recv()["frame"]]


def _assert_severed(stream):
    """The stream's server end is gone: an error or a clean EOF, within
    the step that was in flight."""
    with pytest.raises((wire.WireError, ConnectionError, OSError)):
        for _ in range(3):
            stream.send({"type": "action", "action": 0})
            if stream.recv() is None:
                raise ConnectionError("clean EOF")


def _children(pid):
    return {c.pid for c in psutil.Process(pid).children()}


def _gone(pid):
    """Neither running nor a zombie: collected."""
    return not psutil.pid_exists(pid)


def _segment_paths(streams):
    return [
        os.path.join("/dev/shm", name.lstrip("/"))
        for stream, _, _ in streams
        if hasattr(stream, "segment_names")
        for name in stream.segment_names
    ]


def test_hosted_server_steps_each_stream_in_its_own_process(probe_server):
    proc, address = probe_server
    streams = _connect(address, STREAMS)
    try:
        pids = {pid for _, pid, _ in streams}
        assert len(pids) == STREAMS and proc.pid not in pids
        assert pids == _children(proc.pid)
        # The listener draws a stream's index before it forks: a counter
        # advanced in the child would seed all eight alike.
        assert {seed for _, _, seed in streams} == set(
            range(SEED_BASE, SEED_BASE + STREAMS)
        )
        for round_ in (1, 2, 3):
            for stream, pid, seed in streams:
                assert _step(stream) == [pid, seed, round_]
        assert psutil.Process(proc.pid).num_threads() == 1
    finally:
        for stream, _, _ in streams:
            stream.close()
    # A stream that ends takes its process with it, and the listener
    # collects it: no zombie.
    _wait(lambda: not _children(proc.pid), "the children to be reaped")


@pytest.mark.parametrize("kind", KINDS)
def test_in_process_start_still_serves_by_threads(kind):
    path = os.path.join(tempfile.mkdtemp(), "threads")
    address = f"{kind}:{path}"
    server = EnvServer(ProbeEnv, address, seed_base=SEED_BASE)
    children_before = _children(os.getpid())
    server.start()
    try:
        _wait(lambda: os.path.exists(path), "the server to bind")
        streams = _connect(address, STREAMS)
        try:
            assert {pid for _, pid, _ in streams} == {os.getpid()}
            # Thread mode's seed set is the hosted mode's.
            assert {seed for _, _, seed in streams} == set(
                range(SEED_BASE, SEED_BASE + STREAMS)
            )
            for stream, pid, seed in streams:
                assert _step(stream) == [pid, seed, 1]
            assert _children(os.getpid()) == children_before
        finally:
            for stream, _, _ in streams:
                stream.close()
    finally:
        server.stop()
    with pytest.raises(ValueError, match="fork"):
        EnvServer(ProbeEnv, address, stream_processes=True).start()


def _catch_trace(recv_frame, step_frame, steps=30):
    """A stream's frames under a fixed action sequence, as bytes: with
    its seed, its identity (three Catch episodes' ball columns)."""
    frames = [recv_frame()]
    for t in range(steps):
        frames.append(step_frame(t % 3))
    return b"".join(np.ascontiguousarray(f).tobytes() for f in frames)


def test_serve_seeds_its_streams_as_the_parent_commit_did():
    """`polybeast_env._serve` with `--env_seed`: stream s of a server
    steps create_env(seed=seed_base + s), one seed each, whichever
    process builds the env."""
    path = os.path.join(tempfile.mkdtemp(), "catch")
    address = f"shm:{path}"
    proc = _spawn(
        polybeast_env._serve, ("Catch", address, False, SEED_BASE), path
    )
    streams = []
    try:
        for _ in range(STREAMS):
            streams.append(transport.connect_transport(address, timeout_s=20))
        served = set()
        for stream in streams:
            def step(action, stream=stream):
                stream.send({"type": "action", "action": action})
                return stream.recv()["frame"].copy()

            served.add(_catch_trace(
                lambda stream=stream: stream.recv()["frame"].copy(), step
            ))
        assert len(_children(proc.pid)) == STREAMS
    finally:
        for stream in streams:
            stream.close()
        proc.terminate()
        proc.join(10)
    expected = set()
    for s in range(STREAMS):
        env = create_env("Catch", seed=SEED_BASE + s)
        expected.add(_catch_trace(
            env.reset, lambda action, env=env: _autoreset(env, action)
        ))
    assert len(expected) == STREAMS  # the traces tell the seeds apart
    assert served == expected


def _autoreset(env, action):
    frame, _, done = env.step(action)
    return env.reset() if done else frame


@pytest.mark.parametrize("how", ["sigterm", "sigint", "sigterm_group"])
def test_a_stopped_server_leaves_nothing_behind(how):
    """SIGTERM to the listener (`reap_group`), a Ctrl-C (stop() alone
    must end the children) and SIGTERM to every process at once (the
    benchmark's `kill_group`): no child, no zombie, no shm segment."""
    path = os.path.join(tempfile.mkdtemp(), "stop")
    address = f"shm:{path}"
    proc = _spawn(polybeast_env._serve, ("Mock", address, False, 7), path)
    streams = []
    try:
        for _ in range(STREAMS):
            stream = transport.connect_transport(address, timeout_s=20)
            stream.recv()
            streams.append((stream, None, None))
        children = _children(proc.pid)
        segments = _segment_paths(streams)
        assert len(children) == STREAMS
        assert len(segments) == 2 * STREAMS
        assert all(os.path.exists(p) for p in segments)
        if how == "sigint":
            os.kill(proc.pid, signal.SIGINT)
        else:
            targets = [proc.pid]
            if how == "sigterm_group":
                targets += sorted(children)
            for pid in targets:
                try:
                    os.kill(pid, signal.SIGTERM)
                except ProcessLookupError:
                    # The listener got its signal first and, on a host
                    # that keeps this process waiting between two
                    # kills, has ended and collected this child
                    # already (D16); `kill_group` passes over it too.
                    assert pid != proc.pid
        # Long enough for a host that six test workers load: the
        # listener's own teardown is at most 4 s of grace.
        proc.join(45)
        assert not proc.is_alive() and proc.exitcode == 0
        for pid in children:
            _wait(lambda pid=pid: _gone(pid), f"stream process {pid} to go")
        assert [p for p in segments if os.path.exists(p)] == []
        assert not os.path.exists(path)
        _assert_severed(streams[0][0])  # not left hanging
    finally:
        for stream, _, _ in streams:
            stream.close()
        proc.kill()
        proc.join(10)


def test_killed_stream_process_ends_only_its_own_stream(probe_server):
    proc, address = probe_server
    streams = _connect(address, 3)
    try:
        (victim, victim_pid, _), *others = streams
        segments = _segment_paths(streams[:1])
        os.kill(victim_pid, signal.SIGKILL)
        _assert_severed(victim)
        # The listener collects the child (no zombie) and unlinks what
        # it could not unlink itself.
        _wait(lambda: _gone(victim_pid), "the killed child to be reaped")
        _wait(lambda: not any(os.path.exists(p) for p in segments),
              "the killed child's segments to be swept")
        for stream, pid, seed in others:
            assert _step(stream) == [pid, seed, 1]
        # The client's reconnect: a new stream, a new process, the next
        # seed.
        (again, new_pid, new_seed), = _connect(address, 1)
        streams.append((again, new_pid, new_seed))
        assert new_pid not in {pid for _, pid, _ in streams[:3]}
        assert new_seed == SEED_BASE + 3
        assert _step(again) == [new_pid, new_seed, 1]
        assert _children(proc.pid) == {
            new_pid, *(pid for _, pid, _ in others)
        }
    finally:
        for stream, _, _ in streams:
            stream.close()


def test_actor_pool_reconnects_past_a_killed_stream_process(probe_server):
    """The actor's own reconnect path, as after a dead stream thread:
    one reconnect for the one stream that died, none for its
    neighbour."""
    proc, address = probe_server
    learner_queue, batcher, pool, pool_thread = _run_pool(
        address, max_reconnects=3
    )
    try:
        rollouts = iter(learner_queue)
        next(rollouts)
        _wait(lambda: len(_children(proc.pid)) == 1, "the actor's stream")
        (child,) = _children(proc.pid)
        os.kill(child, signal.SIGKILL)
        _wait(lambda: pool.reconnects >= 1, "the actor to reconnect")
        for _ in range(3):
            next(rollouts)
        assert pool.errors == []
        assert pool.reconnects == 1
        (replacement,) = _children(proc.pid)
        assert replacement != child
    finally:
        batcher.close()
        learner_queue.close()
        pool_thread.join(5)
