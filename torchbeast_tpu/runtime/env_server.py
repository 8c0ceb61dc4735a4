"""Environment server: hosts environments behind a streaming socket.

The reference's gRPC `EnvServer` (/root/reference/src/cc/rpcenv.cc:36-211,
driven by polybeast_env.py:61-77) re-designed over the framed-socket wire
protocol: each incoming connection gets a FRESH environment instance
(reference rpcenv.cc:72), the server sends the initial Step, then loops
recv(Action) -> env.step -> send(Step). Episode accounting and auto-reset
live in the Environment adapter (envs/environment.py), matching the
reference's server-side bookkeeping (rpcenv.cc:106-119).

Env exceptions are reported to the client as an error message frame (the
reference surfaces them as grpc INTERNAL status, rpcenv.cc:76-81).

Addresses: "unix:/path", "host:port" (same convention as the reference's
pipes_basename, polybeast_learner.py:40-42), or "shm:/path" — shared-
memory rings with a unix doorbell socket at /path, for env servers
co-located with the learner process (runtime/transport.py): obs/action
frames skip the socket data plane entirely.
"""

import ctypes
import itertools
import logging
import os
import signal
import socket
import sys
import threading
import time
from typing import Callable, Optional

import numpy as np

from torchbeast_tpu.envs.environment import Environment
from torchbeast_tpu.runtime import transport as transport_lib
from torchbeast_tpu.runtime import wire

# Re-exported: parse_address lived here before the transport module
# existed and tests/drivers import it from this path.
from torchbeast_tpu.runtime.transport import parse_address  # noqa: F401
from torchbeast_tpu.utils.preempt import install_preemption_handler

log = logging.getLogger(__name__)

# How often a listener that forks its streams wakes from accept() to
# reap the children that have exited.
_REAP_PERIOD_S = 0.5
_PR_SET_PDEATHSIG = 1  # <linux/prctl.h>


def _die_with_parent() -> None:
    """Have the kernel SIGTERM this process when its parent dies (a
    SIGKILLed listener cannot end its streams itself, and a dead server
    has always meant dropped streams: the supervisor's and the actors'
    accounting count on it). Linux only; elsewhere an orphaned stream
    serves until its client hangs up."""
    if not sys.platform.startswith("linux"):
        return
    prctl = ctypes.CDLL(None, use_errno=True).prctl
    prctl.argtypes = [ctypes.c_int, ctypes.c_ulong, ctypes.c_ulong,
                      ctypes.c_ulong, ctypes.c_ulong]
    prctl.restype = ctypes.c_int
    if prctl(_PR_SET_PDEATHSIG, signal.SIGTERM, 0, 0, 0) != 0:
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_PDEATHSIG)")


def _step_to_message(step, stepped_ns: int, recv_ns: int = 0) -> dict:
    """`stepped_ns`, `recv_ns`: the server's half of an actor's cycle,
    carried back on the message as two plain integers (ISSUE 66):
    `time.monotonic_ns()` when the action arrived (the initial Step has
    none) and when the env returned from `step` (or `initial`). On Linux
    that clock is the machine's, the same number in every process, so
    the native actor pool subtracts its own stamps from these
    (csrc/actor_pool.h: actor.env_wire_down_s, env_step_s,
    env_wire_up_s) with nothing to synchronise; a client that knows
    neither key takes the env's keys and ignores them."""
    # 0-d arrays (not python scalars) so dtypes survive the wire exactly:
    # reward stays float32, done bool, counters int32.
    msg = {"type": "step", **{k: np.asarray(v) for k, v in step.items()}}
    msg["server_stepped_ns"] = stepped_ns
    if recv_ns:
        msg["server_recv_ns"] = recv_ns
    return msg


class EnvServer:
    """Serve env streams: one thread per connection or, where the server
    has a process to itself (`stream_processes=True`), one forked child
    per connection, so that an env step holds no other stream's GIL."""

    def __init__(self, env_init: Callable, address: str,
                 max_frame_bytes: Optional[int] = None,
                 obs_ring_bytes: int = transport_lib.DEFAULT_OBS_RING_BYTES,
                 act_ring_bytes: int = transport_lib.DEFAULT_ACT_RING_BYTES,
                 seed_base: Optional[int] = None,
                 stream_processes: bool = False):
        """`seed_base`: stream s (in accept order) is built as
        `env_init(seed=seed_base + s)`; None calls `env_init()`.

        `stream_processes`: run() forks a child for every accepted
        stream and only accepts, supervises and sweeps itself. A fork
        is safe only from a process with no other thread, so this is
        for a server process whose main thread calls run() and that
        never imported JAX (`polybeast_env._serve`); start() refuses
        it."""
        self._env_init = env_init
        self._address = address
        self._shm = transport_lib.is_shm_address(address)
        self._max_frame_bytes = max_frame_bytes
        self._obs_ring_bytes = obs_ring_bytes
        self._act_ring_bytes = act_ring_bytes
        self._seed_base = seed_base
        self._stream_processes = stream_processes
        # Drawn by the listener, one per accepted stream: a counter
        # advanced inside a forked child would hand every stream index 0.
        self._stream_index = itertools.count()
        self._family, self._target = parse_address(address)
        # Control fields shared between run() (its own thread under
        # start()), the per-stream threads, and stop() (caller thread):
        # all guarded by the conns lock (RACE burn-down, ISSUE 7).
        self._sock = None  # guarded-by: self._conns_lock
        self._threads = []  # guarded-by: self._conns_lock
        # Permanent stop latch: a stop() that wins the race against a
        # just-starting run() (before the listener is published) must
        # still stop it — run() re-checks this at publish time.
        self._stopped = False  # guarded-by: self._conns_lock
        self._conns = []
        # Live stream children (stream_processes), by pid.
        self._children = set()  # guarded-by: self._conns_lock
        # stream (its conn, or its child's pid) -> shm segment names of
        # a live shm stream: reaping a child and stop()'s owner-side
        # sweep unlink whatever the stream itself didn't get to
        # (ISSUE 6 — SIGKILL chaos must not grow /dev/shm).
        self._ring_names = {}  # guarded-by: self._conns_lock
        self._conns_lock = threading.Lock()
        self._running = False  # guarded-by: self._conns_lock
        # No instrument of its own: a server's registry (a listener's,
        # a stream child's) is written nowhere. Its step time rides back
        # on the step message (_step_to_message) and its bytes are the
        # client's wire.bytes_up / bytes_down.

    def run(self):
        """Bind and serve until stop() (reference Server.run blocks too,
        rpcenv.cc:142-156)."""
        sock = socket.socket(self._family, socket.SOCK_STREAM)
        if self._family == socket.AF_UNIX:
            try:
                os.unlink(self._target)
            except FileNotFoundError:
                pass
        else:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind(self._target)
        sock.listen(16)
        # Publish the listener + running flag under the lock: a stop()
        # racing a just-starting run() either already latched _stopped
        # (we tear down here and never serve) or sees the published
        # socket and closes it.
        with self._conns_lock:
            if self._stopped:
                sock.close()
                if self._family == socket.AF_UNIX:
                    try:
                        os.unlink(self._target)
                    except FileNotFoundError:
                        pass
                return
            self._sock = sock
            self._running = True
        log.info("EnvServer listening on %s", self._address)
        if self._stream_processes:
            sock.settimeout(_REAP_PERIOD_S)  # wake to reap
        while True:
            with self._conns_lock:
                if not self._running:
                    break
            try:
                conn, _ = sock.accept()
            except socket.timeout:
                self._reap_children()
                continue
            except OSError:
                break  # socket closed by stop()
            index = next(self._stream_index)
            if self._stream_processes:
                self._fork_stream(sock, conn, index)
                self._reap_children()
                continue
            # Register the conn BEFORE spawning its thread so a concurrent
            # stop() can never miss a just-accepted stream.
            with self._conns_lock:
                if not self._running:
                    conn.close()
                    break
                self._conns.append(conn)
            t = threading.Thread(
                target=self._serve_stream, args=(conn, index), daemon=True
            )
            t.start()
            # Prune finished stream threads so reconnect-heavy workloads
            # don't grow this list unboundedly.
            with self._conns_lock:
                self._threads = [
                    x for x in self._threads if x.is_alive()
                ] + [t]

    def _fork_stream(self, listener: socket.socket, conn: socket.socket,
                     index: int):
        """Hand an accepted stream to a child of its own. The rings are
        created here, before the fork, so that this process knows the
        segment names of every child whatever becomes of it."""
        # SIGTERM / Ctrl-C are held back across the fork: the unwinding
        # they start must begin neither in a child that still stands in
        # the listener's frames, nor here before the child is on record.
        held = signal.pthread_sigmask(
            signal.SIG_BLOCK, {signal.SIGTERM, signal.SIGINT}
        )
        rings = None
        try:
            if self._shm:
                rings = transport_lib.create_rings(
                    self._obs_ring_bytes, self._act_ring_bytes
                )
            pid = os.fork()
            if pid == 0:
                self._stream_child(listener, conn, index, rings, held)
            with self._conns_lock:
                self._children.add(pid)
                if rings is not None:
                    self._ring_names[pid] = tuple(r.name for r in rings)
            for ring in rings or ():
                ring.detach()  # the child's to unlink now
        except OSError:
            # Out of pids, memory or /dev/shm: this stream is lost (its
            # actor reconnects), the server is not.
            log.exception("Could not start a process for stream %d", index)
            for ring in rings or ():
                ring.close()
        finally:
            # The child's copy of the connection is the stream: with
            # ours closed, the child's death is the client's EOF.
            conn.close()
            signal.pthread_sigmask(signal.SIG_SETMASK, held)

    def _stream_child(self, listener: socket.socket, conn: socket.socket,
                      index: int, rings, sigmask):
        """A stream child's whole life, never returning: the stream loop
        on the inherited connection, then out without the listener's
        atexit and multiprocessing teardown."""
        code = 1
        try:
            _die_with_parent()
            # SIGTERM unwinds through _serve_stream's teardown (env
            # closed, rings unlinked); a repeat is ignored.
            install_preemption_handler()
            signal.pthread_sigmask(signal.SIG_SETMASK, sigmask)
            listener.close()  # the parent's to accept on, not ours
            self._serve_stream(conn, index, rings)
            code = 0
        except KeyboardInterrupt:
            code = 0
        except BaseException:  # noqa: BLE001 - reported, then out
            log.exception("Stream %d's process failed", index)
        finally:
            logging.shutdown()
            os._exit(code)

    def _reap_children(self):
        """Collect the stream children that have exited (no zombies)
        and unlink what they left in /dev/shm (a SIGKILLed child)."""
        with self._conns_lock:
            children = list(self._children)
        for pid in children:
            try:
                if os.waitpid(pid, os.WNOHANG)[0] == 0:
                    continue
            except ChildProcessError:
                pass  # already collected
            with self._conns_lock:
                self._children.discard(pid)
                names = self._ring_names.pop(pid, ())
            for name in names:
                if transport_lib.unlink_segment(name):
                    log.warning(
                        "EnvServer: swept shm segment %s of dead stream "
                        "process %d", name, pid,
                    )

    def _stop_children(self, grace_s: float = 2.0):
        """SIGTERM the stream children, SIGKILL what outlives the
        grace, and reap them all."""
        for sig in (signal.SIGTERM, signal.SIGKILL):
            with self._conns_lock:
                children = list(self._children)
            for pid in children:
                try:
                    os.kill(pid, sig)
                except ProcessLookupError:
                    pass
            deadline = time.monotonic() + grace_s
            while True:
                self._reap_children()
                with self._conns_lock:
                    if not self._children:
                        return
                if time.monotonic() > deadline:
                    break
                time.sleep(0.01)

    def start(self):
        """Non-blocking run() in a daemon thread."""
        if self._stream_processes:
            raise ValueError(
                "stream_processes forks, which is unsafe beside other "
                "threads: call run() from the main thread of a process "
                "of its own"
            )
        t = threading.Thread(target=self.run, daemon=True)
        t.start()
        with self._conns_lock:
            self._threads.append(t)

    def stop(self):
        with self._conns_lock:
            self._stopped = True
            self._running = False
            sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            sock.close()
        # Sever live streams too — stop() means stop, and clients with
        # reconnect enabled treat the cut as a transport failure.
        with self._conns_lock:
            conns = list(self._conns)
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            conn.close()
        self._stop_children()
        # Owner-side shm sweep: give the stream threads a moment to
        # close their rings (which unlinks them), then unlink whatever
        # is left. A thread wedged past the join window must not strand
        # segments in /dev/shm — unlink is safe under live mappings.
        # (Joins happen OUTSIDE the conns lock: a stream thread's
        # teardown takes it to deregister.)
        with self._conns_lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=2)
        with self._conns_lock:
            leftovers = [
                name
                for names in self._ring_names.values()
                for name in names
            ]
            self._ring_names.clear()
        for name in leftovers:
            if transport_lib.unlink_segment(name):
                log.warning(
                    "EnvServer stop(): swept leaked shm segment %s", name
                )
        if self._family == socket.AF_UNIX:
            try:
                os.unlink(self._target)
            except FileNotFoundError:
                pass

    def _serve_stream(self, conn: socket.socket, index: int, rings=None):
        """One stream, start to end, on this thread (or, in a stream's
        child, this process). `rings`: the shm ring pair the listener
        made before it forked; otherwise the stream makes its own."""
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass  # unix sockets
        stream = None
        env = None
        msg = None
        try:
            # For shm addresses this creates the per-connection rings
            # (unless `rings` came with the stream) and completes the
            # handshake BEFORE the env is built, so a client that never
            # acks can't leak an env instance.
            stream = transport_lib.server_transport(
                conn, shm=self._shm,
                obs_ring_bytes=self._obs_ring_bytes,
                act_ring_bytes=self._act_ring_bytes,
                max_frame_bytes=self._max_frame_bytes,
                rings=rings,
            )
            if self._shm:
                with self._conns_lock:
                    self._ring_names[conn] = stream.segment_names
            if self._seed_base is None:
                raw_env = self._env_init()
            else:
                raw_env = self._env_init(seed=self._seed_base + index)
            env = Environment(raw_env)
            # The initial Step doubles as the env spec: remote learners
            # probe num_actions/frame shape from it instead of having to
            # build the env locally (split deployments may not have the
            # env deps on the learner host).
            from torchbeast_tpu.envs import num_actions_of

            step = env.initial()
            initial = _step_to_message(step, time.monotonic_ns())
            initial["num_actions"] = num_actions_of(raw_env)
            stream.send(initial)
            while True:
                msg = stream.recv()
                recv_ns = time.monotonic_ns()
                if msg is None:
                    break  # client hung up
                if msg.get("type") != "action":
                    raise wire.WireError(f"Expected action, got {msg!r}")
                step = env.step(int(msg["action"]))
                stream.send(
                    _step_to_message(step, time.monotonic_ns(), recv_ns)
                )
        except (wire.WireError, ConnectionError, BrokenPipeError,
                TimeoutError) as e:
            log.debug("Stream ended: %s", e)
        except Exception as e:  # env raised: report to client, drop stream
            log.exception("Environment raised")
            try:
                if stream is not None:
                    stream.send({
                        "type": "error",
                        "message": f"{type(e).__name__}: {e}",
                    })
            except (OSError, wire.WireError):
                pass
        finally:
            msg = None  # drop transport-buffer views before close
            if env is not None:
                env.close()
            if stream is not None:
                stream.close()  # closes conn and, for shm, the rings
            else:
                conn.close()
            with self._conns_lock:
                if conn in self._conns:
                    self._conns.remove(conn)
                # stream.close() unlinked the rings; drop them from the
                # stop() sweep's ledger.
                self._ring_names.pop(conn, None)


def serve_once(env_init: Callable, address: str):
    """Convenience: construct and run (blocking)."""
    EnvServer(env_init, address).run()
