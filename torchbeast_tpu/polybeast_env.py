"""Env-server process group driver (the reference's polybeast_env.py role,
/root/reference/torchbeast/polybeast_env.py:61-89): spawn `num_servers`
processes, each serving environments on `{pipes_basename}.{i}` over the
framed-socket protocol, every accepted stream in a child process of its
own.

Run:  python -m torchbeast_tpu.polybeast_env --num_servers 4 --env Mock
"""

import argparse
import functools
import itertools
import logging
import multiprocessing as mp
import threading
import time

from torchbeast_tpu import telemetry
from torchbeast_tpu.resilience.backoff import Backoff
from torchbeast_tpu.utils import configure_logging
from torchbeast_tpu.utils.spawn import start_cpu_pinned

log = logging.getLogger("torchbeast_tpu.polybeast_env")


def make_parser():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--pipes_basename", default="unix:/tmp/torchbeast_tpu",
                        help="Basename for the env-server addresses "
                             "(unix:/path, host:baseport, or shm:/path "
                             "for shared-memory rings when the servers "
                             "are co-located with the learner host).")
    parser.add_argument("--num_servers", type=int, default=4,
                        help="How many addresses to serve on (one "
                             "listener process each). Addresses, not "
                             "parallelism: a listener forks a process "
                             "for every stream it accepts, so env "
                             "stepping spreads over the host's cores "
                             "however many actors share an address. A "
                             "stream's process costs host memory: a "
                             "few MB of its own on top of the pages it "
                             "shares with its listener.")
    parser.add_argument("--env", type=str, default="PongNoFrameskip-v4",
                        help="Gym environment (or Mock / Counting).")
    parser.add_argument("--env_seed", type=int, default=None,
                        help="Base seed for stochastic envs. Server i "
                             "seeds its streams from env_seed + i*1000 "
                             "+ stream index: every env instance draws a "
                             "distinct deterministic stream. Default: OS "
                             "entropy per env.")
    parser.add_argument("--max_server_restarts", type=int, default=10,
                        help="Supervision budget: dead env servers are "
                             "respawned on their address up to this many "
                             "times per group (actors bridge the gap "
                             "with their reconnect budget). 0 disables "
                             "restarts.")
    parser.add_argument("--native_server", action="store_true",
                        help="Serve with the C++ EnvServer (_tbt_core): "
                             "socket I/O and wire codec run GIL-free, the "
                             "GIL is taken only around env calls (the "
                             "reference's rpcenv.cc embedding).")
    return parser


def server_address(pipes_basename: str, index: int) -> str:
    """unix:/tmp/x and shm:/tmp/x -> {base}.{i};  host:port ->
    host:{port+i}."""
    if pipes_basename.startswith(("unix:", "shm:")):
        return f"{pipes_basename}.{index}"
    host, _, port = pipes_basename.rpartition(":")
    return f"{host}:{int(port) + index}"


def host_scoped_basename(pipes_basename: str, process_id: int,
                         num_servers: int) -> str:
    """Multi-host fan-out: each learner host gets its own address range so
    its actors connect to its OWN env servers (the reference's per-machine
    topology, polybeast_learner.py:436-444). unix paths get a -h{pid}
    suffix; host:port bases step by num_servers per host."""
    if process_id == 0:
        return pipes_basename
    if pipes_basename.startswith(("unix:", "shm:")):
        return f"{pipes_basename}-h{process_id}"
    host, _, port = pipes_basename.rpartition(":")
    return f"{host}:{int(port) + process_id * num_servers}"


def _serve(env_name: str, address: str, native: bool = False,
           seed_base=None):
    # Child process body. Spawn-context children re-import this module
    # but never run main(), so the child configures its own logging
    # (INFO lines like "EnvServer listening" would otherwise be lost
    # now that import no longer calls basicConfig).
    configure_logging()
    # SIGTERM (reap_group's terminate, a k8s preemption) must run this
    # child's teardown — for shm servers that is the owner-side ring
    # unlink sweep (EnvServer.stop). The default handler kills the
    # process without finally blocks, stranding /dev/shm segments.
    from torchbeast_tpu.utils import install_preemption_handler

    install_preemption_handler()
    # Import here: workers must never inherit JAX state.
    from torchbeast_tpu.envs import Environment, create_env

    make_env = functools.partial(create_env, env_name)
    if native:
        from torchbeast_tpu.runtime.native import import_native

        core = import_native()
        if core is None:
            raise RuntimeError(
                "--native_server requested but _tbt_core is not built; "
                "run scripts/build_native.sh"
            )
        env_init = make_env
        if seed_base is not None:
            # Fresh env per actor stream (the server calls env_init
            # once per connection, holding the GIL): stream s draws
            # seed_base + s. Reproducible seed SET; which stream gets
            # which seed follows connection order.
            counter = itertools.count()

            def env_init():
                return make_env(seed=seed_base + next(counter))
        core.EnvServer(env_init, address).run()
        return
    from torchbeast_tpu.runtime.env_server import EnvServer

    try:
        # One throwaway env, so that whatever an env imports on first
        # use (gymnasium, an emulator) is imported here, once, and not
        # in every stream's child.
        Environment(make_env()).close()
    except Exception:  # noqa: BLE001 - a stream reports it to its client
        log.exception("Could not build a first %s env", env_name)
    # This process is the server and nothing else (one thread, no JAX),
    # so every stream gets a child of its own, forked from here: an env
    # step then holds no other stream's GIL. The same seeds as above,
    # drawn by the listener in accept order.
    server = EnvServer(make_env, address, seed_base=seed_base,
                       stream_processes=True)
    try:
        server.run()
    except KeyboardInterrupt:
        log.info("Env server on %s preempted; cleaning up.", address)
    finally:
        # stop() severs live streams (ends their processes) and runs
        # the owner-side shm unlink sweep — the difference between a
        # preempted shm server and a /dev/shm leak.
        server.stop()


def reap_group(procs):
    """Terminate, join (bounded), then kill a spawned env-server group.
    Terminate-without-join strands spawn-context children when SIGTERM
    lands mid-bootstrap (observed: orphaned `spawn_main` processes after
    validation-failure runs) and leaves zombies otherwise."""
    for p in procs:
        p.terminate()
    for p in procs:
        p.join(timeout=5)
        if p.is_alive():
            p.kill()
            p.join(timeout=5)


class ServerSupervisor:
    """Owns an env-server process group and restarts members that die.

    The actor side has elastic reconnects (ActorPool's max_reconnects
    budget, runtime/actor_pool.py); this is the missing other half —
    someone to bring a dead server BACK. A member is respawned on its
    original address with its original seed base, so in-flight actors
    resume through their reconnect budget instead of exhausting it
    against a dead socket. `max_restarts` (per group, cumulative) caps
    crash-looping a deterministically broken env. The reference has no
    supervision at all: its env driver only LOGS a death
    (/root/reference/torchbeast/polybeast_env.py:61-75 serve loop; the
    gRPC server dying takes the slot down for good).
    """

    def __init__(self, flags, ctx_name: str = "spawn",
                 pipes_basename=None, env_seed=None, max_restarts=10,
                 poll_interval_s=1.0, backoff_factory=None,
                 stable_s=30.0):
        self._env_name = flags.env
        self._native = getattr(flags, "native_server", False)
        self._basename = pipes_basename or flags.pipes_basename
        if env_seed is None:
            env_seed = getattr(flags, "env_seed", None)
        self._env_seed = env_seed
        self._ctx = mp.get_context(ctx_name)
        self.max_restarts = max_restarts
        self.restarts = 0
        self._poll_interval_s = poll_interval_s
        self._stop = threading.Event()
        self._thread = None
        self._budget_logged = set()  # indices already error-logged
        # Jittered exponential backoff per slot: a crash-looping env
        # must not be respawned every poll tick (and N servers dying
        # together must not restart in lockstep). A member that stayed
        # up for `stable_s` earns its slot's backoff reset.
        self._backoff_factory = backoff_factory or (
            lambda: Backoff(base_s=0.25, cap_s=10.0)
        )
        self._stable_s = stable_s
        self._backoffs = {}  # slot -> Backoff
        self._respawn_at = {}  # slot -> monotonic time respawn is due
        self._spawned_at = {}  # slot -> monotonic time of last spawn
        self._tm_restarts = telemetry.get_registry().counter(
            "recovery.server_restarts"
        )
        # The group list is MUTATED IN PLACE on restart so callers that
        # captured it (the driver's reap paths) always see the current
        # members.
        self.processes = []
        try:
            for i in range(flags.num_servers):
                self.processes.append(self._spawn(i))
        except BaseException:
            # A partial group must not outlive a failed construction —
            # the caller never gets a handle to reap.
            reap_group(self.processes)
            raise
        log.info("Starting %d supervised env servers on %s",
                 len(self.processes), self._basename)

    def _spawn(self, i):
        address = server_address(self._basename, i)
        seed_base = (
            None if self._env_seed is None else self._env_seed + i * 1000
        )
        p = self._ctx.Process(
            target=_serve,
            args=(self._env_name, address, self._native, seed_base),
            daemon=True,
        )
        start_cpu_pinned(p)
        # beastlint: disable=RACE  single-writer map: the constructor fills every slot before start_watch() creates the watcher (Thread.start publishes); afterwards _spawn runs only on the watcher thread
        self._spawned_at[i] = time.monotonic()
        return p

    def start_watch(self):
        self._thread = threading.Thread(
            target=self._watch, daemon=True, name="server-supervisor"
        )
        self._thread.start()

    def _watch(self):
        while not self._stop.wait(self._poll_interval_s):
            for i, p in enumerate(self.processes):
                if p.is_alive() or self._stop.is_set():
                    continue
                if self.restarts >= self.max_restarts:
                    if i not in self._budget_logged:
                        log.error(
                            "Env server %d died (exit %s) and the "
                            "restart budget (%d) is exhausted; leaving "
                            "this slot down.",
                            i, p.exitcode, self.max_restarts,
                        )
                        self._budget_logged.add(i)
                    continue
                now = time.monotonic()
                due = self._respawn_at.get(i)
                if due is None:
                    # First poll to see this death: schedule the
                    # respawn through jittered backoff, not
                    # immediately — a crash-looping env must not be
                    # respawned every tick, and simultaneous deaths
                    # must not restart in lockstep.
                    bo = self._backoffs.setdefault(
                        i, self._backoff_factory()
                    )
                    if now - self._spawned_at.get(i, now) >= self._stable_s:
                        bo.reset()  # the last incarnation was healthy
                    delay = bo.next_delay()
                    self._respawn_at[i] = now + delay
                    log.warning(
                        "Env server %d died (exit %s); respawning on "
                        "its address in %.2fs (jittered backoff).",
                        i, p.exitcode, delay,
                    )
                    continue
                if now < due:
                    continue
                # beastlint: disable=RACE  watcher-only read-modify-write; the driver's monitor reads an int that is torn-free under the GIL and only informational (stats line / chaos accounting)
                self.restarts += 1
                log.warning(
                    "Env server %d: restarting on its address "
                    "(restart %d/%d).",
                    i, self.restarts, self.max_restarts,
                )
                try:
                    replacement = self._spawn(i)
                except Exception:
                    # Spawn failure (fd/pid pressure is exactly when
                    # servers die) must not kill the watcher thread —
                    # that would END supervision silently. Refund the
                    # attempt and retry after another backoff step.
                    self.restarts -= 1
                    self._respawn_at[i] = (
                        time.monotonic() + self._backoffs[i].next_delay()
                    )
                    log.exception(
                        "Respawn of env server %d failed; backing off.",
                        i,
                    )
                    continue
                del self._respawn_at[i]
                self._tm_restarts.inc()
                if self._stop.is_set():
                    # stop() landed while we were spawning: the reap may
                    # already have iterated the group, so this member
                    # must die here, not serve forever unreaped.
                    reap_group([replacement])
                    return
                # beastlint: disable=RACE  single-reference slot store under the GIL; readers (driver reap, chaos injector) tolerate a momentarily stale member and re-check is_alive()/pid before acting on it
                self.processes[i] = replacement

    def stop(self):
        """Stop restarting. Call BEFORE terminating the group, or the
        watcher resurrects members mid-reap."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                log.error(
                    "server-supervisor watcher did not stop within 10s "
                    "(a respawn may still be in flight); its in-flight "
                    "member reaps itself on insert."
                )


def main(flags):
    configure_logging()
    # SIGTERM must run the finally below: Python's default handler kills
    # the process without atexit/finally, orphaning the daemonic server
    # children (ppid 1, still serving their ports) — exactly what
    # `kill <group-launcher>` or a supervisor teardown sends. Observed:
    # every split-deployment test run leaked its server pair this way.
    import signal

    def _graceful_term(signum, frame):
        raise SystemExit(143)

    signal.signal(signal.SIGTERM, _graceful_term)

    supervisor = ServerSupervisor(
        flags, max_restarts=getattr(flags, "max_server_restarts", 10)
    )
    supervisor.start_watch()
    try:
        while True:
            time.sleep(10)
    except KeyboardInterrupt:
        pass
    finally:
        supervisor.stop()
        reap_group(supervisor.processes)


def cli():
    main(make_parser().parse_args())


if __name__ == "__main__":
    cli()
