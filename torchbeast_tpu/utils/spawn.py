"""Starting child processes from a parent that may hold the accelerator.

A chip belongs to one process. Env workers and env servers are spawned
by the driver that holds it, and a spawn child re-imports `__main__`,
which imports jax. Nothing in a child asks JAX for a device, but that is
a property of today's imports; the child's environment makes it one of
the process: `JAX_PLATFORMS=cpu` is in place before the interpreter
starts, so a child that ever initialises a backend gets the CPU and
never a second claim on the chip.
"""

import os
import threading

_env_lock = threading.Lock()


def start_cpu_pinned(process) -> None:
    """`process.start()` with JAX pinned to the CPU platform in the
    child's environment. A spawn child inherits os.environ as it is at
    start(); the parent's own JAX read the variable at import, so the
    short override does not reach it. Serialised: supervisor threads
    respawn members concurrently with the main thread."""
    with _env_lock:
        saved = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            process.start()
        finally:
            if saved is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = saved
