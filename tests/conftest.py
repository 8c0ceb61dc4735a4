"""Test environment: the CPU backend with 8 virtual devices, so every
sharding/mesh test runs without an accelerator (SURVEY.md §4
implication; `__graft_entry__.dryrun_multichip` uses the same
mechanism). The variables are set before jax is imported — JAX reads
them at import, and nothing here touches a backend.

The native runtime is built here too, so that what the suite counts
does not depend on what an earlier build left in the tree.

The order is set here as well (ROADMAP D22): under the driver's
`--dist load` a worker takes CASES, in the order of collection, so a
file's cases spread over the workers and the collection's order is the
run's. The files go by their number of cases, most first: the driver
cuts the run at its time limit and counts the cases that finished, so
what is still running at the cut must be the files with the fewest
cases (replayed from a whole run's junit file under `--dist loadfile`,
whose rule this was, a run 10% over the limit counts 2,094 of 2,096 so,
and 1,110 with the files longest first). Among files of as many cases
the one-case whole-cell compiles (70-310 s each) would sit in the
alphabet's order: `pytest_configure` turns xdist's own reorder off and
`pytest_collection_modifyitems` makes the order with the ties longest
first, by `tests/file_seconds.json` (the files' sums in a whole run's
junit file; a file it does not know counts as long). The cases, and
their order inside a file, are what they were.
"""

import collections
import fcntl
import glob
import json
import os
import subprocess
import sys
import warnings

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from torchbeast_tpu.utils.xla_cache import use_compile_cache  # noqa: E402

# Persistent compilation cache. The driver's checkout starts cold every
# time, so what it does there is share each compile of 0.5 s or more
# between the six workers of ONE run; a one-op program is under that
# and is compiled again in every worker, which is why the tests trace
# whole modules (tests/family_scaffold.py). On a builder's machine a
# repeat run also skips what the last one compiled.
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def _ensure_native():
    """Make `_tbt_core` importable and no older than its sources: build
    it in place (~20 s) unless a fresh one is there. One builder at a
    time (xdist's workers each import this file): the others wait on
    the lock and find the build done. Where it cannot be built (no C++
    compiler) the native tests skip as they did, and the reason is a
    warning in the run's summary."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def fresh():
        sources = glob.glob(os.path.join(root, "csrc", "*"))
        sources.append(os.path.join(root, "setup.py"))
        built = glob.glob(os.path.join(root, "_tbt_core*.so"))
        return bool(built) and min(map(os.path.getmtime, built)) >= max(
            map(os.path.getmtime, sources)
        )

    if fresh():
        return
    os.makedirs(os.path.join(root, "build"), exist_ok=True)
    with open(os.path.join(root, "build", ".native_build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if fresh():
            return
        try:
            done = subprocess.run(
                [sys.executable, "setup.py", "build_ext", "--inplace"],
                cwd=root, capture_output=True, text=True, timeout=600,
            )
            failure = done.stderr[-2000:] if done.returncode else None
        except (OSError, subprocess.TimeoutExpired) as e:
            failure = repr(e)
    if failure:
        warnings.warn(
            "_tbt_core could not be built, so the native tests skip: "
            + failure
        )


_ensure_native()


_FILE_SECONDS = os.path.join(os.path.dirname(__file__), "file_seconds.json")


def pytest_configure(config):
    # Absent without xdist: the option is the plugin's.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    try:
        with open(_FILE_SECONDS) as f:
            seconds = json.load(f)
    except FileNotFoundError:
        seconds = {}

    def file_of(item):
        return item.nodeid.split("::", 1)[0]

    cases = collections.Counter(map(file_of, items))

    def most_cases_then_longest(item):
        path = file_of(item)
        return -cases[path], -seconds.get(path, float("inf"))

    # Stable: a file's cases stay together and in their order.
    items.sort(key=most_cases_then_longest)
