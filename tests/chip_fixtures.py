"""The described v5e every `tests/test_chip_compile*.py` compiles for,
in one place: the fixtures (`topo`, `one_chip`) and the shapes placed on
them. A test file imports the two fixtures by name; pytest then builds
them once a file (`scope="module"`).

A whole-cell compile is one AOT compile of the real cell, 150-210 s
with nothing to share or to jit away, so each has a file of its own
(`tests/test_chip_compile_<family>.py`): under `--dist loadfile` a file
is what a worker takes, and no file may be a worker's whole run. One
process at a time may load libtpu unless `ALLOW_MULTIPLE_LIBTPU_LOAD=1`
is in the environment, as it is in tier-1's command (ROADMAP.md): run
under xdist WITHOUT it, the files that lose the race skip at `topo`.

The persistent compile cache is off around these compiles: an
executable for a described device is written but cannot be read back
without a chip.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.compilation_cache import (  # noqa: E402
    compilation_cache,
)
from jax.sharding import SingleDeviceSharding  # noqa: E402

# The flagship learner's batch, and every cell's: unroll 80, 32 rows.
T, B, NUM_ACTIONS = 80, 32, 6


@pytest.fixture(scope="module")
def topo():
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def on(sharding, tree):
    """ShapeDtypeStructs of `tree` placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), jnp.result_type(x), sharding=sharding
        ),
        tree,
    )


def struct(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
